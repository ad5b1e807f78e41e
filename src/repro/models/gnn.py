"""GraphSAGE (Hamilton et al. 2017) — mean aggregator, 3 execution regimes.

Message passing is ``jnp.take`` over an edge list + ``jax.ops.segment_sum``
scatter (JAX has no CSR SpMM — the segment formulation IS the system, per
the assignment note). Regimes:

  full_graph   — full-batch: edges sharded across every mesh axis via
                 shard_map; each device scatter-adds its edge shard into a
                 node-indexed partial, combined with one psum (the classic
                 1D edge-partitioned SpMM).
  minibatch    — sampled training (Reddit-scale): a host-side uniform
                 neighbor sampler (CSR, numpy) emits fixed-shape
                 (B, f1), (B, f1, f2) feature/neighbor tensors; the device
                 step is dense.
  molecule     — batched small graphs: padded (B, N, F) + (B, E, 2) with
                 vmap'd segment_sum.

BinSketch tie-in (DESIGN.md §4): adjacency rows are sparse binary vectors;
``neighborhood_sketches`` sketches them for Jaccard-similarity diagnostics
and near-duplicate-node detection using the paper's machinery unchanged.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..optim import adamw
from ..parallel.sharding import RULES, logical_to_spec
from .layers import init_dense

__all__ = ["SAGEConfig", "GraphSAGE", "NeighborSampler"]


@dataclasses.dataclass(frozen=True)
class SAGEConfig:
    name: str
    n_layers: int = 2
    d_hidden: int = 128
    d_feat: int = 602
    n_classes: int = 41
    fanouts: Tuple[int, ...] = (25, 10)
    dtype: object = jnp.float32


class GraphSAGE:
    def __init__(self, cfg: SAGEConfig, mesh: Mesh, rules: Optional[Dict] = None):
        self.cfg = cfg
        self.mesh = mesh
        self.rules = dict(RULES, **(rules or {}))
        self.dp_axes = tuple(a for a in self.rules.get("batch", ()) if a in mesh.axis_names)
        self.edge_axes = tuple(a for a in mesh.axis_names)  # edges over ALL axes

    # ------------------------------------------------------------- params
    def init(self, key) -> Dict:
        cfg = self.cfg
        dims = [cfg.d_feat] + [cfg.d_hidden] * cfg.n_layers
        ks = jax.random.split(key, cfg.n_layers + 1)
        layers = []
        for i in range(cfg.n_layers):
            k1, k2 = jax.random.split(ks[i])
            layers.append(
                {
                    "w_self": init_dense(k1, (dims[i], dims[i + 1]), cfg.dtype),
                    "w_neigh": init_dense(k2, (dims[i], dims[i + 1]), cfg.dtype),
                    "b": jnp.zeros((dims[i + 1],), cfg.dtype),
                }
            )
        return {
            "layers": layers,
            "head": init_dense(ks[-1], (cfg.d_hidden, cfg.n_classes), cfg.dtype),
        }

    def abstract_params(self):
        return jax.eval_shape(self.init, jax.random.PRNGKey(0))

    def logical_tree(self):
        layer = {"w_self": (None, "mlp"), "w_neigh": (None, "mlp"), "b": ("mlp",)}
        return {
            "layers": [dict(layer) for _ in range(self.cfg.n_layers)],
            "head": (None, None),
        }

    def param_specs(self):
        return jax.tree.map(
            lambda lg: logical_to_spec(lg, self.mesh, self.rules),
            self.logical_tree(),
            is_leaf=lambda x: isinstance(x, tuple)
            and all(a is None or isinstance(a, str) for a in x),
        )

    # ---------------------------------------------- full-graph propagation
    def _propagate(self, h: jax.Array, edges: jax.Array, n_nodes: int) -> jax.Array:
        """Mean-aggregate over in-edges. h (N, F); edges (E, 2) [src, dst],
        padded rows = (-1, -1). Edge-sharded shard_map + psum combine."""
        mesh = self.mesh
        axes = self.edge_axes

        def local(h_full, e):
            src, dst = e[:, 0], e[:, 1]
            valid = src >= 0
            srcs = jnp.where(valid, src, 0)
            dsts = jnp.where(valid, dst, 0)
            msg = jnp.take(h_full, srcs, axis=0) * valid[:, None].astype(h_full.dtype)
            agg = jax.ops.segment_sum(msg, dsts, num_segments=n_nodes)
            cnt = jax.ops.segment_sum(valid.astype(h_full.dtype), dsts, num_segments=n_nodes)
            agg = jax.lax.psum(agg, axes)
            cnt = jax.lax.psum(cnt, axes)
            return agg / jnp.maximum(cnt, 1.0)[:, None]

        fn = jax.shard_map(
            local,
            mesh=mesh,
            in_specs=(P(), P(axes, None)),
            out_specs=P(),
            check_vma=False,
        )
        return fn(h, edges)

    def _sage_layer(self, p, h_self, h_neigh_mean):
        z = h_self @ p["w_self"] + h_neigh_mean @ p["w_neigh"] + p["b"]
        h = jax.nn.relu(z)
        return h / jnp.maximum(jnp.linalg.norm(h, axis=-1, keepdims=True), 1e-6)

    def full_forward(self, params, feats, edges):
        h = feats
        n = feats.shape[0]
        for p in params["layers"]:
            h = self._sage_layer(p, h, self._propagate(h, edges, n))
        return h @ params["head"]

    # ------------------------------------------------- sampled (minibatch)
    def mini_forward(self, params, x0, x1, x2):
        """x0 (B,F) batch nodes; x1 (B,f1,F) hop-1; x2 (B,f1,f2,F) hop-2."""
        p1, p2 = params["layers"][0], params["layers"][1]
        h1_batch = self._sage_layer(p1, x0, jnp.mean(x1, axis=1))
        h1_hop1 = self._sage_layer(p1, x1, jnp.mean(x2, axis=2))
        h2 = self._sage_layer(p2, h1_batch, jnp.mean(h1_hop1, axis=1))
        return h2 @ params["head"]

    # ------------------------------------------------- batched small graphs
    def mol_forward(self, params, feats, edges):
        """feats (B, N, F); edges (B, E, 2) padded with -1."""
        n = feats.shape[1]

        def one(h, e):
            for p in params["layers"]:
                src, dst = e[:, 0], e[:, 1]
                valid = src >= 0
                msg = jnp.take(h, jnp.where(valid, src, 0), axis=0) * valid[:, None].astype(
                    h.dtype
                )
                agg = jax.ops.segment_sum(msg, jnp.where(valid, dst, 0), num_segments=n)
                cnt = jax.ops.segment_sum(valid.astype(h.dtype), jnp.where(valid, dst, 0), n)
                h = self._sage_layer(p, h, agg / jnp.maximum(cnt, 1.0)[:, None])
            return jnp.mean(h, axis=0) @ params["head"]  # graph-level readout

        return jax.vmap(one)(feats, edges)

    # ------------------------------------------------------------- steps
    def make_train_step(self, kind: str):
        opt_cfg = adamw.AdamWConfig(lr=1e-3, weight_decay=0.0)

        def loss_fn(params, batch):
            if kind == "full":
                logits = self.full_forward(params, batch["feats"], batch["edges"])
                labels, mask = batch["labels"], batch.get("mask")
            elif kind == "mini":
                logits = self.mini_forward(params, batch["x0"], batch["x1"], batch["x2"])
                labels, mask = batch["labels"], None
            else:  # molecule
                logits = self.mol_forward(params, batch["feats"], batch["edges"])
                labels, mask = batch["labels"], None
            logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            nll = -jnp.take_along_axis(logp, labels[:, None], axis=1)[:, 0]
            if mask is not None:
                return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
            return jnp.mean(nll)

        def train_step(params, opt_state, batch):
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
            new_p, new_o = adamw.update(opt_cfg, grads, opt_state, params)
            return new_p, new_o, {"loss": loss}

        return train_step, adamw.init


class NeighborSampler:
    """Host-side uniform neighbor sampler over a CSR adjacency (numpy)."""

    def __init__(self, n_nodes: int, edges: np.ndarray, seed: int = 0):
        """edges: (E, 2) [src, dst] — samples *in*-neighbors of dst."""
        order = np.argsort(edges[:, 1], kind="stable")
        self.dst_sorted_src = edges[order, 0].astype(np.int32)
        counts = np.bincount(edges[:, 1], minlength=n_nodes)
        self.offsets = np.concatenate([[0], np.cumsum(counts)]).astype(np.int64)
        self.n_nodes = n_nodes
        self.rng = np.random.default_rng(seed)

    def sample(self, nodes: np.ndarray, fanout: int) -> np.ndarray:
        """(B,) -> (B, fanout) sampled in-neighbors (with replacement;
        isolated nodes self-loop)."""
        lo = self.offsets[nodes]
        deg = self.offsets[nodes + 1] - lo
        r = self.rng.integers(0, np.maximum(deg, 1)[:, None], (len(nodes), fanout))
        idx = lo[:, None] + r
        out = self.dst_sorted_src[np.minimum(idx, len(self.dst_sorted_src) - 1)]
        return np.where(deg[:, None] > 0, out, nodes[:, None]).astype(np.int32)

    def sample_batch(self, nodes: np.ndarray, fanouts: Tuple[int, ...], feats: np.ndarray):
        """2-hop GraphSAGE batch: features for (batch, hop1, hop2)."""
        f1, f2 = fanouts[0], fanouts[1]
        n1 = self.sample(nodes, f1)  # (B, f1)
        n2 = self.sample(n1.reshape(-1), f2).reshape(len(nodes), f1, f2)
        return {
            "x0": feats[nodes],
            "x1": feats[n1],
            "x2": feats[n2],
        }


def neighborhood_sketches(edges: np.ndarray, n_nodes: int, psi: int, rho: float = 0.1, seed: int = 0):
    """BinSketch the adjacency rows (paper §IV applications: similarity of
    neighbor *sets*). Returns (packed sketches (n_nodes, W), config)."""
    from ..core import BinSketchConfig, make_mapping, sketch_indices

    deg = np.bincount(edges[:, 1], minlength=n_nodes)
    pad = int(min(max(deg.max(), 1), psi))
    rows = np.full((n_nodes, pad), -1, np.int32)
    fill = np.zeros(n_nodes, np.int64)
    for s, d in edges:
        if fill[d] < pad:
            rows[d, fill[d]] = s
            fill[d] += 1
    cfg = BinSketchConfig.from_sparsity(n_nodes, pad, rho)
    mapping = make_mapping(cfg, jax.random.PRNGKey(seed))
    return sketch_indices(cfg, mapping, jnp.asarray(rows)), cfg
