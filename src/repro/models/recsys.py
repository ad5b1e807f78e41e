"""RecSys stack: sharded EmbeddingBag substrate + BST / xDeepFM / BERT4Rec /
AutoInt, with the paper's BinSketch integrated two ways (DESIGN.md §4):

  * ``sketched_features``: the 39-field categorical one-hot space is exactly
    the paper's §I.A setting; a BinSketch of the concatenated one-hot
    replaces the raw multi-hot as a dense {0,1}^N input block.
  * ``retrieval_sketch_step``: the 1M-candidate retrieval shape scored in
    sketch space (packed AND-popcount + Alg 1/3/4 epilogue) next to the
    exact dense-dot tower.

EmbeddingBag: JAX has no nn.EmbeddingBag — it is built here as
``jnp.take`` + masked segment-sum, with tables row-sharded over "model" via
shard_map (range-masked local gather + psum combine), so a 10^8-row table
never exists on one device.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..optim import adamw
from ..parallel.sharding import RULES, logical_to_spec
from .layers import init_dense

__all__ = ["RecsysConfig", "RecsysModel", "criteo_like_vocabs"]


def criteo_like_vocabs(n_fields: int = 39, scale: float = 1.0) -> Tuple[int, ...]:
    """Power-law field vocabularies, Criteo-shaped: a few huge id spaces,
    a body of medium ones, many small."""
    sizes = []
    for i in range(n_fields):
        if i < 3:
            sizes.append(int(40_000_000 * scale))
        elif i < 9:
            sizes.append(int(4_000_000 * scale))
        elif i < 19:
            sizes.append(int(100_000 * scale))
        else:
            sizes.append(max(int(1_000 * scale), 4))
    return tuple(sizes)


@dataclasses.dataclass(frozen=True)
class RecsysConfig:
    name: str
    kind: str  # "bst" | "xdeepfm" | "bert4rec" | "autoint"
    embed_dim: int
    field_vocabs: Tuple[int, ...] = ()  # ctr models: per-field vocab sizes
    n_items: int = 1_000_000  # sequential models: item vocab
    seq_len: int = 20
    n_blocks: int = 1
    n_heads: int = 8
    mlp_dims: Tuple[int, ...] = (1024, 512, 256)
    cin_dims: Tuple[int, ...] = (200, 200, 200)
    n_attn_layers: int = 3
    d_attn: int = 32
    n_negatives: int = 8192  # bert4rec sampled softmax
    n_mask: int = 20  # bert4rec masked positions
    dtype: object = jnp.float32

    @property
    def n_fields(self) -> int:
        return len(self.field_vocabs)


# =============================================================== embedding sub
def sharded_embedding_lookup(
    table: jax.Array,  # (V, D) row-sharded over `axis`
    ids: jax.Array,  # (B, ...) int32
    mesh: Mesh,
    dp_axes: Tuple[str, ...],
    axis: str = "model",
) -> jax.Array:
    """EmbeddingBag gather: range-masked local take + psum over the table
    shards. ids out of the local range contribute zeros; psum assembles.

    Tables too small to split evenly (< one row per shard granule) are
    replicated — a plain take, no collective (matches logical_tree, which
    marks them replicated)."""
    if table.shape[0] % mesh.shape[axis]:
        return jnp.take(table, ids, axis=0)

    def local(tab, ix):
        v_loc = tab.shape[0]
        lo = jax.lax.axis_index(axis) * v_loc
        loc = ix - lo
        valid = (loc >= 0) & (loc < v_loc)
        rows = jnp.take(tab, jnp.clip(loc, 0, v_loc - 1), axis=0)
        rows = rows * valid[..., None].astype(tab.dtype)
        return jax.lax.psum(rows, axis)

    ids_spec = P(dp_axes) if dp_axes else P(None)
    out_spec = P(dp_axes) if dp_axes else P(None)
    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axis, None), ids_spec),
        out_specs=out_spec,
        check_vma=False,
    )(table, ids)


def embedding_bag(
    table, ids, mask, mesh, dp_axes, axis: str = "model", mode: str = "sum"
):
    """Multi-hot bag over the trailing ids axis. ids (B, L), mask (B, L)."""
    rows = sharded_embedding_lookup(table, ids, mesh, dp_axes, axis)  # (B, L, D)
    s = jnp.sum(rows * mask[..., None].astype(rows.dtype), axis=-2)
    if mode == "mean":
        s = s / jnp.maximum(jnp.sum(mask, axis=-1, keepdims=True), 1.0)
    return s


# ================================================================== the model
class RecsysModel:
    def __init__(self, cfg: RecsysConfig, mesh: Mesh, rules: Optional[Dict] = None):
        self.cfg = cfg
        self.mesh = mesh
        self.rules = dict(RULES, **(rules or {}))
        self.dp_axes = tuple(a for a in self.rules.get("batch", ()) if a in mesh.axis_names)
        self.ep_axis = "model" if "model" in mesh.axis_names else mesh.axis_names[-1]

    # ------------------------------------------------------------- params
    def init(self, key) -> Dict:
        cfg = self.cfg
        ks = iter(jax.random.split(key, 256))
        p: Dict = {}
        if cfg.kind in ("xdeepfm", "autoint"):
            p["tables"] = [
                init_dense(next(ks), (v, cfg.embed_dim), cfg.dtype, scale=0.01)
                for v in cfg.field_vocabs
            ]
            if cfg.kind == "xdeepfm":
                p["linear"] = [
                    init_dense(next(ks), (v, 1), cfg.dtype, scale=0.01) for v in cfg.field_vocabs
                ]
                m = cfg.n_fields
                dims = [m] + list(cfg.cin_dims)
                p["cin"] = [
                    init_dense(next(ks), (dims[i] * m, dims[i + 1]), cfg.dtype)
                    for i in range(len(cfg.cin_dims))
                ]
                flat = cfg.n_fields * cfg.embed_dim
                mlp_dims = [flat, 400, 400]
                p["mlp"] = [
                    {
                        "w": init_dense(next(ks), (mlp_dims[i], mlp_dims[i + 1]), cfg.dtype),
                        "b": jnp.zeros((mlp_dims[i + 1],), cfg.dtype),
                    }
                    for i in range(2)
                ]
                p["head"] = init_dense(
                    next(ks), (sum(cfg.cin_dims) + 400 + 1, 1), cfg.dtype
                )
            else:  # autoint
                d = cfg.embed_dim
                p["attn"] = [
                    {
                        "w_q": init_dense(next(ks), (d if i == 0 else cfg.d_attn, cfg.d_attn), cfg.dtype),
                        "w_k": init_dense(next(ks), (d if i == 0 else cfg.d_attn, cfg.d_attn), cfg.dtype),
                        "w_v": init_dense(next(ks), (d if i == 0 else cfg.d_attn, cfg.d_attn), cfg.dtype),
                        "w_res": init_dense(next(ks), (d if i == 0 else cfg.d_attn, cfg.d_attn), cfg.dtype),
                    }
                    for i in range(cfg.n_attn_layers)
                ]
                p["head"] = init_dense(next(ks), (cfg.n_fields * cfg.d_attn, 1), cfg.dtype)
        else:  # bst / bert4rec: item-sequence models
            d = cfg.embed_dim
            p["items"] = init_dense(next(ks), (cfg.n_items, d), cfg.dtype, scale=0.01)
            p["pos"] = init_dense(next(ks), (cfg.seq_len + 1, d), cfg.dtype, scale=0.01)
            p["blocks"] = [
                {
                    "w_qkv": init_dense(next(ks), (d, 3 * d), cfg.dtype),
                    "w_o": init_dense(next(ks), (d, d), cfg.dtype),
                    "ln1": jnp.ones((d,), cfg.dtype),
                    "ln2": jnp.ones((d,), cfg.dtype),
                    "w_ff1": init_dense(next(ks), (d, 4 * d), cfg.dtype),
                    "w_ff2": init_dense(next(ks), (4 * d, d), cfg.dtype),
                }
                for _ in range(cfg.n_blocks)
            ]
            if cfg.kind == "bst":
                # sequence fed to the MLP = (seq_len-1) history + 1 target
                dims = [cfg.seq_len * d] + list(cfg.mlp_dims)
                p["mlp"] = [
                    {
                        "w": init_dense(next(ks), (dims[i], dims[i + 1]), cfg.dtype),
                        "b": jnp.zeros((dims[i + 1],), cfg.dtype),
                    }
                    for i in range(len(cfg.mlp_dims))
                ]
                p["head"] = init_dense(next(ks), (cfg.mlp_dims[-1], 1), cfg.dtype)
        return p

    def abstract_params(self):
        return jax.eval_shape(self.init, jax.random.PRNGKey(0))

    def logical_tree(self):
        """Embedding tables row-sharded over 'model'; everything else
        replicated (the dense parts of these models are tiny)."""
        p = self.abstract_params()
        n_shards = self.mesh.shape.get(self.ep_axis, 1)
        tbl = lambda leaf: ("table", None) if leaf.shape[0] % n_shards == 0 else (None, None)
        lg = jax.tree.map(lambda leaf: (None,) * leaf.ndim, p)
        if "tables" in p:
            lg["tables"] = [tbl(t) for t in p["tables"]]
        if "linear" in p:
            lg["linear"] = [tbl(t) for t in p["linear"]]
        if "items" in p:
            lg["items"] = tbl(p["items"])
        return lg

    def param_specs(self):
        return jax.tree.map(
            lambda t: logical_to_spec(t, self.mesh, self.rules),
            self.logical_tree(),
            is_leaf=lambda x: isinstance(x, tuple)
            and all(a is None or isinstance(a, str) for a in x),
        )

    # ------------------------------------------------------------ forwards
    def _field_embeds(self, params, sparse_ids):
        """sparse_ids (B, F) -> (B, F, D) via per-field sharded lookup."""
        cols = [
            sharded_embedding_lookup(t, sparse_ids[:, i], self.mesh, self.dp_axes, self.ep_axis)
            for i, t in enumerate(params["tables"])
        ]
        return jnp.stack(cols, axis=1)

    def _xdeepfm(self, params, batch):
        cfg = self.cfg
        x0 = self._field_embeds(params, batch["sparse"])  # (B, m, D)
        # linear term
        lin = sum(
            sharded_embedding_lookup(t, batch["sparse"][:, i], self.mesh, self.dp_axes, self.ep_axis)[:, 0]
            for i, t in enumerate(params["linear"])
        )[:, None]
        # CIN
        xk = x0
        pooled = []
        for w in params["cin"]:
            z = jnp.einsum("bhd,bmd->bhmd", xk, x0)  # (B, Hk, m, D)
            b, hk, m, d = z.shape
            xk = jnp.einsum("bhmd,hmn->bnd", z, w.reshape(hk, m, -1))
            pooled.append(jnp.sum(xk, axis=-1))
        cin_out = jnp.concatenate(pooled, axis=-1)
        # DNN
        h = x0.reshape(x0.shape[0], -1)
        for lyr in params["mlp"]:
            h = jax.nn.relu(h @ lyr["w"] + lyr["b"])
        feats = jnp.concatenate([cin_out, h, lin], axis=-1)
        return (feats @ params["head"])[:, 0]

    def _autoint(self, params, batch):
        cfg = self.cfg
        h = self._field_embeds(params, batch["sparse"])  # (B, m, D)
        nh = 2
        for lyr in params["attn"]:
            q = h @ lyr["w_q"]
            k = h @ lyr["w_k"]
            v = h @ lyr["w_v"]
            b, m, da = q.shape
            dh = da // nh
            qh = q.reshape(b, m, nh, dh)
            kh = k.reshape(b, m, nh, dh)
            vh = v.reshape(b, m, nh, dh)
            s = jnp.einsum("bmhd,bnhd->bhmn", qh, kh) / jnp.sqrt(float(dh))
            a = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("bhmn,bnhd->bmhd", a, vh).reshape(b, m, da)
            h = jax.nn.relu(o + h @ lyr["w_res"])
        return (h.reshape(h.shape[0], -1) @ params["head"])[:, 0]

    def _seq_encode(self, params, seq_ids, mask):
        """Shared transformer trunk for bst/bert4rec. (B,S) -> (B,S,D)."""
        cfg = self.cfg
        d = cfg.embed_dim
        h = sharded_embedding_lookup(params["items"], seq_ids, self.mesh, self.dp_axes, self.ep_axis)
        h = h + params["pos"][: seq_ids.shape[1]][None]
        for blk in params["blocks"]:
            hn = _layernorm(h, blk["ln1"])
            qkv = hn @ blk["w_qkv"]
            q, k, v = jnp.split(qkv, 3, axis=-1)
            nh = cfg.n_heads
            b, s, _ = q.shape
            dh = d // nh
            qh = q.reshape(b, s, nh, dh)
            kh = k.reshape(b, s, nh, dh)
            vh = v.reshape(b, s, nh, dh)
            sc = jnp.einsum("bqhd,bkhd->bhqk", qh, kh) / jnp.sqrt(float(dh))
            sc = jnp.where(mask[:, None, None, :], sc, -1e30)
            a = jax.nn.softmax(sc, axis=-1)
            o = jnp.einsum("bhqk,bkhd->bqhd", a, vh).reshape(b, s, d)
            h = h + o @ blk["w_o"]
            hn = _layernorm(h, blk["ln2"])
            h = h + jax.nn.gelu(hn @ blk["w_ff1"]) @ blk["w_ff2"]
        return h

    def _bst(self, params, batch):
        """behavior seq (B, S-1) + target item (B,) -> CTR logit (B,)."""
        seq = jnp.concatenate([batch["hist"], batch["target"][:, None]], axis=1)
        mask = jnp.concatenate(
            [batch["hist_mask"], jnp.ones_like(batch["target"][:, None], dtype=bool)], axis=1
        )
        h = self._seq_encode(params, seq, mask)
        h = h.reshape(h.shape[0], -1)
        for lyr in params["mlp"]:
            h = jax.nn.leaky_relu(h @ lyr["w"] + lyr["b"])
        return (h @ params["head"])[:, 0]

    def _bert4rec_loss(self, params, batch, key):
        """Masked-item prediction with sampled softmax over n_negatives."""
        cfg = self.cfg
        h = self._seq_encode(params, batch["seq"], batch["mask"])  # (B,S,D)
        pos_idx = batch["mask_pos"]  # (B, n_mask)
        hid = jnp.take_along_axis(h, pos_idx[..., None], axis=1)  # (B,n_mask,D)
        labels = batch["mask_labels"]  # (B, n_mask)
        negs = jax.random.randint(key, (cfg.n_negatives,), 0, cfg.n_items)
        neg_emb = sharded_embedding_lookup(params["items"], negs, self.mesh, (), self.ep_axis)
        pos_emb = sharded_embedding_lookup(
            params["items"], labels, self.mesh, self.dp_axes, self.ep_axis
        )
        pos_logit = jnp.sum(hid * pos_emb, axis=-1)  # (B,n_mask)
        neg_logit = jnp.einsum("bmd,nd->bmn", hid, neg_emb)
        lse = jax.nn.logsumexp(
            jnp.concatenate([pos_logit[..., None], neg_logit], axis=-1).astype(jnp.float32),
            axis=-1,
        )
        return jnp.mean(lse - pos_logit.astype(jnp.float32))

    # -------------------------------------------------------------- steps
    def score(self, params, batch):
        if self.cfg.kind == "xdeepfm":
            return self._xdeepfm(params, batch)
        if self.cfg.kind == "autoint":
            return self._autoint(params, batch)
        if self.cfg.kind == "bst":
            return self._bst(params, batch)
        # bert4rec serve: next-item scores against provided candidates
        h = self._seq_encode(params, batch["seq"], batch["mask"])[:, -1]  # (B,D)
        cand = sharded_embedding_lookup(
            params["items"], batch["candidates"], self.mesh, self.dp_axes, self.ep_axis
        )  # (B, C, D)
        return jnp.einsum("bd,bcd->bc", h, cand)

    def make_train_step(self):
        opt_cfg = adamw.AdamWConfig(lr=1e-3, weight_decay=0.0)
        kind = self.cfg.kind

        def loss_fn(params, batch):
            if kind == "bert4rec":
                return self._bert4rec_loss(params, batch, jax.random.PRNGKey(0))
            logit = self.score(params, batch)
            y = batch["label"].astype(jnp.float32)
            z = logit.astype(jnp.float32)
            return jnp.mean(jnp.maximum(z, 0) - z * y + jnp.log1p(jnp.exp(-jnp.abs(z))))

        def train_step(params, opt_state, batch):
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
            new_p, new_o = adamw.update(opt_cfg, grads, opt_state, params)
            return new_p, new_o, {"loss": loss}

        return train_step, adamw.init

    def make_serve_step(self):
        return lambda params, batch: self.score(params, batch)

    # ----------------------------------------------------------- retrieval
    def make_retrieval_step(self):
        """1 query vs n_candidates: dense-dot tower + top-k (batched matmul,
        item embeddings row-sharded; local partial top-k then merge)."""
        cfg = self.cfg
        k_top = 100

        def retrieval(params, query):
            """query: {"user_vec" (B, D), "cand_emb" (C, D)}; candidate
            embeddings row-sharded over 'model' (C = n_candidates)."""
            table = query["cand_emb"]
            u = query["user_vec"]

            def local(tab, uu):
                s = uu @ tab.T  # (B, V_loc)
                sc, ix = jax.lax.top_k(s, k_top)
                lo = jax.lax.axis_index(self.ep_axis) * tab.shape[0]
                ix = ix + lo
                sc_all = jax.lax.all_gather(sc, self.ep_axis, axis=1, tiled=True)
                ix_all = jax.lax.all_gather(ix, self.ep_axis, axis=1, tiled=True)
                sc2, pos = jax.lax.top_k(sc_all, k_top)
                return sc2, jnp.take_along_axis(ix_all, pos, axis=1)

            return jax.shard_map(
                local,
                mesh=self.mesh,
                in_specs=(P(self.ep_axis, None), P(None, None)),
                out_specs=(P(None, None), P(None, None)),
                check_vma=False,
            )(table, u)

        return retrieval

    def make_retrieval_sketch_step(self, n_bins: int):
        """BinSketch-space retrieval (the paper's ranking experiment at the
        1M-candidate shape): the engine's shared shard_topk body — packed
        popcount + Alg-3 epilogue + local top-k + O(k·devices) merge.
        Candidates sharded over 'model'; oracle scoring path (= kernels/ref)
        so it lowers for the TPU dry-run. When the serving store's cached
        fill counts ride along as ``query["corpus_fills"]`` the per-query
        O(C·W) corpus popcount disappears."""
        from ..engine import shard_topk

        k_top = 100
        ep = self.ep_axis

        def retrieval(params, query):
            """query: {"sketch" (B, W), "corpus_sketches" (C, W),
            optional "corpus_fills" (C,) from the SketchStore cache}."""
            corpus = query["corpus_sketches"]  # (C, W) uint32
            fills = query.get("corpus_fills")

            def local(cand, qs, *cand_fills):
                return shard_topk(
                    qs, cand, n_bins, "jaccard", k_top, ep,
                    cand_fills=cand_fills[0] if cand_fills else None,
                )

            in_specs = [P(ep, None), P(None, None)]
            operands = [corpus, query["sketch"]]
            if fills is not None:
                in_specs.append(P(ep))
                operands.append(fills)
            return jax.shard_map(
                local,
                mesh=self.mesh,
                in_specs=tuple(in_specs),
                out_specs=(P(None, None), P(None, None)),
                check_vma=False,
            )(*operands)

        return retrieval


def _layernorm(x, w, eps=1e-6):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w
