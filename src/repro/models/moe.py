"""Expert-parallel MoE (token-choice top-k, capacity factor) via shard_map.

Layout (DESIGN.md §5): token activations are batch-sharded over the DP axes
and *replicated* over the TP/EP axis "model"; experts are sharded over
"model". Because every model-column device already holds the tokens, the
dispatch is entirely local — each device gathers the tokens routed to ITS
experts into a capacity buffer, runs its expert SwiGLUs, and the combine is
one psum over "model" (same traffic as a TP MLP all-reduce). No all-to-all
is needed in this replicated-activation regime; that is the point of
choosing it.

Dispatch is scatter-based (argsort-free, one-hot cumsum for within-expert
positions), looped over the k routing slots so the transient is one
(T_loc, d) buffer per slot instead of a (T_loc*k, d) gather. Dropped
tokens (over capacity) fall into a trash row, standard token-choice
semantics.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from .layers import init_dense, swiglu_apply

__all__ = ["MoEConfig", "init_moe", "logical_moe", "moe_apply"]


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_ff_expert: int
    n_shared: int = 0  # shared experts (always-on), DeepSeek/Kimi style
    first_dense: int = 1  # leading dense layers
    capacity_factor: float = 1.25
    router_aux_coef: float = 0.001


def init_moe(key, cfg: MoEConfig, d_model: int, dtype) -> Dict:
    ks = jax.random.split(key, 5)
    e, f = cfg.n_experts, cfg.d_ff_expert
    p = {
        "w_router": init_dense(ks[0], (d_model, e), jnp.float32),
        "w_gate": init_dense(ks[1], (e, d_model, f), dtype),
        "w_up": init_dense(ks[2], (e, d_model, f), dtype),
        "w_down": init_dense(ks[3], (e, f, d_model), dtype),
    }
    if cfg.n_shared:
        fs = cfg.n_shared * f
        kk = jax.random.split(ks[4], 3)
        p["shared"] = {
            "w_gate": init_dense(kk[0], (d_model, fs), dtype),
            "w_up": init_dense(kk[1], (d_model, fs), dtype),
            "w_down": init_dense(kk[2], (fs, d_model), dtype),
        }
    return p


def logical_moe(cfg: MoEConfig) -> Dict:
    # expert_ff is () under training rules (FSDP on embed) and ("data",)
    # under MoE decode rules (weights fully resident: EP over model + TP
    # over data on the expert hidden dim; §Perf-2)
    lg = {
        "w_router": ("embed", None),
        "w_gate": ("experts", "embed", "expert_ff"),
        "w_up": ("experts", "embed", "expert_ff"),
        "w_down": ("experts", "expert_ff", "embed"),
    }
    if cfg.n_shared:
        lg["shared"] = {
            "w_gate": ("embed", "mlp"),
            "w_up": ("embed", "mlp"),
            "w_down": ("mlp", "embed"),
        }
    return lg


def _local_moe(
    x, w_router, w_gate, w_up, w_down, *, cfg: MoEConfig, ep_axis: str, dp_axes, ff_axes=()
):
    """Per-device body. x: (T_loc, d) tokens (replicated over ep_axis and
    ff_axes); w_*: this device's (E_loc, ..., f_loc) expert shards (f_loc
    sharded over ff_axes in decode mode). Returns (y, aux_loss)."""
    t_loc, d = x.shape
    e_loc = w_gate.shape[0]
    n_shards = jax.lax.axis_size(ep_axis)
    e_total = e_loc * n_shards
    mi = jax.lax.axis_index(ep_axis)
    lo = mi * e_loc

    logits = x.astype(jnp.float32) @ w_router  # (T_loc, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, ids = jax.lax.top_k(probs, cfg.top_k)  # (T_loc, k)
    gate_vals = gate_vals / jnp.maximum(jnp.sum(gate_vals, -1, keepdims=True), 1e-9)

    # switch-style aux loss, averaged over the DP shards (ep replicas agree)
    frac = jnp.mean(jax.nn.one_hot(ids[:, 0], e_total, dtype=jnp.float32), axis=0)
    aux = e_total * jnp.sum(frac * jnp.mean(probs, axis=0))
    if dp_axes:
        aux = jax.lax.pmean(aux, dp_axes)

    capacity = max(int(t_loc * cfg.top_k / e_total * cfg.capacity_factor), 4)

    # within-expert positions for every (token, slot) assignment, local experts
    local_ids = ids - lo  # (T_loc, k)
    valid = (local_ids >= 0) & (local_ids < e_loc)
    flat_ids = jnp.where(valid, local_ids, e_loc).reshape(-1)  # trash row = e_loc
    oh = jax.nn.one_hot(flat_ids, e_loc + 1, dtype=jnp.int32)  # (T_loc*k, E_loc+1)
    pos = (jnp.cumsum(oh, axis=0) - 1) * oh
    pos_flat = jnp.sum(pos, axis=-1).reshape(t_loc, cfg.top_k)  # (T_loc, k)
    keep = valid & (pos_flat < capacity)
    eid = jnp.where(keep, local_ids, e_loc)
    slot = jnp.where(keep, pos_flat, capacity)

    # dispatch, one routing slot at a time (bounds transients at (T_loc, d))
    buf = jnp.zeros((e_loc + 1, capacity + 1, d), x.dtype)
    for s in range(cfg.top_k):
        buf = buf.at[eid[:, s], slot[:, s]].set(x)
    buf = buf[:e_loc, :capacity]  # (E_loc, C, d)

    # expert SwiGLU; with ff_axes the hidden dim is a local f-slice and the
    # down-projection yields an f-partial summed in the combine psum below
    gate_act = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, w_gate))
    up = jnp.einsum("ecd,edf->ecf", buf, w_up)
    y = jnp.einsum("ecf,efd->ecd", gate_act * up, w_down).astype(x.dtype)  # (E_loc,C,d)

    # combine
    y = jnp.concatenate([y, jnp.zeros((1, capacity, d), y.dtype)], axis=0)
    y = jnp.concatenate([y, jnp.zeros((e_loc + 1, 1, d), y.dtype)], axis=1)
    out = jnp.zeros((t_loc, d), jnp.float32)
    for s in range(cfg.top_k):
        out = out + y[eid[:, s], slot[:, s]].astype(jnp.float32) * (
            gate_vals[:, s] * keep[:, s]
        )[:, None]
    out = jax.lax.psum(out, (ep_axis,) + tuple(ff_axes))
    return out.astype(x.dtype), aux


def moe_apply(
    params: Dict,
    x: jax.Array,  # (B, S, d) or (T, d)
    cfg: MoEConfig,
    mesh: Mesh,
    dp_axes: Tuple[str, ...],
    ep_axis: str = "model",
    ff_axes: Tuple[str, ...] = (),
) -> Tuple[jax.Array, jax.Array]:
    """Returns (y same shape as x, scalar aux loss).

    ``ff_axes``: mesh axes sharding the expert hidden dim (decode-serving
    layout: weights fully resident EP x TP, no per-step FSDP re-gather —
    §Perf-2). Empty under training rules (hidden dim whole, embed dim
    FSDP-sharded outside the shard_map).
    """
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    tok_spec = P(dp_axes, None) if dp_axes else P(None, None)
    ff = tuple(a for a in ff_axes if a in mesh.axis_names)
    ff_spec = ff if ff else None

    up_spec = P(ep_axis, None, ff_spec)
    down_spec = P(ep_axis, ff_spec, None)

    fn = jax.shard_map(
        lambda xs, wr, wg, wu, wd: _local_moe(
            xs, wr, wg, wu, wd, cfg=cfg, ep_axis=ep_axis, dp_axes=dp_axes, ff_axes=ff
        ),
        mesh=mesh,
        in_specs=(tok_spec, P(None, None), up_spec, up_spec, down_spec),
        out_specs=(tok_spec, P()),
        check_vma=False,
    )
    y, aux = fn(x2, params["w_router"], params["w_gate"], params["w_up"], params["w_down"])
    if cfg.n_shared:
        y = y + swiglu_apply(params["shared"], x2)
    return y.reshape(shape), jnp.mean(aux)
