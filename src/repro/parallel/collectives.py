"""Hand-scheduled collectives: SP split-K decode attention and a ring
collective matmul (compute/comm overlap), both shard_map-native.

These are the places XLA's automatic SPMD either cannot express the
algorithm (partial-softmax combine) or schedules it poorly (all-gather
before a big matmul instead of a pipelined ring).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


__all__ = ["split_kv_decode_attention", "flash_combine", "ring_matmul"]


def flash_combine(o: jax.Array, m: jax.Array, l: jax.Array, axis: str):
    """Combine per-shard flash-attention partials across ``axis``.

    o: (..., d) un-normalized partial output = sum_j exp(s_j - m) v_j
    m: (...,)   per-shard running max
    l: (...,)   per-shard sum exp(s_j - m)
    One psum of (o*alpha, l*alpha) after a pmax of m — O(d) traffic per
    query vs O(seq) for gathering scores.
    """
    m_glob = jax.lax.pmax(m, axis)
    alpha = jnp.exp(m - m_glob)
    l_glob = jax.lax.psum(l * alpha, axis)
    o_glob = jax.lax.psum(o * alpha[..., None], axis)
    return o_glob / jnp.maximum(l_glob, 1e-30)[..., None]


def split_kv_decode_attention(
    q: jax.Array,  # (B, H, Dh)       replicated over `axis`
    k: jax.Array,  # (B, S_loc, G, Dh) KV shard local to this device
    v: jax.Array,  # (B, S_loc, G, Dh)
    axis: str,
    scale: float,
) -> jax.Array:
    """One decode step with the KV cache sequence-sharded over ``axis``.

    GQA: H q-heads read G kv-heads (H % G == 0). Each shard computes a
    flash-style partial over its S_loc keys; partials merge with
    ``flash_combine`` (a single psum). Call under shard_map.
    """
    b, h, dh = q.shape
    g = k.shape[2]
    rep = h // g
    qg = q.reshape(b, g, rep, dh)
    s = jnp.einsum("bgrd,bsgd->bgrs", qg.astype(jnp.float32), k.astype(jnp.float32)) * scale
    m = jnp.max(s, axis=-1)  # (B, G, rep)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bgrs,bsgd->bgrd", p, v.astype(jnp.float32))
    out = flash_combine(
        o.reshape(b, h, dh), m.reshape(b, h), l.reshape(b, h), axis
    )
    return out


def ring_matmul(x: jax.Array, w_shard: jax.Array, axis: str) -> jax.Array:
    """y = x @ W_full with W column-sharded over ``axis`` — the classic
    all-gather collective matmul, comm overlapped with compute.

    x: (B_loc, K) local batch shard (replicated K); w_shard: (K, N_loc)
    this device's column block of W. Instead of all-gathering W up front
    (serializing comm before compute), the ring rotates weight shards with
    ``ppermute`` while each already-received shard is being multiplied —
    at step t the device holds the shard that originated at
    ``(idx - t) mod n_dev`` and writes column block ``origin * N_loc``.
    Output: (B_loc, n_dev * N_loc) = x @ W. Call under shard_map.
    """
    n_dev = jax.lax.axis_size(axis)
    idx = jax.lax.axis_index(axis)
    perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]
    n_loc = w_shard.shape[1]
    out_dtype = jnp.promote_types(x.dtype, w_shard.dtype)

    def body(t, carry):
        out, w = carry
        origin = (idx - t) % n_dev
        # kick off the permute of the *next* shard, then do this chunk's
        # matmul — XLA/TPU overlaps the async collective-permute with it
        w_next = jax.lax.ppermute(w, axis, perm)
        chunk = (x @ w).astype(out_dtype)
        out = jax.lax.dynamic_update_slice(out, chunk, (0, origin * n_loc))
        return out, w_next

    out0 = jnp.zeros((x.shape[0], n_dev * n_loc), out_dtype)
    out, _ = jax.lax.fori_loop(0, n_dev, body, (out0, w_shard))
    return out


def make_sp_decode(mesh: Mesh, axis: str = "data"):
    """shard_map wrapper for split_kv_decode_attention on `mesh`."""

    def fn(q, k, v, scale):
        return split_kv_decode_attention(q, k, v, axis, scale)

    return jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(P(), P(None, axis, None, None), P(None, axis, None, None), None),
        out_specs=P(),
        check_vma=False,
    )
