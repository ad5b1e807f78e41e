"""Minimal pipeline-parallel stage wrapper over a mesh axis (GPipe-style).

Not the default layout (DESIGN.md §5: at 2 pods, DP-over-pod with
compressed gradient sync beats PP on bubble math), but provided and
unit-tested so the multi-pod mesh has a working PP option:

    y = pipeline_apply(stage_fns, params_per_stage, x, mesh, axis="pod",
                       n_microbatches=m)

Each device along ``axis`` owns one stage; microbatches stream through
with ``lax.ppermute`` boundary transfers. Bubble fraction is
(S-1)/(m+S-1) as usual.
"""

from __future__ import annotations

from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


__all__ = ["pipeline_apply"]


def pipeline_apply(
    stage_fn: Callable,  # (params, x) -> y, same signature every stage
    stage_params: Sequence,  # list of per-stage param pytrees, len == axis size
    x: jax.Array,  # (n_micro, B_micro, ...) microbatched input
    mesh: Mesh,
    axis: str = "pod",
    check: bool = False,
) -> jax.Array:
    """Runs x through stages laid along ``axis``; returns final-stage output
    in microbatch order (n_micro, B_micro, ...)."""
    n_stage = mesh.shape[axis]
    n_micro = x.shape[0]
    if len(stage_params) != n_stage:
        raise ValueError(f"need {n_stage} stage param trees, got {len(stage_params)}")

    # stack per-stage params so shard_map can split them along `axis`
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *stage_params)
    perm = [(i, (i + 1) % n_stage) for i in range(n_stage)]

    def shard_fn(params, xs):
        params = jax.tree.map(lambda p: p[0], params)  # this stage's params
        stage = jax.lax.axis_index(axis)
        total = n_micro + n_stage - 1

        def step(t, carry):
            buf, out = carry  # buf: (B_micro, ...) current stage input
            mb = t - stage
            # stage 0 feeds itself from x; others consume the permuted buf
            feed = jax.lax.dynamic_index_in_dim(
                xs, jnp.clip(mb, 0, n_micro - 1), axis=0, keepdims=False
            )
            cur = jnp.where(stage == 0, feed, buf)
            y = stage_fn(params, cur)
            active = (mb >= 0) & (mb < n_micro)
            y = jnp.where(active, y, jnp.zeros_like(y))
            # last stage records finished microbatches
            out = jax.lax.cond(
                active & (stage == n_stage - 1),
                lambda o: jax.lax.dynamic_update_index_in_dim(o, y, jnp.clip(mb, 0, n_micro - 1), 0),
                lambda o: o,
                out,
            )
            buf = jax.lax.ppermute(y, axis, perm)
            return buf, out

        buf0 = jnp.zeros_like(xs[0])
        out0 = jnp.zeros_like(xs)
        _, out = jax.lax.fori_loop(0, total, step, (buf0, out0))
        # only the last stage holds real outputs; broadcast them
        out = jax.lax.psum(jnp.where(stage == n_stage - 1, out, jnp.zeros_like(out)), axis)
        return out

    fn = jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        check_vma=check,
    )
    return fn(stacked, x)
