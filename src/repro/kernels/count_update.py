"""Pallas TPU kernel: occupancy-counter construction as compare-reduce.

The counting BinSketch (``repro.core.counting``) needs, per document row,
the per-bin occupancy ``c[b, j] = |{p : bins[b, p] = j}|`` — a batched
histogram. The scatter-add reference is as TPU-hostile as the scatter-max
of the binary build, so this kernel reuses the compare-reduce formulation
of ``sketch_build`` (DESIGN.md §3) with the OR-reduce swapped for a sum:

    count[b, t] = sum_p( bins[b, p] == bin_base + t ),  t in [0, TILE)

a broadcast-compare + integer sum-reduce on the VPU. Pad slots (-1) never
match a non-negative target, so they contribute zero — the same padding
contract as every other kernel here.

Grid: (rows / TB, n_bins / TILE). Each program re-streams a (TB, P) slab
of bin ids (tiny next to the compare work) and writes a (TB, TILE) int32
tile of the dense counter matrix.

VMEM budget per program (defaults TB=8, TILE=512, P<=1024):
  bins slab   8*1024*4 B                 = 32 KiB
  compare     8*1024*512 bool (staged)   = 4 MiB     << 16 MiB VMEM
  out tile    8*512*4 B                  = 16 KiB
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["count_bins_kernel"]


def _kernel(bins_ref, out_ref, *, tile_bins: int):
    j = pl.program_id(1)
    bins = bins_ref[...]  # (TB, P) int32, pad = -1
    base = j * tile_bins
    # (TB, P, TILE) compare; pads (-1) never equal a non-negative target.
    # The compare stays bool (the sum accumulates straight into int32) —
    # an .astype(int32) here would stage a 4x larger intermediate and blow
    # the VMEM budget the header documents.
    targets = base + jax.lax.broadcasted_iota(jnp.int32, (1, 1, tile_bins), 2)
    hits = bins[:, :, None] == targets
    out_ref[...] = jnp.sum(hits, axis=1, dtype=jnp.int32)  # (TB, TILE)


def count_bins_kernel(
    bins: jax.Array,
    n_bins: int,
    *,
    block_rows: int = 8,
    tile_bins: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """``bins: (B, P)`` pre-mapped padded bin ids -> dense ``(B, n_bins)`` int32.

    B must be a multiple of ``block_rows`` and ``n_bins`` a multiple of
    ``tile_bins`` — ``ops.count_bins`` handles padding/cropping.
    """
    bsz, _ = bins.shape
    assert bsz % block_rows == 0 and n_bins % tile_bins == 0, (bsz, n_bins)
    grid = (bsz // block_rows, n_bins // tile_bins)
    return pl.pallas_call(
        functools.partial(_kernel, tile_bins=tile_bins),
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, bins.shape[1]), lambda i, j: (i, 0))],
        out_specs=pl.BlockSpec((block_rows, tile_bins), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((bsz, n_bins), jnp.int32),
        name="count_update",
        interpret=interpret,
    )(bins)
