"""Pallas TPU kernel: fused hash + compare-OR sketch construction.

``sketch_build`` takes pre-mapped bin ids — fine when the pi table exists.
At tera-scale d (the paper's motivating regime) there is no table: the map
is a multiply-shift hash. Mapping on the host costs one extra HBM round
trip of the (B, P) int32 bins; this kernel computes

    bin = ((a * idx + b) mod 2^32) mod N

inside the kernel body (VPU integer ops) and feeds the same compare-OR
pack (``sketch_build.or_pack_tile``), so raw indices stream from HBM
exactly once. The coefficients arrive as a (2,) uint32 operand in SMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .sketch_build import or_pack_tile

__all__ = ["hash_build_kernel"]


def _kernel(coeffs_ref, idx_ref, out_ref, *, tile_words: int, n_bins: int):
    a = coeffs_ref[0]
    b = coeffs_ref[1]

    def to_bins(idx):  # (8, TB) int32 raw feature indices, pad = -1
        h = a * idx.astype(jnp.uint32) + b  # wraps mod 2^32
        bins = (h % jnp.uint32(n_bins)).astype(jnp.int32)
        return jnp.where(idx >= 0, bins, -1)

    out_ref[...] = or_pack_tile(idx_ref, tile_words, to_bins)


def hash_build_kernel(
    idx_t: jax.Array,
    coeffs: jax.Array,
    n_bins: int,
    *,
    block_rows: int = 128,
    tile_words: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """``idx_t: (P, B)`` transposed raw indices (pad -1, P a multiple of
    8), ``coeffs: (2,)`` uint32 multiply-shift pair -> packed
    ``(B, ceil(n_bins/32))`` int32 sketches (``ops.hash_build_sketch``
    transposes in and bitcasts out)."""
    p, bsz = idx_t.shape
    assert p % 8 == 0, p
    n_words = (n_bins + 31) // 32
    grid = (pl.cdiv(bsz, block_rows), pl.cdiv(n_words, tile_words))
    return pl.pallas_call(
        functools.partial(_kernel, tile_words=tile_words, n_bins=n_bins),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((p, block_rows), lambda i, j: (0, i)),
        ],
        out_specs=pl.BlockSpec((block_rows, tile_words), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((bsz, n_words), jnp.int32),
        name="hash_build",
        interpret=interpret,
    )(coeffs, idx_t)
