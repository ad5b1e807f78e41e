"""Pallas TPU kernel: N→N' sketch re-bucketing as a funnel-shift OR-fold.

Segment distillation (DESIGN.md §11) re-sketches a sealed slab from width
N to a smaller N' without touching raw documents. Because folding composes
in sketch space — new bin ``j' = j mod N'`` — the packed fold is, per
source *chunk* ``q`` (bits ``[q·N', (q+1)·N')``), a bit-level extraction
of N' consecutive bits OR-ed into the accumulator. Consecutive bits of a
chunk live in **consecutive words** of the packed row at a fixed bit
offset, so the extraction is a classic funnel shift:

    out[w'] |= (src[lo + w'] >> s) | (src[lo + w' + 1] << (32 - s))
    lo = (q·N') // 32,  s = (q·N') % 32

— two contiguous static word slices, two shifts, one OR per chunk; no
gather, no unpacking to dense bits. Bits of the extraction window beyond
N' (they belong to chunk q+1) are masked once at the end: the mask is
position-based and identical for every chunk, and OR commutes with it.

Grid: (rows / TB,). Each program reads a (TB, W_pad) slab of source words
(the wrapper pads the word axis so every chunk's window is in range and
zeroes source bits >= N) and writes the (TB, W') folded rows.

VMEM per program (TB=8, W<=2048 words = 64k bins): 8·2048·4 B = 64 KiB in,
out strictly smaller — trivially resident.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["rebucket_kernel"]


def _kernel(src_ref, out_ref, *, n_bins: int, n_bins_new: int):
    src = src_ref[...]  # (TB, W_pad) uint32
    w_new = out_ref.shape[1]
    n_chunks = -(-n_bins // n_bins_new)
    acc = jnp.zeros((src.shape[0], w_new), jnp.uint32)
    for q in range(n_chunks):
        lo_bit = q * n_bins_new
        lo, s = lo_bit // 32, lo_bit % 32
        cur = jax.lax.shift_right_logical(
            src[:, lo : lo + w_new], jnp.uint32(s)
        )
        if s:  # s == 0 would left-shift by 32: undefined, and unneeded
            cur = cur | jax.lax.shift_left(
                src[:, lo + 1 : lo + 1 + w_new], jnp.uint32(32 - s)
            )
        acc = acc | cur
    # zero extraction bits >= n_bins_new (chunk-overhang + output tail)
    wi = jax.lax.broadcasted_iota(jnp.int32, (1, w_new), 1)
    bits_left = n_bins_new - wi * 32
    full = jnp.uint32(0xFFFFFFFF)
    partial = jax.lax.shift_left(
        jnp.uint32(1), jnp.clip(bits_left, 0, 31).astype(jnp.uint32)
    ) - jnp.uint32(1)
    out_ref[...] = acc & jnp.where(bits_left >= 32, full, partial)


def rebucket_kernel(
    src: jax.Array,
    n_bins: int,
    n_bins_new: int,
    *,
    block_rows: int = 8,
    interpret: bool = False,
) -> jax.Array:
    """``src: (B, W_pad)`` packed rows -> ``(B, W')`` rows folded to
    ``n_bins_new`` bins.

    B must be a multiple of ``block_rows`` and ``W_pad`` large enough for
    the last chunk's funnel window; ``ops.rebucket`` handles the padding,
    the source tail-bit masking, and the crops.
    """
    bsz, w_pad = src.shape
    w_new = (n_bins_new + 31) // 32
    assert bsz % block_rows == 0, bsz
    n_chunks = -(-n_bins // n_bins_new)
    assert w_pad >= ((n_chunks - 1) * n_bins_new) // 32 + w_new + 1, w_pad
    grid = (bsz // block_rows,)
    return pl.pallas_call(
        functools.partial(_kernel, n_bins=n_bins, n_bins_new=n_bins_new),
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, w_pad), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_rows, w_new), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, w_new), jnp.uint32),
        name="rebucket",
        interpret=interpret,
    )(src)
