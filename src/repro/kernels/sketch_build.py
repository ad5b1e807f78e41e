"""Pallas TPU kernel: BinSketch construction as compare-OR (no scatter).

The paper's reference construction is a random scatter
(``sketch[pi(i)] = 1``) — pathological on TPU. The TPU-native formulation
(DESIGN.md §3) works per packed *word*: bin ``b`` sets bit ``b & 31`` of
word ``b >> 5``, so for a tile of TW output words and a block of TB rows

    out[t, r] = OR_p ( (bins[p, r] >> 5) == word_base + t ? 1 << (bins[p, r] & 31) : 0 )

an OR-accumulation over the P bin slots of each row. Rows sit on lanes
(the wrapper hands the bins in transposed, (P, B)), words on sublanes: one
bin slot of all TB rows is a (1, TB) row, broadcast against the (TW, TB)
word-index tile — a compare, a select and an OR per slot on the VPU, no
reduction across lanes. Duplicate bins in a row OR into the same bit, so
no dedupe is needed. Pads (-1) have word -1 and never match. The tile is
transposed once at the end and written as (TB, TW) of the packed output.

Grid: (rows / TB, words / TW), trailing partial blocks allowed (rows past
B are dropped on write, words past W never match a real bin). Each program
loops over the P bin slots eight at a time (an aligned (8, TB) load per
iteration).

VMEM per program (defaults TB=TW=128, P<=1024): bins 512 KiB
(double-buffered), accumulator + word-index tile 128 KiB, out 64 KiB.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["build_sketch_kernel", "or_pack_tile"]


def or_pack_tile(bins_ref, tile_words: int, to_bins: Callable = lambda x: x):
    """(P, TB) bin slots -> (TB, TW) packed int32 words of word-tile
    ``program_id(1)``; ``to_bins`` maps a loaded (8, TB) slot block to bin
    ids (-1 = pad). P must be a multiple of 8. Shared with ``hash_build``,
    which computes the bins in-kernel."""
    tb = bins_ref.shape[1]
    word = (pl.program_id(1) * tile_words
            + jax.lax.broadcasted_iota(jnp.int32, (tile_words, tb), 0))

    def body(g, acc):
        blk = to_bins(bins_ref[pl.ds(pl.multiple_of(g * 8, 8), 8), :])
        for r in range(8):
            b = blk[r : r + 1, :]
            bit = jnp.left_shift(jnp.int32(1), b & 31)
            acc = acc | jnp.where((b >> 5) == word, bit, 0)
        return acc

    acc = jax.lax.fori_loop(0, bins_ref.shape[0] // 8, body,
                            jnp.zeros((tile_words, tb), jnp.int32))
    return acc.T


def _kernel(bins_ref, out_ref, *, tile_words: int):
    out_ref[...] = or_pack_tile(bins_ref, tile_words)


def build_sketch_kernel(
    bins_t: jax.Array,
    n_words: int,
    *,
    block_rows: int = 128,
    tile_words: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """``bins_t: (P, B)`` transposed pre-mapped bin ids (pad -1, P a
    multiple of 8) -> packed ``(B, n_words)`` int32 (bit-identical to the
    uint32 sketch; ``ops.build_sketch`` transposes in and bitcasts out)."""
    p, bsz = bins_t.shape
    assert p % 8 == 0, p
    grid = (pl.cdiv(bsz, block_rows), pl.cdiv(n_words, tile_words))
    return pl.pallas_call(
        functools.partial(_kernel, tile_words=tile_words),
        grid=grid,
        in_specs=[pl.BlockSpec((p, block_rows), lambda i, j: (0, i))],
        out_specs=pl.BlockSpec((block_rows, tile_words), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((bsz, n_words), jnp.int32),
        name="sketch_build",
        interpret=interpret,
    )(bins_t)
