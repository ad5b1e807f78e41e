"""Pallas TPU kernel: packed AND-popcount scoring + fused estimator epilogue.

Scores Q query sketches against C candidate sketches (both packed uint32,
W words per row):

    counts[q, c] = sum_w popcount( a[q, w] & b[c, w] )

blocked (TQ, TC) over queries and candidates, with the word axis as the
contraction — matmul-style arithmetic-intensity scaling: bytes/tile
O((TQ + TC) * W), work O(TQ * TC * W). Popcount is the VPU's native
``population_count``.

The contraction is an outer product per word (``_and_popcount_tile``):
queries sit on sublanes and candidates on lanes, so word ``w`` contributes
``popcount(a[:, w] & b[:, w]^T)`` — a (TQ, 1) lane-broadcast column ANDed
with a (1, TC) sublane-broadcast row. Each TW-word tile of the candidate
block is transposed once to make its word ``w`` a row. No 3-D
intermediate, no gather. ``and_popcount`` walks the word axis in a loop of
aligned TW-word tiles (plus one static tail tile), so the unrolled body
does not grow with W.

The Alg 1/3/4 estimator epilogue (DESIGN.md §1) is applied in-register —
fill counts |a_s| (TQ, 1) and |b_s| (1, TC) stream in as tiny per-row
vectors — so the (Q, C) float similarity matrix leaves VMEM once.

Grid: (Q/TQ, C/TC); each program holds whole (TQ, W) / (TC, W) rows.
Trailing partial blocks are allowed: rows past Q or C only ever reach
output slots that the out-of-bounds write drops.

VMEM per program (defaults TQ=TC=TW=128, W=1090): a, b blocks 558 KiB
each (double-buffered 2.2 MiB), transposed word tile 64 KiB, out 64 KiB
<< 16 MiB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core.estimators import cardinality_from_fill

__all__ = ["and_popcount", "sketch_score_kernel"]


def _and_popcount_tile(a, b):
    """(TQ, TW) x (TC, TW) uint32 -> (TQ, TC) int32 AND-popcounts.

    One outer-product step per word: column ``w`` of ``a`` against row
    ``w`` of ``b^T``."""
    bt = b.T  # (TW, TC): word w of every candidate is one row
    acc = jnp.zeros((a.shape[0], b.shape[0]), jnp.int32)
    for w in range(a.shape[1]):
        both = a[:, w : w + 1] & bt[w : w + 1, :]
        acc = acc + jax.lax.population_count(both).astype(jnp.int32)
    return acc


def and_popcount(a_ref, b_ref, block_w):
    """Whole-row (TQ, W) x (TC, W) refs -> (TQ, TC) int32 AND-popcounts:
    a loop over aligned ``block_w``-word tiles plus one static tail tile,
    so the unrolled body stays ``block_w`` words whatever W is."""
    n_full, tail = divmod(a_ref.shape[1], block_w)

    def body(k, acc):
        off = pl.multiple_of(k * block_w, block_w)
        return acc + _and_popcount_tile(a_ref[:, pl.ds(off, block_w)],
                                        b_ref[:, pl.ds(off, block_w)])

    acc = jnp.zeros((a_ref.shape[0], b_ref.shape[0]), jnp.int32)
    if n_full:
        acc = jax.lax.fori_loop(0, n_full, body, acc)
    if tail:
        lo = n_full * block_w
        acc = acc + _and_popcount_tile(a_ref[:, lo:], b_ref[:, lo:])
    return acc


def _epilogue(counts, na, nb, n_bins, measure):
    """counts: (TQ, TC) int32 AND-popcounts; na: (TQ, 1); nb: (1, TC)."""
    if measure == "counts":
        return counts.astype(jnp.float32)
    card_a = cardinality_from_fill(na, n_bins)
    card_b = cardinality_from_fill(nb, n_bins)
    union_s = na + nb - counts
    card_u = cardinality_from_fill(union_s, n_bins)
    ip = jnp.maximum(card_a + card_b - card_u, 0.0)
    if measure == "ip":
        return ip
    if measure == "hamming":
        return jnp.maximum(card_a + card_b - 2.0 * ip, 0.0)
    if measure == "jaccard":
        return jnp.clip(ip / jnp.maximum(card_u, 1e-9), 0.0, 1.0)
    if measure == "cosine":
        return jnp.clip(ip / jnp.sqrt(jnp.maximum(card_a * card_b, 1e-18)), 0.0, 1.0)
    raise ValueError(f"unknown measure {measure!r}")


def _kernel(a_ref, b_ref, na_ref, nb_ref, out_ref, *, n_bins, measure, block_w):
    out_ref[...] = _epilogue(and_popcount(a_ref, b_ref, block_w), na_ref[...],
                             nb_ref[...], n_bins, measure)


def sketch_score_kernel(
    a: jax.Array,
    b: jax.Array,
    na: jax.Array,
    nb: jax.Array,
    n_bins: int,
    measure: str = "jaccard",
    *,
    block_q: int = 128,
    block_c: int = 128,
    block_w: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """(Q, W) x (C, W) packed sketches -> (Q, C) float32 similarity/counts.

    ``na`` (Q, 1) / ``nb`` (1, C) are per-row int32 fill counts — tiny,
    precomputed by a single popcount pass in ``ops.sketch_score``. Row
    blocks need not divide Q or C: the grid covers trailing partial
    blocks. ``block_w`` is the word tile of the in-kernel contraction loop
    (a multiple of 128 on a TPU, or W itself).
    """
    q, w = a.shape
    c, _ = b.shape
    return pl.pallas_call(
        functools.partial(
            _kernel, n_bins=n_bins, measure=measure, block_w=block_w
        ),
        grid=(pl.cdiv(q, block_q), pl.cdiv(c, block_c)),
        in_specs=[
            pl.BlockSpec((block_q, w), lambda i, j: (i, 0)),
            pl.BlockSpec((block_c, w), lambda i, j: (j, 0)),
            pl.BlockSpec((block_q, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, block_c), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_q, block_c), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((q, c), jnp.float32),
        name="sketch_score",
        interpret=interpret,
    )(a, b, na, nb)
