"""Pallas TPU kernel: fused streaming score -> top-k over a packed corpus.

``sketch_score`` writes the full (Q, C) float32 similarity matrix to HBM and
reads it back just so ``jax.lax.top_k`` can keep k values per query — an
O(Q·C) memory wall that caps corpus size. This kernel never materializes
that matrix: the grid iterates corpus blocks as the *innermost* sequential
dimension; each step computes the AND-popcount of its (TQ, TC) tile in VMEM
(``popcount_sim.and_popcount``'s outer-product contraction), applies the
estimator epilogue, and merges the tile into a
per-query running top-L of scores + *global* doc ids. Only (Q, L) scores /
ids ever leave the chip: HBM output shrinks from O(Q·C) to O(Q·L).

Top-L maintenance is a sort-based compare-exchange network (DESIGN.md §7),
with L = TC lanes (128, or the next power of two >= k):

  * each (TQ, TC) score tile is bitonic-sorted *ascending* along the lane
    axis together with its doc ids (order: score, then smaller id wins —
    ``jax.lax.top_k``'s tie-break);
  * the running top-L is kept descending, so the lane-wise winner of the
    running list and the ascending tile holds the L best of their union as
    a bitonic sequence (the first half-cleaner of a bitonic merge); one
    bitonic *merge* (log2(L) compare-exchange stages) re-sorts it
    descending. The output block stays VMEM-resident across the
    corpus-block grid steps (the revisited-output pattern of a matmul
    accumulator).

Partner exchange at lane distance ``stride`` is two lane rotations
(``pltpu.roll``) and a select — the XOR partner of lane ``i`` is
``i + stride`` where bit ``stride`` of ``i`` is clear, ``i - stride``
where it is set.

Invalid corpus rows (masked docs, and the undefined rows of a trailing
partial block) score -inf with id -1, so they can never displace a real doc.

Grid: (Q/TQ, C/TC) with the corpus axis innermost; each program holds
whole (TQ, W) / (TC, W) rows.

VMEM per program (TQ=TC=TW=128, W=1090): a, b blocks 558 KiB each
(double-buffered 2.2 MiB), running top-L scores + ids 128 KiB, sort
temporaries a few hundred KiB  << 16 MiB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .popcount_sim import _epilogue, and_popcount

__all__ = ["sketch_topk_kernel", "next_pow2"]

_NEG_INF = float("-inf")


def next_pow2(n: int) -> int:
    """Smallest power of two >= n (n >= 1)."""
    p = 1
    while p < n:
        p *= 2
    return p


def _lane(shape):
    return jax.lax.broadcasted_iota(jnp.int32, shape, 1)


def _exchange(x, stride, low):
    """Each lane's partner at XOR-distance ``stride`` (last axis); ``low``
    marks lanes whose ``stride`` bit is clear."""
    l = x.shape[-1]
    up = pltpu.roll(x, l - stride, 1)  # up[i] = x[i + stride]
    down = pltpu.roll(x, stride, 1)  # down[i] = x[i - stride]
    return jnp.where(low, up, down)


def _beats(s, ids, ps, pids):
    """(s, ids) ranks before (ps, pids): score desc, then id asc — the id
    tie-break reproduces ``jax.lax.top_k``'s lowest-index-first order."""
    return (s > ps) | ((s == ps) & (ids <= pids))


def _compare_exchange(s, ids, stride, take_max):
    """One compare-exchange stage on (score, id) pairs at lane distance
    ``stride``; ``take_max`` marks lanes that keep the better element."""
    low = (_lane(s.shape) & stride) == 0
    ps, pids = _exchange(s, stride, low), _exchange(ids, stride, low)
    keep_self = take_max == _beats(s, ids, ps, pids)
    return jnp.where(keep_self, s, ps), jnp.where(keep_self, ids, pids)


def _bitonic_sort(s, ids, descending):
    """Full bitonic sort of (TQ, L) along lanes, L a power of 2."""
    l = s.shape[-1]
    lane = _lane(s.shape)
    size = 2
    while size <= l:
        stride = size // 2
        while stride >= 1:
            desc_block = ((lane & size) == 0) == descending
            lower = (lane & stride) == 0
            s, ids = _compare_exchange(s, ids, stride, lower == desc_block)
            stride //= 2
        size *= 2
    return s, ids


def _bitonic_merge_desc(s, ids):
    """Merge a bitonic (TQ, L) sequence into descending order: one pass of
    log2(L) compare-exchange stages, the better element kept at the lower
    lane."""
    lane = _lane(s.shape)
    stride = s.shape[-1] // 2
    while stride >= 1:
        s, ids = _compare_exchange(s, ids, stride, (lane & stride) == 0)
        stride //= 2
    return s, ids


def _kernel(a_ref, b_ref, na_ref, nb_ref, valid_ref, out_s_ref, out_i_ref, *,
            n_bins, measure, c, block_w):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_s_ref[...] = jnp.full_like(out_s_ref, _NEG_INF)
        out_i_ref[...] = jnp.full_like(out_i_ref, -1)

    s = _epilogue(and_popcount(a_ref, b_ref, block_w), na_ref[...], nb_ref[...],
                  n_bins, measure)
    ids = j * s.shape[1] + _lane(s.shape)  # global doc ids of this block
    ok = (valid_ref[...] != 0) & (ids < c)
    s = jnp.where(ok, s, _NEG_INF)
    ids = jnp.where(ok, ids, -1)
    s, ids = _bitonic_sort(s, ids, descending=False)
    run_s, run_i = out_s_ref[...], out_i_ref[...]
    keep_run = _beats(run_s, run_i, s, ids)
    s, ids = _bitonic_merge_desc(jnp.where(keep_run, run_s, s),
                                 jnp.where(keep_run, run_i, ids))
    out_s_ref[...] = s
    out_i_ref[...] = ids


def sketch_topk_kernel(
    a: jax.Array,
    b: jax.Array,
    na: jax.Array,
    nb: jax.Array,
    valid: jax.Array,
    n_bins: int,
    measure: str,
    *,
    block_q: int = 128,
    block_c: int = 128,
    block_w: int = 128,
    interpret: bool = False,
):
    """(Q, W) x (C, W) packed sketches -> ((Q, L) scores, (Q, L) ids), L =
    ``block_c``.

    ``na`` (Q, 1) / ``nb`` (1, C) are per-row int32 fill counts, ``valid``
    (1, C) int32 marks real corpus rows (0 -> score -inf, id -1).
    ``block_c`` is a power of two: the width of the sort network and of the
    running top-L. Row blocks need not divide Q or C; ``block_w`` is the
    word tile of the contraction loop. Output rows are
    sorted descending; HBM traffic is O(Q·W + C·W), output O(Q·L).
    """
    q, w = a.shape
    c, _ = b.shape
    assert block_c == next_pow2(block_c), block_c
    out_spec = pl.BlockSpec((block_q, block_c), lambda i, j: (i, 0))
    return pl.pallas_call(
        functools.partial(
            _kernel, n_bins=n_bins, measure=measure, c=c, block_w=block_w,
        ),
        grid=(pl.cdiv(q, block_q), pl.cdiv(c, block_c)),
        in_specs=[
            pl.BlockSpec((block_q, w), lambda i, j: (i, 0)),
            pl.BlockSpec((block_c, w), lambda i, j: (j, 0)),
            pl.BlockSpec((block_q, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, block_c), lambda i, j: (0, j)),
            pl.BlockSpec((1, block_c), lambda i, j: (0, j)),
        ],
        out_specs=[out_spec, out_spec],
        out_shape=[
            jax.ShapeDtypeStruct((q, block_c), jnp.float32),
            jax.ShapeDtypeStruct((q, block_c), jnp.int32),
        ],
        name="topk_stream",
        interpret=interpret,
    )(a, b, na, nb, valid)
