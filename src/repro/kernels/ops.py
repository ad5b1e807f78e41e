"""Jit'd public wrappers for the Pallas kernels: layout, dtype checks,
interpret-mode fallback off-TPU, and estimator plumbing.

These are the entry points the rest of the framework uses — primarily the
``pallas*`` backends in ``repro.engine.backends`` (which stream the
``SketchStore`` fill cache in via ``a_fills``/``b_fills``) plus benchmarks.

Block sizes: on a TPU every block dim must be the whole axis or a multiple
of the (8, 128) tile (sublane, lane). The defaults are 128 on every tiled
axis and each wrapper clamps a block to its axis, so a small axis becomes
one whole-axis block. Blocks need not divide their axes — the kernels run
a trailing partial block and mask what it reads past the end — so no
corpus-sized operand is ever padded or copied here.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..core import packed as pk
from . import (
    band_hash as band_hash_mod,
    count_update,
    hash_build,
    popcount_sim,
    rebucket as rebucket_mod,
    sketch_build,
    topk_stream,
)

__all__ = ["band_hash", "build_sketch", "count_bins", "hash_build_sketch",
           "rebucket", "sketch_score", "sketch_topk", "score_counts"]


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def _pad_to(x: jax.Array, axis: int, multiple: int, fill) -> jax.Array:
    size = x.shape[axis]
    target = _round_up(size, multiple)
    if target == size:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, target - size)
    return jnp.pad(x, pads, constant_values=fill)


def _round_up(n: int, multiple: int) -> int:
    return -(-n // multiple) * multiple


def _slots_t(idx: jax.Array) -> jax.Array:
    """(B, P) padded index/bin slots -> (P8, B) int32, P padded with -1 to
    a multiple of 8: the rows-on-lanes layout of the compare-OR builds."""
    return _pad_to(idx.astype(jnp.int32).T, 0, 8, -1)


@functools.partial(
    jax.jit, static_argnames=("n_bins", "block_rows", "tile_words", "interpret")
)
def build_sketch(
    bins: jax.Array,
    n_bins: int,
    *,
    block_rows: int = 128,
    tile_words: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """Pre-mapped padded bin ids (B, P) -> packed sketches (B, ceil(N/32)).

    Pad slots are -1. Bin ids >= n_bins must not occur (``map_indices``
    never produces them)."""
    if interpret is None:
        interpret = _interpret_default()
    bsz = bins.shape[0]
    n_words = pk.num_words(n_bins)
    if bsz == 0:
        return jnp.zeros((0, n_words), jnp.uint32)
    out = sketch_build.build_sketch_kernel(
        _slots_t(bins),
        n_words,
        block_rows=min(block_rows, bsz),
        tile_words=min(tile_words, n_words),
        interpret=interpret,
    )
    return jax.lax.bitcast_convert_type(out, jnp.uint32)


@functools.partial(
    jax.jit, static_argnames=("n_bins", "block_rows", "tile_bins", "interpret")
)
def count_bins(
    bins: jax.Array,
    n_bins: int,
    *,
    block_rows: int = 8,
    tile_bins: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """Pre-mapped padded bin ids (B, P) -> dense occupancy counters (B, n_bins).

    The counting-BinSketch construction (``core.counting``) as a batched
    compare-reduce histogram — insert/retract deltas for the mutable head
    segment come from here. Pads rows to ``block_rows`` (pad rows are all
    -1 -> zero counters) and the bin axis to ``tile_bins``; crops both on
    return. int32 out; the store clamps into u16 occupancy.
    """
    if interpret is None:
        interpret = _interpret_default()
    bsz = bins.shape[0]
    tile_bins = min(tile_bins, n_bins)
    padded_rows = _pad_to(bins.astype(jnp.int32), 0, block_rows, -1)
    n_bins_padded = -(-n_bins // tile_bins) * tile_bins
    out = count_update.count_bins_kernel(
        padded_rows,
        n_bins_padded,
        block_rows=block_rows,
        tile_bins=tile_bins,
        interpret=interpret,
    )
    return out[:bsz, :n_bins]


@functools.partial(
    jax.jit, static_argnames=("n_bins", "block_rows", "tile_words", "interpret")
)
def hash_build_sketch(
    idx: jax.Array,
    coeffs: jax.Array,
    n_bins: int,
    *,
    block_rows: int = 128,
    tile_words: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused hash+build: raw indices (B, P) + (2,) uint32 multiply-shift
    coefficients -> packed sketches, mapping computed in-kernel (the
    tera-scale-d path where no pi table exists)."""
    if interpret is None:
        interpret = _interpret_default()
    bsz = idx.shape[0]
    n_words = pk.num_words(n_bins)
    if bsz == 0:
        return jnp.zeros((0, n_words), jnp.uint32)
    out = hash_build.hash_build_kernel(
        _slots_t(idx),
        coeffs.astype(jnp.uint32),
        n_bins,
        block_rows=min(block_rows, bsz),
        tile_words=min(tile_words, n_words),
        interpret=interpret,
    )
    return jax.lax.bitcast_convert_type(out, jnp.uint32)


@functools.partial(
    jax.jit, static_argnames=("n_bins", "n_bins_new", "block_rows", "interpret")
)
def rebucket(
    packed: jax.Array,
    n_bins: int,
    n_bins_new: int,
    *,
    block_rows: int = 8,
    interpret: bool | None = None,
) -> jax.Array:
    """Packed (B, W) sketches at ``n_bins`` -> (B, W') sketches at the
    smaller ``n_bins_new``, OR-folding bin ``j`` into ``j mod n_bins_new``.

    The sketch-space re-bucketing identity behind segment distillation
    (DESIGN.md §11): the result equals sketching the raw documents under
    the derived mapping ``pi'(i) = pi(i) mod n_bins_new`` — so a query
    sketched once at the base width serves every distilled width via this
    op, never via a second pass over the query's raw indices. Source pad
    bits (>= n_bins in the last word) are zeroed here defensively; fill
    counts of folded rows change and must be re-popcounted by the caller.
    """
    if interpret is None:
        interpret = _interpret_default()
    if packed.dtype != jnp.uint32:
        raise TypeError(f"packed sketches must be uint32, got {packed.dtype}")
    if not 1 <= n_bins_new <= n_bins:
        raise ValueError(f"need 1 <= n_bins_new <= n_bins, got {n_bins_new} vs {n_bins}")
    if n_bins_new == n_bins:
        return packed
    bsz, w = packed.shape
    if n_bins % 32:
        packed = packed.at[:, -1].set(
            packed[:, -1] & jnp.uint32((1 << (n_bins % 32)) - 1)
        )
    w_new = pk.num_words(n_bins_new)
    n_chunks = -(-n_bins // n_bins_new)
    w_need = ((n_chunks - 1) * n_bins_new) // 32 + w_new + 1
    src = _pad_to(packed, 0, block_rows, 0)
    if w_need > w:
        src = jnp.pad(src, ((0, 0), (0, w_need - w)))
    out = rebucket_mod.rebucket_kernel(
        src, n_bins, n_bins_new, block_rows=block_rows, interpret=interpret
    )
    return out[:bsz]


@functools.partial(
    jax.jit, static_argnames=("n_bands", "block_rows", "interpret")
)
def band_hash(
    packed: jax.Array,
    n_bands: int,
    *,
    block_rows: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """Packed (B, W) sketches -> (B, nb_eff) uint32 band keys.

    Splits the word axis into ``n_bands`` groups of ``wpb = ceil(W /
    n_bands)`` contiguous words and hashes each group with a seeded
    xorshift-multiply chain (``core.packed.band_hash`` is the jnp oracle,
    bit-identical). ``n_bands`` clamps to W and the effective band count is
    ``nb_eff = ceil(W / wpb)`` — size bucket indexes off the output shape,
    not the requested count. The word axis is zero-padded to ``nb_eff *
    wpb`` (zero pad words mix identically into every row's key, so
    collisions are unaffected) and regrouped to the kernel's
    (wpb, nb_eff, B) layout.
    """
    if interpret is None:
        interpret = _interpret_default()
    if packed.dtype != jnp.uint32:
        raise TypeError(f"packed sketches must be uint32, got {packed.dtype}")
    bsz, w = packed.shape
    n_bands = max(1, min(int(n_bands), w))
    wpb = -(-w // n_bands)
    nb_eff = -(-w // wpb)
    if bsz == 0:
        return jnp.zeros((0, nb_eff), jnp.uint32)
    src = _pad_to(packed, 1, wpb, 0).reshape(bsz, nb_eff, wpb).transpose(2, 1, 0)
    out = band_hash_mod.band_hash_kernel(
        src, block_rows=min(block_rows, bsz), interpret=interpret
    )
    return out.T


@functools.partial(
    jax.jit,
    static_argnames=("n_bins", "measure", "block_q", "block_c", "block_w", "interpret"),
)
def sketch_score(
    a: jax.Array,
    b: jax.Array,
    n_bins: int,
    measure: str = "jaccard",
    *,
    a_fills: jax.Array | None = None,
    b_fills: jax.Array | None = None,
    block_q: int = 128,
    block_c: int = 128,
    block_w: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """Packed (Q, W) x (C, W) -> (Q, C) float32 similarity, fused epilogue.

    Fill counts |a_s|, |b_s| stream into the epilogue as tiny per-row
    vectors. Pass ``a_fills``/``b_fills`` to reuse precomputed counts (the
    ``engine.SketchStore`` ingest-time cache — skips the O(C·W) corpus
    popcount per query); ``None`` computes them here in one cheap pass
    (O((Q+C) W) vs the kernel's O(Q C W)).
    """
    if interpret is None:
        interpret = _interpret_default()
    if a.dtype != jnp.uint32 or b.dtype != jnp.uint32:
        raise TypeError(f"packed sketches must be uint32, got {a.dtype}, {b.dtype}")
    q, w = a.shape
    c, _ = b.shape
    if q == 0 or c == 0:
        return jnp.zeros((q, c), jnp.float32)
    na = a_fills if a_fills is not None else pk.row_popcount(a)
    nb = b_fills if b_fills is not None else pk.row_popcount(b)
    return popcount_sim.sketch_score_kernel(
        a, b, na.astype(jnp.int32).reshape(q, 1), nb.astype(jnp.int32).reshape(1, c),
        n_bins, measure,
        block_q=min(block_q, _round_up(q, 8)), block_c=min(block_c, c),
        block_w=block_w,
        interpret=interpret,
    )


@functools.partial(
    jax.jit,
    static_argnames=("n_bins", "measure", "k", "block_q", "block_c", "block_w",
                     "interpret"),
)
def sketch_topk(
    a: jax.Array,
    b: jax.Array,
    n_bins: int,
    measure: str = "jaccard",
    *,
    k: int,
    a_fills: jax.Array | None = None,
    b_fills: jax.Array | None = None,
    b_valid: jax.Array | None = None,
    block_q: int = 128,
    block_c: int = 128,
    block_w: int = 128,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Packed (Q, W) x (C, W) -> top-k (scores (Q, k), ids (Q, k)), fused.

    The streaming kernel (``topk_stream``) never materializes the (Q, C)
    score matrix: corpus blocks flow through VMEM once and only O(Q·k)
    leaves the chip. Fill counts stream in as in ``sketch_score``
    (``a_fills``/``b_fills`` reuse the SketchStore ingest-time cache,
    ``None`` popcounts here in one cheap pass). ``b_valid`` (C,) masks
    corpus rows out of the result entirely. ``block_c`` (rounded up to a
    power of two, and to at least k) is both the corpus block and the
    width of the kernel's running top-L. Rows come back sorted descending
    with ``jax.lax.top_k``'s lowest-index-first tie-break; slots past the
    number of retrievable docs (k > C, or masked rows) hold score -inf /
    id -1.
    """
    if interpret is None:
        interpret = _interpret_default()
    if a.dtype != jnp.uint32 or b.dtype != jnp.uint32:
        raise TypeError(f"packed sketches must be uint32, got {a.dtype}, {b.dtype}")
    if k < 1:
        raise ValueError(f"k must be >= 1, got {k}")
    q, w = a.shape
    c, _ = b.shape
    if c == 0 or q == 0:  # no docs: every slot is the empty sentinel
        return (jnp.full((q, k), -jnp.inf, jnp.float32),
                jnp.full((q, k), -1, jnp.int32))
    na = a_fills if a_fills is not None else pk.row_popcount(a)
    nb = b_fills if b_fills is not None else pk.row_popcount(b)
    valid = (b_valid.astype(jnp.int32) if b_valid is not None
             else jnp.ones((c,), jnp.int32))
    out_s, out_i = topk_stream.sketch_topk_kernel(
        a, b, na.astype(jnp.int32).reshape(q, 1),
        nb.astype(jnp.int32).reshape(1, c), valid.reshape(1, c),
        n_bins, measure,
        block_q=min(block_q, _round_up(q, 8)),
        block_c=max(topk_stream.next_pow2(block_c), topk_stream.next_pow2(k)),
        block_w=block_w,
        interpret=interpret,
    )
    return out_s[:, :k], out_i[:, :k]


def score_counts(a: jax.Array, b: jax.Array, **kw) -> jax.Array:
    """AND-popcount counts (Q, C) as float32 (no estimator)."""
    return sketch_score(a, b, n_bins=1, measure="counts", **kw)


def make_scorer(n_bins: int, measure: str = "jaccard", **kw):
    """DEPRECATED: scorer closure for the old ``core.index.SketchIndex``
    hook. Use ``repro.engine.get_backend("pallas")`` instead — backends also
    accept the store's cached fill counts, which a 2-arg closure cannot."""

    def scorer(qs, cand):
        return sketch_score(qs, cand, n_bins=n_bins, measure=measure, **kw)

    return scorer
