"""SegmentedStore — LSM-style mutable corpus lifecycle (DESIGN.md §9).

``SketchStore`` is append-only by construction: the OR-homomorphic ingest
cannot be undone, so a live catalog could never delete or update a document
without a full rebuild. This module lifts it into a mutable index with the
classic log-structured layout:

  * a **mutable head segment** backed by the *counting* BinSketch
    (``core.counting``): per-doc, per-bin u16 occupancy counters over the
    same Ψ-mapping. The binary sketch every estimator and kernel consumes
    is ``counters > 0`` — bit-for-bit the paper's sketch — so insert is an
    increment, element retraction a decrement, and document replacement a
    counter overwrite, all in place;
  * **sealed segments** that stay packed-only (C, W) + fill cache, exactly
    a frozen ``SketchStore`` slab. Deletion there is a tombstone flip in a
    host-side bitmap that feeds ``Backend.topk``'s ``corpus_valid`` mask —
    the row never scores again but no data moves;
  * a **compaction pass** that merges sealed segments, dropping tombstoned
    rows and re-gathering the fill caches — the only time sealed bytes are
    rewritten, and still never a re-sketch. :meth:`SegmentedStore.compact`
    is the synchronous global pass; :meth:`SegmentedStore.compact_async`
    runs the same merge as a **background job** on the checkpoint-thread
    pattern (snapshot-to-host, merge off-thread, atomic swap on the
    caller's thread with tombstone reconciliation), optionally *grouped* —
    one merge per placement device — so serving never stalls and each
    device's resident set compacts locally (DESIGN.md §10);
  * **TTL expiry** over per-doc ingest timestamps — eagerly via
    :meth:`SegmentedStore.expire` (tombstones, reclaimed at the next
    compaction), and **lazily** at query time: with a store-level ``ttl``,
    passing ``now`` to the query path folds ``born + ttl <= now`` into the
    ``corpus_valid`` mask, so expired docs vanish from results without
    anyone sweeping;
  * **distillation** (:meth:`SegmentedStore.distill_async`, DESIGN.md §11):
    a background re-sketch of a sealed segment from the base width N to a
    smaller N', trading recall for memory *per segment*. Because
    re-bucketing composes in sketch space (bin ``j`` folds into
    ``j mod N'`` — ``core.packed.fold_packed``), the fold runs over the
    packed slab alone, never the raw documents; a :class:`DistillPolicy`
    picks which segments drop to which width tier, and serving becomes
    mixed-width (every :class:`~repro.engine.store.SegmentView` carries
    its ``n_bins``).

**Invariants the rest of the stack leans on.**

  * *Location map*: ``_loc[gid] == (segment, row)`` for exactly the live
    documents — every mutation that kills a row removes (or repoints) its
    entry *and* flips the row's validity in the same call, so "live" has
    one definition. Background swaps (compaction *and* distillation)
    reconcile against the **source tombstone bitmaps**, not ``_loc``: a
    merged/folded row stays live iff its snapshot source row is still
    valid, and a dead sealed row can never come back (ids are never
    reused; relocation only tombstones) — mid-job casualties surface as
    tombstones in the new segment, never as resurrected rows.
  * *Valid-mask predicate*: a row is retrievable iff
    ``valid[row] and (ttl is None or now is None or born[row] + ttl > now)``
    — the same predicate, evaluated lazily by every query view and
    eagerly by :meth:`SegmentedStore.expire`, so a doc on the TTL boundary
    cannot be invisible to queries yet unreclaimable by the sweep.
  * *u16 saturation*: head counters clamp at ``counting.COUNTER_MAX`` and
    the clamp is sticky — retraction is refused on saturated rows (the
    true occupancy is gone; ``update``'s overwrite is the recovery path).
    See ``core.counting``'s module docstring for the full contract.

Global doc ids are assigned once at insert and survive seal, compaction
and distillation (query results stay stable across lifecycle events).
Updating a *sealed* doc relocates it into the head under its old id —
rows inside every segment are kept ascending in id (the head re-sorts
lazily), and the cross-segment merge in the engine breaks score ties
toward the lower id, so an arbitrarily mutated store is query-identical
to a fresh batch build over the surviving documents (at each segment's
own width).

Snapshots ride the existing :class:`~repro.checkpoint.manager.CheckpointManager`
(atomic, async, retention) — the store serializes to a pytree + aux dict
and restores from cold without re-sketching anything.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import faults
from ..core import binsketch, counting
from ..core import packed as pk
from ..obs import metrics as obs_metrics
from ..obs.trace import span
from .banding import BandIndex, BandPolicy
from .store import SegmentView, _grow
from .supervision import JobSupervisor, SupervisedJob

__all__ = ["DistillPolicy", "SealedSegment", "SegmentedStore"]

_HEAD = -1  # segment index of the mutable head in the location map


def _in_span(name: str, **stats):
    """Run the decorated function inside the span ``repro.<name>``: the
    background job bodies, whose spans land on the worker's thread."""
    def wrap(fn):
        @functools.wraps(fn)
        def run(*args, **kwargs):
            with span(name, **stats):
                return fn(*args, **kwargs)
        return run
    return wrap


def _check_rows_match(ids: np.ndarray, idx: jax.Array) -> None:
    """One content row per doc id — jax's clamping gather would otherwise
    turn a length mismatch into silent row duplication, not an error."""
    if idx.shape[0] != len(ids):
        raise ValueError(
            f"got {idx.shape[0]} content rows for {len(ids)} doc ids"
        )


def _clamp_rows(counts: jax.Array, counter_max: int):
    """(B, N) int32 occupancy -> the head's four per-row results: the u16
    counters clamped at ``counter_max``, their packed rows, their fills, and
    whether the clamp lost information (any bin above ``counter_max``).
    Pure math, shared by the append and the row-rewrite paths."""
    sat = jnp.any(counts > counter_max, axis=-1)
    clamped = jnp.clip(counts, 0, counter_max).astype(
        counting.COUNTER_DTYPE
    )
    return (clamped, counting.counters_to_packed(clamped),
            counting.counter_fills(clamped), sat)


@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3),
                   static_argnames="counter_max")
def _append_rows(counters, packed, fills, sat_dev, counts, lo, counter_max):
    """Write (B, N) ``counts`` into rows ``[lo, lo + B)`` of the head's
    device buffers as one program: one contiguous slice update per buffer,
    in place (the buffers are donated). ``lo`` is traced, so one compile
    serves every offset. ``dynamic_update_slice`` moves a start that would
    overrun back to fit, so the caller must guarantee ``lo + B <= cap``.
    ``counter_max`` is ``counting.COUNTER_MAX``, passed at each call: a
    module global read while tracing would stay baked into the cached
    program after it changes."""
    rows = _clamp_rows(counts.astype(jnp.int32), counter_max)
    return tuple(
        jax.lax.dynamic_update_slice_in_dim(buf, new, lo, axis=0)
        for buf, new in zip((counters, packed, fills, sat_dev), rows)
    )


def _grow_host(arr: np.ndarray, new_capacity: int) -> np.ndarray:
    out = np.zeros((new_capacity,) + arr.shape[1:], arr.dtype)
    out[: arr.shape[0]] = arr
    return out


def _fold_packed_host(sk: np.ndarray, n_bins: int, n_bins_new: int,
                      chunk: int = 32768):
    """Numpy twin of ``core.packed.fold_packed`` + fill re-gather, for the
    distillation worker thread (pure host math, no device dispatch that
    could contend with serving). Returns ``(folded (n, W') uint32,
    fills (n,) int32)``.

    Word-level funnel shift, as in the ``kernels.rebucket`` kernel: source
    chunk ``q`` (bits ``[q·N', (q+1)·N')``) starts at bit ``s`` of word
    ``lo`` and ORs in as ``(src[lo + w] >> s) | (src[lo + w + 1] << (32 -
    s))``; bits past N' are masked once at the end. Rows go in blocks of
    ``chunk`` so the temporaries stay bounded on a full-size slab."""
    sk = np.asarray(sk, np.uint32)
    n, w = sk.shape
    w_new = pk.num_words(n_bins_new)
    n_chunks = -(-n_bins // n_bins_new)
    w_src = max(w, ((n_chunks - 1) * n_bins_new) // 32 + w_new + 1)
    tail = np.full(w_new, 0xFFFFFFFF, np.uint32)
    if n_bins_new % 32:
        tail[-1] = (1 << (n_bins_new % 32)) - 1
    folded = np.empty((n, w_new), np.uint32)
    for r0 in range(0, n, chunk):
        src = np.zeros((min(chunk, n - r0), w_src), np.uint32)
        src[:, :w] = sk[r0 : r0 + chunk]
        if n_bins % 32:  # source pad bits past N never fold in
            src[:, w - 1] &= np.uint32((1 << (n_bins % 32)) - 1)
        acc = np.zeros((len(src), w_new), np.uint32)
        for q in range(n_chunks):
            lo, sh = divmod(q * n_bins_new, 32)
            acc |= src[:, lo : lo + w_new] >> np.uint32(sh)
            if sh:
                acc |= src[:, lo + 1 : lo + 1 + w_new] << np.uint32(32 - sh)
        folded[r0 : r0 + chunk] = acc & tail
    return folded, np.bitwise_count(folded).sum(axis=1, dtype=np.int32)


@dataclasses.dataclass(frozen=True)
class DistillPolicy:
    """Which sealed segments drop to which smaller sketch width, and when.

    ``widths`` are the tiers (any order; applied descending): an eligible
    segment at current width ``w`` is re-sketched to the *largest* tier
    strictly below ``w`` — one tier per distillation pass, so a segment
    walks down the ladder as it keeps qualifying. Eligibility is
    age/size-tiered: a segment qualifies when its **youngest live row** is
    at least ``min_age`` old (the whole segment is cold), or when its live
    rows have dwindled to ``live_floor`` or fewer (mostly-dead segments
    are cheap to shrink). With both thresholds ``None`` every sealed
    segment is eligible — the explicit "distill now" call.
    """

    widths: Tuple[int, ...]
    min_age: Optional[float] = None
    live_floor: Optional[int] = None

    def __post_init__(self):
        if not self.widths or any(int(w) < 1 for w in self.widths):
            raise ValueError(f"widths must be positive ints, got {self.widths}")
        object.__setattr__(
            self, "widths", tuple(sorted((int(w) for w in self.widths),
                                         reverse=True))
        )

    def target_width(
        self, n_bins_cur: int, age: float, n_live: int
    ) -> Optional[int]:
        """Next tier for a segment, or None if ineligible / already at the
        bottom of the ladder."""
        gated = self.min_age is not None or self.live_floor is not None
        if gated and not (
            (self.min_age is not None and age >= self.min_age)
            or (self.live_floor is not None and n_live <= self.live_floor)
        ):
            return None
        for w in self.widths:
            if w < n_bins_cur:
                return w
        return None


def _gather_live(parts):
    """Live rows of segment ``parts`` merge-sorted by global id.

    ``parts``: iterable of ``(sketches, fills, ids, valid, born)`` — device
    arrays for the first two, host numpy for the rest. Returns
    ``(sketches, fills, ids, born)`` or ``None`` if nothing is live. The
    one implementation behind ``live()``, ``seal()`` and ``compact()`` so
    the query view and the compaction output cannot drift apart.
    """
    sk, fl, ids, born = [], [], [], []
    for sketches, fills, ids_np, valid_np, born_np in parts:
        keep = np.nonzero(valid_np)[0]
        if len(keep) == 0:
            continue
        rows = jnp.asarray(keep.astype(np.int32))
        sk.append(jnp.take(sketches, rows, axis=0))
        fl.append(jnp.take(fills, rows, axis=0))
        ids.append(ids_np[keep])
        born.append(born_np[keep])
    if not ids:
        return None
    ids_c = np.concatenate(ids)
    order = np.argsort(ids_c, kind="stable")
    order_dev = jnp.asarray(order.astype(np.int32))
    return (
        jnp.take(jnp.concatenate(sk, axis=0), order_dev, axis=0),
        jnp.take(jnp.concatenate(fl, axis=0), order_dev, axis=0),
        ids_c[order],
        np.concatenate(born)[order],
    )


@dataclasses.dataclass
class SealedSegment:
    """Immutable packed slab + tombstone bitmap; rows ascend in global id.

    ``n_bins`` is None for a segment at the store's base sketch width and
    the smaller width for a *distilled* segment — its ``sketches`` then
    have ``num_words(n_bins)`` words per row and queries must be
    re-bucketed to match (the engine does, via ``Backend.rebucket``)."""

    sketches: jax.Array  # (n, W) uint32
    fills: jax.Array  # (n,) int32
    ids: np.ndarray  # (n,) int64 global doc ids, ascending
    valid: np.ndarray  # (n,) bool — False = tombstoned
    born: np.ndarray  # (n,) float64 ingest timestamps
    n_bins: Optional[int] = None  # sketch width; None = store base width
    # banded prefilter index (DESIGN.md §12), built over this slab's rows at
    # seal/swap time and immutable with it — tombstones leave it alone (dead
    # candidates are dropped at query time against ``valid``), and every
    # lifecycle rewrite (compact/distill) produces a *new* segment with a
    # fresh index, so stale buckets cannot outlive their rows
    band_index: Optional[BandIndex] = None
    # telemetry (DESIGN.md §14): number of query passes that *scored* this
    # segment (one per planner chunk that scanned it; a banded pass whose
    # candidate set came up empty does not count). Always-on — a host int
    # increment is nothing next to a kernel dispatch — and deliberately
    # outside the metrics registry: it is the per-segment access-rate
    # signal the ROADMAP's hot/cold tiering will read, and it must not
    # reset when a registry is swapped. Rewrites (compact/distill) start
    # the new segment at 0 — access history belongs to the dead layout.
    hits: int = 0

    def __post_init__(self):
        self._ids_dev: Optional[jax.Array] = None
        self._valid_dev: Optional[jax.Array] = None
        self._ttl_cache: Optional[tuple] = None  # (now, ttl) -> device mask
        # ids are fixed at construction: compute the identity-mapping flag
        # once so a freshly compacted, gap-free segment skips the id gather
        self._ids_identity = bool(
            np.array_equal(self.ids, np.arange(len(self.ids)))
        )
        self._all_valid = bool(self.valid.all())

    @property
    def n_rows(self) -> int:
        return len(self.ids)

    @property
    def n_live(self) -> int:
        return int(self.valid.sum())

    def tombstone(self, row: int) -> None:
        self.valid[row] = False
        self._valid_dev = None  # invalidate the device-side mask caches
        self._ttl_cache = None
        self._all_valid = False

    def view(
        self, ttl: Optional[float] = None, now: Optional[float] = None
    ) -> SegmentView:
        """Tombstone-free segments pass ``valid=None`` (no per-score mask in
        the kernels) and identity-id segments pass ``ids=None`` (no gather)
        — a compacted corpus queries at append-only speed. With ``ttl`` and
        ``now``, rows aged out (``born + ttl <= now``) are masked lazily —
        they never reach a top-k even before anyone calls ``expire()``; the
        (now, ttl)-keyed single-slot cache makes repeated queries at the
        same timestamp free."""
        if self._ids_identity:
            ids_dev = None
        elif self._ids_dev is None:
            ids_dev = self._ids_dev = jnp.asarray(self.ids.astype(np.int32))
        else:
            ids_dev = self._ids_dev
        if ttl is not None and now is not None:
            expired = self.born + ttl <= now
            if expired.any():
                if self._ttl_cache is None or self._ttl_cache[0] != (now, ttl):
                    mask = jnp.asarray((self.valid & ~expired).astype(np.int32))
                    self._ttl_cache = ((now, ttl), mask)
                return SegmentView(
                    self.sketches, self.fills, ids_dev, self._ttl_cache[1],
                    self.n_bins,
                )
        if self._all_valid:
            valid_dev = None
        elif self._valid_dev is None:
            valid_dev = self._valid_dev = jnp.asarray(self.valid.astype(np.int32))
        else:
            valid_dev = self._valid_dev
        return SegmentView(
            self.sketches, self.fills, ids_dev, valid_dev, self.n_bins
        )


@dataclasses.dataclass
class _Head:
    """Mutable counting segment: u16 occupancy counters + derived packed rows.

    ``counters/packed/fills`` live on device; the per-row metadata
    (``ids/valid/born/exact``) is host numpy — mutation bookkeeping, not
    kernel data. ``exact`` marks rows whose counters carry true element
    multiplicity (built from indices); rows re-entered from packed form
    (sealed relocation, ``add_sketches``) are occupancy-1 approximations
    whose binary sketch is exact but whose counters cannot support
    element-level retraction. ``sat_dev`` marks rows where a bin counter
    hit ``COUNTER_MAX`` and was clamped: the clamp loses the true
    occupancy, so a later decrement would silently under-count — retraction
    is refused on such rows rather than corrupting the sketch (flags stay
    on device so the test never stalls the ingest dispatch stream; see the
    field comment).

    Two write paths: :meth:`append` writes a contiguous block of new rows
    with one jitted in-place slice update (``_append_rows``), and
    :meth:`_write_rows` rewrites arbitrary rows with scatters (update,
    retract, delete). The append *donates* ``counters``, ``packed``,
    ``fills`` and ``sat_dev``: code that holds one of those arrays itself,
    rather than a slice of it, must not expect it to survive an append.
    A slice is a copy, except that ``x[:n]`` with ``n == x.shape[0]`` is
    ``x`` itself, so the views of a full head (``size == capacity``) hold
    the head's own buffers. That is safe because an append to a full head
    grows it first (:meth:`ensure_capacity` allocates new buffers), and
    ``SegmentedStore._insert_counts`` never appends an empty batch: a
    buffer a view holds is never donated.
    """

    counters: jax.Array  # (cap, N) uint16
    packed: jax.Array  # (cap, W) uint32
    fills: jax.Array  # (cap,) int32
    ids: np.ndarray  # (cap,) int64
    valid: np.ndarray  # (cap,) bool
    born: np.ndarray  # (cap,) float64
    exact: np.ndarray  # (cap,) bool
    # device-side, deliberately: a host flag would force a device->host
    # sync on every ingest batch; instead the clamp test rides the same
    # async dispatch as the counter write and is materialized to host only
    # where it is consumed (retraction refusal, checkpoint)
    sat_dev: jax.Array  # (cap,) bool — counters clamped, retraction unsafe
    size: int = 0
    is_sorted: bool = True  # ids[:size] ascending?
    # query-view (ids, valid) device pair incl. fast-path Nones; rebuilt on
    # mutation (see meta_dev)
    _meta_cache: Optional[Tuple] = dataclasses.field(
        default=None, init=False, repr=False
    )
    # (now, ttl) -> device mask; separate from _meta_cache so a TTL query
    # cannot pollute the TTL-free view
    _ttl_cache: Optional[Tuple] = dataclasses.field(
        default=None, init=False, repr=False
    )

    @classmethod
    def create(cls, n_bins: int, n_words: int, capacity: int) -> "_Head":
        capacity = max(int(capacity), 1)
        return cls(
            jnp.zeros((capacity, n_bins), counting.COUNTER_DTYPE),
            jnp.zeros((capacity, n_words), jnp.uint32),
            jnp.zeros((capacity,), jnp.int32),
            np.zeros((capacity,), np.int64),
            np.zeros((capacity,), bool),
            np.zeros((capacity,), np.float64),
            np.zeros((capacity,), bool),
            jnp.zeros((capacity,), jnp.bool_),
        )

    @property
    def saturated(self) -> np.ndarray:
        """(cap,) host view of the clamp flags — one sync, consumers only."""
        return np.asarray(self.sat_dev)

    @property
    def capacity(self) -> int:
        return int(self.counters.shape[0])

    def ensure_capacity(self, n: int) -> None:
        cap = self.capacity
        if n <= cap:
            return
        while cap < n:
            cap *= 2
        self.counters = _grow(self.counters, cap)
        self.packed = _grow(self.packed, cap)
        self.fills = _grow(self.fills, cap)
        self.sat_dev = _grow(self.sat_dev, cap)
        for name in ("ids", "valid", "born", "exact"):
            setattr(self, name, _grow_host(getattr(self, name), cap))

    def _write_rows(self, rows: jax.Array, counts: jax.Array) -> jax.Array:
        """Overwrite counter rows (unique positions) and refresh the derived
        packed sketches + fill cache for exactly those rows. Returns the
        per-row *device* flag of whether the clamp lost information (any
        bin above ``COUNTER_MAX``) — the caller folds it into ``sat_dev``;
        nothing here blocks the async dispatch stream."""
        clamped, packed, fills, sat = _clamp_rows(counts, counting.COUNTER_MAX)
        self.counters = self.counters.at[rows].set(clamped)
        self.packed = self.packed.at[rows].set(packed)
        self.fills = self.fills.at[rows].set(fills)
        obs_metrics.inc("store.head.rows_rewritten", int(rows.shape[0]))
        return sat

    def append(self, counts: jax.Array) -> range:
        """Write (B, N) ``counts`` into the B rows after the last on the
        device (counters, packed rows, fills, clamp flags) and return those
        rows; :meth:`index` then records them on the host. One program,
        in place: see the class docstring for what the donation asks of
        code that holds the head's arrays."""
        b = int(counts.shape[0])
        lo = self.size
        self.ensure_capacity(lo + b)
        if lo + b > self.capacity:
            # the slice update would shift the block back over acknowledged rows
            raise RuntimeError(
                f"head append of {b} rows at row {lo} overruns capacity "
                f"{self.capacity}"
            )
        self.counters, self.packed, self.fills, self.sat_dev = _append_rows(
            self.counters, self.packed, self.fills, self.sat_dev, counts,
            np.int32(lo), counter_max=counting.COUNTER_MAX,
        )
        obs_metrics.inc("store.head.rows_appended", b)
        return range(lo, lo + b)

    def index(self, rows: range, ids: np.ndarray, born, exact: bool) -> None:
        """Host metadata of the rows :meth:`append` just wrote. ``born``
        may be a scalar (fresh inserts) or a (B,) array (sealed relocations
        carrying their original birth time)."""
        lo, b = rows.start, len(rows)
        self.ids[lo : lo + b] = ids
        self.valid[lo : lo + b] = True
        self.born[lo : lo + b] = born
        self.exact[lo : lo + b] = exact
        if self.is_sorted:
            # appends only extend the tail: the batch itself ascending plus
            # batch[0] above the previous tail keeps the invariant — O(b),
            # not a full-prefix rescan per add
            ok = bool(np.all(np.diff(ids) > 0)) if b > 1 else True
            if lo > 0:
                ok = ok and self.ids[lo - 1] < ids[0]
            self.is_sorted = ok
        self.size += b
        self._meta_cache = None
        self._ttl_cache = None

    def add_counts(self, rows: np.ndarray, deltas: jax.Array) -> None:
        """Saturating ``counters[rows] += deltas`` (unique rows) + refresh.
        Saturation is *sticky* under increments: once clamped, the true
        occupancy is unrecoverable, so the flag only an overwrite resets."""
        rows_dev = jnp.asarray(rows.astype(np.int32))
        cur = self.counters[rows_dev].astype(jnp.int32) + deltas
        sat = self._write_rows(rows_dev, cur)
        self.sat_dev = self.sat_dev.at[rows_dev].set(self.sat_dev[rows_dev] | sat)

    def set_counts(self, rows: np.ndarray, counts: jax.Array) -> None:
        rows_dev = jnp.asarray(rows.astype(np.int32))
        sat = self._write_rows(rows_dev, counts.astype(jnp.int32))
        self.sat_dev = self.sat_dev.at[rows_dev].set(sat)

    def zero_rows(self, rows: np.ndarray) -> None:
        rows_dev = jnp.asarray(rows.astype(np.int32))
        sat = self._write_rows(
            rows_dev, jnp.zeros((len(rows), self.counters.shape[1]), jnp.int32)
        )
        self.sat_dev = self.sat_dev.at[rows_dev].set(sat)  # zeros: all False
        self.valid[rows] = False
        self._meta_cache = None
        self._ttl_cache = None

    def meta_dev(self) -> Tuple[Optional[jax.Array], Optional[jax.Array]]:
        """(ids, valid) for the head's query view, cached across queries and
        invalidated on mutation (mirrors ``SealedSegment.view``) — with the
        same fast paths: ``None`` ids when row index == global id, ``None``
        valid when nothing is tombstoned. The flags are cached with the
        device arrays so an unmutated head pays no per-query host scan."""
        if self._meta_cache is None:
            ids = self.ids[: self.size]
            ids_dev = (None if np.array_equal(ids, np.arange(self.size))
                       else jnp.asarray(ids.astype(np.int32)))
            valid = self.valid[: self.size]
            valid_dev = (None if valid.all()
                         else jnp.asarray(valid.astype(np.int32)))
            self._meta_cache = (ids_dev, valid_dev)
        return self._meta_cache


@dataclasses.dataclass
class _CompactionJob:
    """A pending background compaction: the supervised worker plus the
    identity of the sealed segments it snapshotted (so the swap can verify
    nothing restructured them mid-flight and knows exactly which segments
    it replaces)."""

    job: SupervisedJob
    segments: List[SealedSegment]


@dataclasses.dataclass
class SegmentedStore:
    """Mutable, segmented drop-in for :class:`SketchStore`.

    Same ``add`` / ``add_sketches`` / ``merge`` / ``merge_rows`` /
    fill-cache surface, plus the lifecycle verbs: ``delete`` / ``update`` /
    ``retract_rows`` / ``seal`` / ``compact`` / ``expire``. Doc ids are
    global, assigned at insert, and never reused.
    """

    cfg: binsketch.BinSketchConfig
    mapping: jax.Array
    sealed: List[SealedSegment]
    head: _Head
    next_id: int = 0
    seal_rows: Optional[int] = None  # auto-seal head when it reaches this many rows
    ttl: Optional[float] = None  # lazy query-time expiry horizon (seconds of `now`)
    # arm the banded prefilter: sealed segments >= min_rows get a BandIndex
    # at seal/compact/distill time and the engine's query paths scan only
    # colliding buckets (head rows stay unbanded — always scored)
    band_policy: Optional[BandPolicy] = None
    # shared obs.Clock (None = caller passes explicit `now` everywhere, the
    # pre-§14 convention): when set, lazy-TTL query masking and segment
    # ages resolve against it so one fake clock drives store + supervisor
    clock: Optional[Callable[[], float]] = None
    # query passes that scored the mutable head (head twin of
    # SealedSegment.hits; the head survives seals by identity, so this
    # accumulates across the store's whole life)
    head_hits: int = 0
    _loc: Dict[int, Tuple[int, int]] = dataclasses.field(default_factory=dict)
    _n_live: int = 0
    # add calls so far, the ``call`` stat of each ``repro.store.add`` span
    _adds: int = dataclasses.field(default=0, init=False, repr=False)
    # epochs drive the placement caches (engine/placement.py): the layout
    # epoch bumps when the *set* of sealed segments changes (seal, compact,
    # background swap) and invalidates resident device slabs; the valid
    # epoch bumps when only tombstone state changes (delete, update
    # relocation, expire) and refreshes nothing but the device-side mask.
    _layout_epoch: int = 0
    _valid_epoch: int = 0
    _compaction: Optional["_CompactionJob"] = dataclasses.field(
        default=None, repr=False
    )
    # every background job (compaction, distillation) routes through this;
    # maintenance failures are retried/quarantined here and NEVER raised
    # into the query path (DESIGN.md §13)
    supervisor: JobSupervisor = dataclasses.field(
        default_factory=JobSupervisor, repr=False
    )

    # ------------------------------------------------------------ construct
    @classmethod
    def create(
        cls,
        cfg: binsketch.BinSketchConfig,
        mapping: jax.Array,
        capacity: int = 1024,
        seal_rows: Optional[int] = None,
        ttl: Optional[float] = None,
        band_policy: Optional[BandPolicy] = None,
        supervisor: Optional[JobSupervisor] = None,
        clock: Optional[Callable[[], float]] = None,
    ) -> "SegmentedStore":
        # the head never needs more rows than it seals at: its u16 counter
        # matrix costs 2·N bytes per row (20.9 GB for 300k rows at
        # N = 34,851), so an auto-sealing head is sized to ``seal_rows``
        if seal_rows is not None:
            capacity = min(int(capacity), int(seal_rows))
        return cls(
            cfg, mapping, [], _Head.create(cfg.n_bins, cfg.n_words, capacity),
            seal_rows=seal_rows, ttl=ttl, band_policy=band_policy,
            # the store's clock also becomes the default supervisor's, so
            # one injected fake drives TTL + backoff/probation together
            supervisor=supervisor or JobSupervisor(clock=clock),
            clock=clock,
        )

    @classmethod
    def from_indices(
        cls,
        cfg: binsketch.BinSketchConfig,
        mapping: jax.Array,
        corpus_idx: jax.Array,
        *,
        backend=None,
        batch: int = 4096,
        now: float = 0.0,
        seal_rows: Optional[int] = None,
        ttl: Optional[float] = None,
        band_policy: Optional[BandPolicy] = None,
        supervisor: Optional[JobSupervisor] = None,
        clock: Optional[Callable[[], float]] = None,
    ) -> "SegmentedStore":
        store = cls.create(
            cfg, mapping, capacity=max(int(corpus_idx.shape[0]), 1),
            seal_rows=seal_rows, ttl=ttl, band_policy=band_policy,
            supervisor=supervisor, clock=clock,
        )
        store.add(corpus_idx, backend=backend, batch=batch, now=now)
        return store

    # ------------------------------------------------------------ properties
    @property
    def size(self) -> int:
        """Number of *live* (retrievable) documents."""
        return self._n_live

    def resolve_now(self, now: Optional[float] = None) -> Optional[float]:
        """Explicit ``now`` wins; else the injected clock; else None (the
        pre-clock convention: no TTL masking, ages unreported)."""
        if now is not None:
            return float(now)
        return float(self.clock()) if self.clock is not None else None

    @property
    def sketches(self) -> jax.Array:
        """(size, W) packed rows of every live doc, ascending id order.

        Materializes the concatenation — analysis surface (``score_all``,
        tests); the serving path iterates :meth:`segment_views` instead.
        """
        return self.live()[0]

    @property
    def fills(self) -> jax.Array:
        return self.live()[1]

    @property
    def live_ids(self) -> np.ndarray:
        return self.live()[2]

    def _parts(self, *, sealed: bool = True, head: bool = True):
        parts = [
            (seg.sketches, seg.fills, seg.ids, seg.valid, seg.born)
            for seg in (self.sealed if sealed else ())
        ]
        if head:
            h = self.head
            parts.append((h.packed[: h.size], h.fills[: h.size],
                          h.ids[: h.size], h.valid[: h.size], h.born[: h.size]))
        return parts

    def _assert_base_width(self, what: str) -> None:
        # n_live, not n_rows: a fully-tombstoned distilled segment
        # contributes nothing to a live-row gather and is no hazard
        off = [i for i, s in enumerate(self.sealed)
               if s.n_bins is not None and s.n_live > 0]
        if off:
            raise ValueError(
                f"{what} needs every row at the base width N={self.cfg.n_bins},"
                f" but sealed segment(s) {off} are distilled to a smaller N'"
                " (the fold is lossy; rows cannot be widened back). Use the"
                " engine's mixed-width query path, or update()/delete() the"
                " docs instead."
            )

    def live(self) -> Tuple[jax.Array, jax.Array, np.ndarray]:
        """(sketches (L, W), fills (L,), ids (L,) int64) of live docs, id-ordered.

        Base-width only: a store holding distilled segments has no common
        row width to concatenate — the analysis surfaces built on this
        (``score_all``, ``merge``) raise rather than mix widths silently.
        """
        self._assert_base_width("live()")
        got = _gather_live(self._parts())
        if got is None:
            return (jnp.zeros((0, self.cfg.n_words), jnp.uint32),
                    jnp.zeros((0,), jnp.int32), np.zeros((0,), np.int64))
        return got[0], got[1], got[2]

    def segment_views(self, now: Optional[float] = None) -> List[SegmentView]:
        """Sealed slabs then the (id-sorted) head — the engine's query list.

        With a store-level ``ttl`` and a query-time ``now``, every view's
        validity mask additionally drops rows whose ``born + ttl <= now`` —
        lazy expiry: the doc is unretrievable the instant it ages out, with
        no ``expire()`` sweep required (the sweep still reclaims space)."""
        views = [
            seg.view(self.ttl, now) for seg in self.sealed if seg.n_rows > 0
        ]
        hv = self.head_view(now)
        if hv is not None:
            views.append(hv)
        return views

    def head_view(self, now: Optional[float] = None) -> Optional[SegmentView]:
        """The mutable head as one scoreable view (None while empty)."""
        h = self.head
        if h.size == 0:
            return None
        self._sort_head()
        ids_dev, valid_dev = h.meta_dev()
        if self.ttl is not None and now is not None:
            expired = h.born[: h.size] + self.ttl <= now
            if expired.any():
                if h._ttl_cache is None or h._ttl_cache[0] != (now, self.ttl):
                    mask = jnp.asarray(
                        (h.valid[: h.size] & ~expired).astype(np.int32)
                    )
                    h._ttl_cache = ((now, self.ttl), mask)
                valid_dev = h._ttl_cache[1]
        return SegmentView(h.packed[: h.size], h.fills[: h.size], ids_dev, valid_dev)

    # ------------------------------------------------------------- telemetry
    def lifecycle_snapshot(self, now: Optional[float] = None) -> dict:
        """JSON-safe lifecycle gauges (DESIGN.md §14) — the signal surface
        the ROADMAP's autonomous controller reads. Computed on demand from
        store state (nothing here is sampled or registry-dependent):
        per-segment live/tombstone/width/age/hits/banded, the width mix
        (live rows per sketch width), and the store-wide tombstone
        density that triggers size-tiered merges."""
        now = self.resolve_now(now)
        base = int(self.cfg.n_bins)
        segs: List[dict] = []
        rows_total = live_total = 0
        width_mix: Dict[str, int] = {}
        for i, s in enumerate(self.sealed):
            w = int(s.n_bins) if s.n_bins is not None else base
            live = s.n_live
            ent = {
                "segment": i,
                "rows": int(s.n_rows),
                "live": int(live),
                "tombstones": int(s.n_rows - live),
                "width": w,
                "hits": int(s.hits),
                "banded": s.band_index is not None,
            }
            if now is not None and s.n_rows:
                ent["age_min"] = float(now - s.born.max())
                ent["age_max"] = float(now - s.born.min())
            segs.append(ent)
            rows_total += s.n_rows
            live_total += live
            width_mix[str(w)] = width_mix.get(str(w), 0) + int(live)
        h = self.head
        head_live = int(h.valid[: h.size].sum())
        if h.size:
            width_mix[str(base)] = width_mix.get(str(base), 0) + head_live
        rows_total += h.size
        live_total += head_live
        return {
            "segments": segs,
            "head": {
                "rows": int(h.size),
                "live": head_live,
                "capacity": int(h.capacity),
                "hits": int(self.head_hits),
            },
            "live_docs": int(self.size),
            "next_id": int(self.next_id),
            "tombstone_density": float(rows_total - live_total)
            / float(max(rows_total, 1)),
            "width_mix": width_mix,
            "compaction_running": self._compaction is not None,
        }

    # ---------------------------------------------------------------- ingest
    def _count_rows(self, idx: jax.Array, backend) -> jax.Array:
        # documents are sets: collapse duplicate indices before they reach
        # the occupancy scatter, or insert->retract round-trips on
        # non-deduplicated rows would leave phantom counts (and a wrong
        # binary sketch) behind
        with span("store.count", docs=int(idx.shape[0])):
            idx = counting.dedup_padded(idx)
            if backend is not None:
                return backend.count(self.cfg, self.mapping, idx)
            return counting.count_indices_dense(self.cfg, self.mapping, idx)

    def _insert_counts(
        self,
        counts: jax.Array,
        *,
        ids: Optional[np.ndarray] = None,
        now,  # scalar timestamp, or (B,) array to carry per-row birth times
        exact: bool,
    ) -> range:
        b = int(counts.shape[0])
        if b == 0:
            return range(self.next_id, self.next_id)
        if ids is None:
            ids = np.arange(self.next_id, self.next_id + b, dtype=np.int64)
            self.next_id += b
        with span("store.head_write", docs=b):
            rows = self.head.append(counts)
        with span("store.index", docs=b):
            self.head.index(rows, ids, now, exact)
            for gid, row in zip(ids, rows):
                self._loc[int(gid)] = (_HEAD, row)
        self._n_live += b
        if self.seal_rows is not None and self.head.size >= self.seal_rows:
            self.seal()
        return rows

    def add(
        self,
        idx: jax.Array,
        *,
        backend=None,
        batch: int = 4096,
        now: float = 0.0,
    ) -> range:
        """Count-sketch (B, P) padded sparse rows into the head; returns the
        assigned (contiguous, fresh) global doc ids."""
        lo = self.next_id
        self._adds += 1
        with span("store.add", docs=int(idx.shape[0]), call=self._adds):
            for s in range(0, idx.shape[0], batch):
                self._insert_counts(
                    self._count_rows(idx[s : s + batch], backend), now=now,
                    exact=True,
                )
        return range(lo, self.next_id)

    def add_sketches(self, sketches: jax.Array, *, now: float = 0.0) -> range:
        """Append pre-packed rows (occupancy-1 counters: binary sketch exact,
        element retraction unavailable on these rows)."""
        lo = self.next_id
        counts = counting.packed_to_counters(sketches.astype(jnp.uint32), self.cfg.n_bins)
        self._insert_counts(counts, now=now, exact=False)
        return range(lo, self.next_id)

    # ------------------------------------------------------------- mutation
    def _locate(self, gid: int) -> Tuple[int, int]:
        try:
            return self._loc[int(gid)]
        except KeyError:
            raise KeyError(f"doc id {int(gid)} is not live in this store") from None

    def _gather_packed(self, doc_ids: np.ndarray) -> jax.Array:
        """(B, W) current packed rows of live docs, in doc_ids order.

        Rows group by owning segment — one batched ``jnp.take`` per segment
        touched, not one device dispatch per document."""
        if len(doc_ids) == 0:
            return jnp.zeros((0, self.cfg.n_words), jnp.uint32)
        locs = [self._locate(gid) for gid in doc_ids]
        by_seg: Dict[int, Tuple[list, list]] = {}
        for i, (seg_i, row) in enumerate(locs):
            if seg_i != _HEAD and self.sealed[seg_i].n_bins is not None:
                raise ValueError(
                    f"doc {int(doc_ids[i])} lives in a distilled segment "
                    f"(width {self.sealed[seg_i].n_bins} < base "
                    f"{self.cfg.n_bins}); its base-width bits are gone, so "
                    "merge_rows/merge cannot grow it — use update() for a "
                    "full replacement"
                )
            by_seg.setdefault(seg_i, ([], []))[0].append(i)
            by_seg[seg_i][1].append(row)
        parts, order = [], []
        for seg_i, (positions, rows) in by_seg.items():
            src = self.head.packed if seg_i == _HEAD else self.sealed[seg_i].sketches
            parts.append(jnp.take(src, jnp.asarray(rows, jnp.int32), axis=0))
            order.extend(positions)
        inv = np.empty(len(doc_ids), np.int32)
        inv[np.asarray(order)] = np.arange(len(doc_ids), dtype=np.int32)
        return jnp.take(jnp.concatenate(parts, axis=0), jnp.asarray(inv), axis=0)

    def delete(self, doc_ids: Sequence[int]) -> int:
        """Tombstone documents. Head rows are zeroed (counters and packed),
        sealed rows flip their bitmap bit; ids are never reused. Returns the
        number of docs deleted. Unknown/already-deleted ids raise KeyError
        — resolved up front, before any state mutates, so a bad id in the
        batch leaves the store untouched."""
        uniq = list(dict.fromkeys(int(g) for g in np.asarray(doc_ids, np.int64)))
        locs = [self._locate(g) for g in uniq]
        head_rows = []
        for gid, (seg_i, row) in zip(uniq, locs):
            del self._loc[gid]
            if seg_i == _HEAD:
                head_rows.append(row)
            else:
                self.sealed[seg_i].tombstone(row)
        if head_rows:
            self.head.zero_rows(np.asarray(head_rows, np.int64))
        self._n_live -= len(uniq)
        self._valid_epoch += 1
        return len(uniq)

    def update(
        self,
        doc_ids: Sequence[int],
        idx: jax.Array,
        *,
        backend=None,
        now: float = 0.0,
    ) -> None:
        """Replace document contents, keeping global ids.

        Head-resident docs are overwritten in place (counter rows reset to
        the new exact occupancy). Sealed docs relocate: the sealed row is
        tombstoned and the new content enters the head under the old id —
        the LSM move; reclaimed at the next compaction."""
        ids = np.asarray(doc_ids, np.int64)
        if len(np.unique(ids)) != len(ids):
            raise ValueError("duplicate doc ids in one update batch are ambiguous")
        _check_rows_match(ids, idx)
        counts = self._count_rows(idx, backend)
        locs = [self._locate(g) for g in ids]
        in_head = np.array([s == _HEAD for s, _ in locs], bool)
        if in_head.any():
            sel = np.nonzero(in_head)[0]
            rows = np.asarray([locs[i][1] for i in sel], np.int64)
            self.head.set_counts(rows, counts[jnp.asarray(sel.astype(np.int32))])
            self.head.born[rows] = now
            self.head.exact[rows] = True
            self.head._ttl_cache = None  # born moved: lazy-expiry mask stale
        if (~in_head).any():
            sel = np.nonzero(~in_head)[0]
            for i in sel:
                seg_i, row = locs[i]
                self.sealed[seg_i].tombstone(row)
                del self._loc[int(ids[i])]
            self._n_live -= len(sel)
            self._valid_epoch += 1
            self._insert_counts(
                counts[jnp.asarray(sel.astype(np.int32))],
                ids=ids[sel], now=now, exact=True,
            )

    def merge_rows(
        self,
        doc_ids: Sequence[int],
        idx: jax.Array,
        *,
        backend=None,
    ) -> None:
        """OR new content into existing docs (``SketchStore.merge_rows``
        surface). Head docs take a counter increment in place; sealed docs
        relocate into the head carrying their old bits as occupancy-1
        counters plus the new exact increments. A merge grows a doc rather
        than re-creating it, so birth timestamps are preserved (TTL clocks
        do not restart). Either way the merged row loses its
        exact-multiplicity mark: the new content may overlap the old (a
        shared element would be double-counted), so retraction on a merged
        row is refused — ``update`` restores exactness."""
        ids = np.asarray(doc_ids, np.int64)
        _check_rows_match(ids, idx)
        deltas = self._count_rows(idx, backend)
        # duplicate ids in one batch: combine their deltas first (segment-sum)
        uniq, inv = np.unique(ids, return_inverse=True)
        if len(uniq) < len(ids):
            deltas = jax.ops.segment_sum(deltas, jnp.asarray(inv), len(uniq))
            ids = uniq
        locs = [self._locate(g) for g in ids]
        in_head = np.array([s == _HEAD for s, _ in locs], bool)
        if in_head.any():
            sel = np.nonzero(in_head)[0]
            rows = np.asarray([locs[i][1] for i in sel], np.int64)
            self.head.add_counts(rows, deltas[jnp.asarray(sel.astype(np.int32))])
            self.head.exact[rows] = False
        if (~in_head).any():
            sel = np.nonzero(~in_head)[0]
            old = self._gather_packed(ids[sel])
            base = counting.packed_to_counters(old, self.cfg.n_bins)
            merged = base + deltas[jnp.asarray(sel.astype(np.int32))]
            # a merge grows a doc, it doesn't re-create it: relocated rows
            # keep their original birth time so TTL expiry is unaffected
            born = np.array([self.sealed[locs[i][0]].born[locs[i][1]] for i in sel])
            for i in sel:
                seg_i, row = locs[i]
                self.sealed[seg_i].tombstone(row)
                del self._loc[int(ids[i])]
            self._n_live -= len(sel)
            self._valid_epoch += 1
            self._insert_counts(merged, ids=ids[sel], now=born, exact=False)

    def retract_rows(self, doc_ids: Sequence[int], idx: jax.Array, *, backend=None) -> None:
        """Decrement elements out of head-resident docs — the counting
        sketch's signature move: a bin clears exactly when its last mapped
        element is retracted, so the binary sketch tracks the shrunken set.

        Only exact head rows support this (sealed rows lost multiplicity);
        ``update`` or delete+re-add covers the rest."""
        ids = np.asarray(doc_ids, np.int64)
        _check_rows_match(ids, idx)
        deltas = self._count_rows(idx, backend)
        uniq, inv = np.unique(ids, return_inverse=True)
        if len(uniq) < len(ids):
            deltas = jax.ops.segment_sum(deltas, jnp.asarray(inv), len(uniq))
            ids = uniq
        sat = self.head.saturated  # one device sync, only on this rare path
        rows = []
        for gid in ids:
            seg_i, row = self._locate(gid)
            if seg_i != _HEAD or not self.head.exact[row]:
                raise ValueError(
                    f"doc {int(gid)} is not an exact head row; retraction needs "
                    "element multiplicity (use update() for full replacement)"
                )
            if sat[row]:
                raise ValueError(
                    f"doc {int(gid)} has saturated counters (a bin occupancy "
                    f"exceeded COUNTER_MAX={counting.COUNTER_MAX} and was "
                    "clamped); a decrement would silently under-count — "
                    "use update() for full replacement instead"
                )
            rows.append(row)
        self.head.add_counts(np.asarray(rows, np.int64), -deltas)

    def merge(self, other: "SegmentedStore", *, now: float = 0.0) -> "SegmentedStore":
        """OR-merge by global doc id (the shard-local ingestion story of
        ``SketchStore.merge``, keyed on ids instead of row alignment).
        Shared ids OR together (relocating into the head); ids only in
        ``other`` are inserted under their original global id."""
        sk_o, _, ids_o = other.live()
        if len(ids_o) == 0:
            return self
        counts_o = counting.packed_to_counters(sk_o, self.cfg.n_bins)
        known = np.array([int(g) in self._loc for g in ids_o], bool)
        if known.any():
            sel = np.nonzero(known)[0]
            ours = self._gather_packed(ids_o[sel])
            merged = (counting.packed_to_counters(ours, self.cfg.n_bins)
                      + counts_o[jnp.asarray(sel.astype(np.int32))])
            self.delete(ids_o[sel])
            self._insert_counts(merged, ids=ids_o[sel], now=now, exact=False)
        if (~known).any():
            sel = np.nonzero(~known)[0]
            self._insert_counts(
                counts_o[jnp.asarray(sel.astype(np.int32))],
                ids=ids_o[sel], now=now, exact=False,
            )
        self.next_id = max(self.next_id, int(ids_o.max()) + 1)
        return self

    # -------------------------------------------------------------- lifecycle
    def _sort_head(self) -> None:
        """Restore the ascending-id invariant after a sealed-doc relocation
        (lazy: queries and seals sort; plain appends never need it)."""
        h = self.head
        if h.is_sorted or h.size <= 1:
            return
        perm = np.argsort(h.ids[: h.size], kind="stable")
        p = jnp.asarray(perm.astype(np.int32))
        h.counters = h.counters.at[: h.size].set(jnp.take(h.counters[: h.size], p, axis=0))
        h.packed = h.packed.at[: h.size].set(jnp.take(h.packed[: h.size], p, axis=0))
        h.fills = h.fills.at[: h.size].set(jnp.take(h.fills[: h.size], p, axis=0))
        h.sat_dev = h.sat_dev.at[: h.size].set(jnp.take(h.sat_dev[: h.size], p, axis=0))
        for name in ("ids", "valid", "born", "exact"):
            arr = getattr(self.head, name)
            arr[: h.size] = arr[: h.size][perm]
        h.is_sorted = True
        h._meta_cache = None
        h._ttl_cache = None
        for row in range(h.size):
            if h.valid[row]:
                self._loc[int(h.ids[row])] = (_HEAD, row)

    def _band_index_for(
        self, sketches: jax.Array, n_rows: int, backend=None
    ) -> Optional[BandIndex]:
        """Build a :class:`BandIndex` over a freshly sealed slab when the
        store's :class:`BandPolicy` wants one (None otherwise). The keys
        come from ``Backend.band_hash`` when a backend is at hand (the
        Pallas kernel rides the accelerator that already holds the slab),
        else from the jnp oracle — bit-identical either way."""
        bp = self.band_policy
        if bp is None or not bp.wants_index(n_rows):
            return None
        try:
            if backend is not None:
                keys = backend.band_hash(sketches, bp.n_bands)
            else:
                keys = pk.band_hash(sketches, bp.n_bands)
            return BandIndex.build(np.asarray(jax.device_get(keys)))
        except Exception as e:
            # the index is an accelerator, not an availability dependency:
            # an unindexed segment just serves through the exhaustive path
            self.supervisor.record_degraded("band_index", f"build failed: {e}")
            return None

    def seal(self, *, backend=None) -> Optional[SealedSegment]:
        """Freeze the head into a sealed segment (tombstoned head rows are
        dropped here — a free mini-compaction) and start a fresh head.
        Counters are discarded: sealed rows live packed-only from now on.
        With a :class:`BandPolicy` armed, the new segment's prefilter index
        is built here — seal time — over exactly the rows being frozen."""
        h = self.head
        if h.size == 0:
            return None
        with span("store.seal", rows=int(h.valid[: h.size].sum())):
            got = _gather_live(self._parts(sealed=False))
            seg = None
            if got is not None:
                sk, fl, ids, born = got
                seg = SealedSegment(
                    sk, fl, ids, np.ones(len(ids), bool), born,
                    band_index=self._band_index_for(sk, len(ids), backend),
                )
                self.sealed.append(seg)
                seg_i = len(self.sealed) - 1
                for row, gid in enumerate(seg.ids):
                    self._loc[int(gid)] = (seg_i, row)
            cap = h.capacity
            if self.seal_rows is not None:  # an overshooting batch grew it
                cap = min(cap, int(self.seal_rows))
            self.head = _Head.create(self.cfg.n_bins, self.cfg.n_words, cap)
        self._layout_epoch += 1
        return seg

    def seal_sketches(
        self, sketches: jax.Array, *, now: float = 0.0, backend=None
    ) -> range:
        """Bulk-ingest pre-packed rows straight into a sealed segment,
        bypassing the counting head entirely; returns the fresh global ids.

        The head's u16 occupancy counters cost ``2·N`` bytes per resident
        doc — fine for a mutation buffer, prohibitive as an ingest path for
        a million-doc backfill (at N=4096 that transient alone is 8 GiB).
        Rows entering here are frozen immediately (no retraction, like
        ``add_sketches`` after a seal) with ids assigned in row order, so
        the segment satisfies the ascending-id invariant by construction.
        The band index (policy permitting) is built at seal time as usual.
        """
        sketches = sketches.astype(jnp.uint32)
        b = int(sketches.shape[0])
        if b == 0:
            return range(self.next_id, self.next_id)
        if sketches.shape[1] != self.cfg.n_words:
            raise ValueError(
                f"expected (B, {self.cfg.n_words}) packed rows at the base "
                f"width, got {tuple(sketches.shape)}"
            )
        with span("store.seal", rows=b):
            fills = pk.row_popcount(sketches).astype(jnp.int32)
            ids = np.arange(self.next_id, self.next_id + b, dtype=np.int64)
            self.next_id += b
            seg = SealedSegment(
                sketches, fills, ids, np.ones(b, bool),
                np.full(b, float(now), np.float64),
                band_index=self._band_index_for(sketches, b, backend),
            )
            self.sealed.append(seg)
            seg_i = len(self.sealed) - 1
            self._loc.update(
                zip(ids.tolist(), ((seg_i, row) for row in range(b)))
            )
        self._n_live += b
        self._layout_epoch += 1
        return range(int(ids[0]), int(ids[-1]) + 1)

    def _widths_present(self) -> List[Optional[int]]:
        """Distinct sealed sketch widths, base (None) first then descending
        — the deterministic group order compaction and placement share."""
        seen = {s.n_bins for s in self.sealed}
        return [w for w in (None, *sorted(
            (x for x in seen if x is not None), reverse=True)) if w in seen]

    def compact(self) -> Dict[str, int]:
        """Merge sealed segments, dropping tombstoned rows and re-gathering
        the fill caches; rows come out merge-sorted by global id. Segments
        merge **per sketch width** (a distilled N' slab cannot concatenate
        with a base-N one), so a mixed-width store compacts to one segment
        per width tier. The head is untouched (seal first for a full major
        compaction). Synchronous — serving waits; see :meth:`compact_async`
        for the background (and per-device) variant."""
        self.wait_compaction()  # never two compactions over the same slabs
        stats = {
            "segments_in": len(self.sealed),
            "rows_in": sum(s.n_rows for s in self.sealed),
            "rows_out": 0,
            "groups": 0,
        }
        if not self.sealed:
            return stats
        new_sealed: List[SealedSegment] = []
        with span("store.compact", rows_in=stats["rows_in"],
                  rows_out=sum(s.n_live for s in self.sealed)):
            for width in self._widths_present():
                stats["groups"] += 1
                parts = [
                    (seg.sketches, seg.fills, seg.ids, seg.valid, seg.born)
                    for seg in self.sealed if seg.n_bins == width
                ]
                got = _gather_live(parts)
                if got is None:
                    continue
                sk, fl, ids, born = got
                new_sealed.append(SealedSegment(
                    sk, fl, ids, np.ones(len(ids), bool), born, n_bins=width,
                    band_index=self._band_index_for(sk, len(ids)),
                ))
            self._layout_epoch += 1
            self.sealed = new_sealed
            for seg_i, seg in enumerate(self.sealed):
                for row, gid in enumerate(seg.ids):
                    self._loc[int(gid)] = (seg_i, row)
                stats["rows_out"] += seg.n_rows
        return stats

    # ------------------------------------------------- background compaction
    def compact_async(
        self,
        groups: Optional[Sequence[Sequence[int]]] = None,
        *,
        _hold=None,
    ) -> bool:
        """Start a compaction on a background thread; serving never stalls.

        The checkpoint-thread pattern (``CheckpointManager.save``'s async
        path, via the shared :class:`~repro.checkpoint.manager.BackgroundJob`):

          1. **snapshot-to-host** — sealed slabs, fill caches and per-row
             metadata are copied to host memory synchronously (the only
             part the caller waits for);
          2. **merge off-thread** — live rows of each group merge-sort by
             global id in pure numpy against the snapshot, touching no live
             state, so queries and mutations proceed concurrently against
             the *old* segments with zero locking;
          3. **atomic swap** — :meth:`poll_compaction` (called by the query
             paths) or :meth:`wait_compaction` applies the result on the
             caller's thread: tombstones and relocations that landed during
             the merge are *reconciled* (a merged row stays live only if
             the location map still points at its snapshot position), the
             group's segments are replaced, and the location map rebuilds.

        ``groups`` is a list of sealed-segment index groups, each merged
        into one output segment — pass a placement's per-device assignment
        (``SegmentPlacement.assign``) for **device-local** compaction: every
        device's resident set merges into one segment that stays on that
        device at the next placement. Default: one global group. Groups are
        split by sketch width first (a device holding both base-N and
        distilled-N' residents merges each tier separately — the slabs
        cannot concatenate); groups of one tombstone-free segment are
        skipped (nothing to reclaim). Returns False if there was nothing
        to do. ``_hold`` (test seam) is an event the worker waits on before
        returning, pinning the job in the "running" state so interleavings
        can be exercised deterministically.
        """
        self.wait_compaction()
        if groups is None:
            groups = [list(range(len(self.sealed)))]
        groups = [[int(i) for i in g] for g in groups]
        seen: set = set()
        for g in groups:
            for i in g:
                if not 0 <= i < len(self.sealed) or i in seen:
                    raise ValueError(
                        f"compaction group index {i} is out of range or "
                        "duplicated — groups must partition current sealed "
                        "segments (a placement from a stale layout epoch?)"
                    )
                seen.add(i)
        by_width: List[List[int]] = []
        for g in groups:
            tiers: Dict[Optional[int], List[int]] = {}
            for i in g:
                tiers.setdefault(self.sealed[i].n_bins, []).append(i)
            by_width.extend(tiers.values())
        groups = [
            g for g in by_width
            if g and not (len(g) == 1 and self.sealed[g[0]]._all_valid)
        ]
        if not groups:
            return False
        snap = []
        for group in groups:
            segs = [self.sealed[i] for i in group]
            parts = [
                (
                    np.asarray(jax.device_get(s.sketches)),
                    np.asarray(jax.device_get(s.fills)),
                    s.ids.copy(),
                    s.valid.copy(),
                    s.born.copy(),
                )
                for s in segs
            ]
            snap.append((group, parts, segs[0].n_bins))

        band_policy = self.band_policy
        sup = self.supervisor
        rows_in = sum(len(p[2]) for _, parts, _ in snap for p in parts)
        rows_out = sum(int(p[3].sum()) for _, parts, _ in snap for p in parts)

        @_in_span("store.compact", rows_in=rows_in, rows_out=rows_out)
        def work():
            faults.inject("compact.work")
            out = []
            for group, parts, width in snap:
                sk, fl, ids, valid, born, src_seg, src_row = (
                    [], [], [], [], [], [], [],
                )
                for local_i, (s_sk, s_fl, s_ids, s_valid, s_born) in zip(
                    group, parts
                ):
                    keep = np.nonzero(s_valid)[0]
                    sk.append(s_sk[keep])
                    fl.append(s_fl[keep])
                    ids.append(s_ids[keep])
                    born.append(s_born[keep])
                    src_seg.append(np.full(len(keep), local_i, np.int64))
                    src_row.append(keep.astype(np.int64))
                ids_c = np.concatenate(ids)
                order = np.argsort(ids_c, kind="stable")
                merged_sk = np.concatenate(sk, axis=0)[order]
                # prefilter index over the merged slab, built here on the
                # worker thread (host hash twin — no device dispatch
                # contending with serving) so the swap installs it for
                # free. A band-build failure must not fail the merge:
                # the segment comes out unindexed (exhaustive-scan
                # fallback) and the degradation is recorded.
                band_index = None
                if band_policy is not None and band_policy.wants_index(len(ids_c)):
                    try:
                        band_index = BandIndex.build_from_packed(
                            merged_sk, band_policy.n_bands
                        )
                    except Exception as e:
                        sup.record_degraded(
                            "band_index", f"build failed during compaction: {e}"
                        )
                out.append({
                    "group": group,
                    "n_bins": width,
                    "rows_in": sum(len(p[2]) for p in parts),
                    "sketches": merged_sk,
                    "fills": np.concatenate(fl)[order],
                    "ids": ids_c[order],
                    "born": np.concatenate(born)[order],
                    "src_seg": np.concatenate(src_seg)[order],
                    "src_row": np.concatenate(src_row)[order],
                    "band_index": band_index,
                })
            if _hold is not None:
                _hold.wait()
            return out

        key = tuple(sorted(i for g in groups for i in g))
        job = sup.submit("compact", key, work)
        if job is None:  # quarantined: keep serving the current segments
            return False
        self._compaction = _CompactionJob(
            job, [self.sealed[i] for g in groups for i in g]
        )
        return True

    # ------------------------------------------------ background distillation
    def distill_async(
        self,
        policy: DistillPolicy,
        *,
        now: float = 0.0,
        only: Optional[Sequence[int]] = None,
        _hold=None,
    ) -> bool:
        """Re-sketch policy-eligible sealed segments to their next smaller
        width tier, off-thread, and atomically swap them in — trading
        memory for recall **per segment** (DESIGN.md §11).

        A distillation is a compaction whose merge step also re-buckets:
        the same checkpoint-thread pattern as :meth:`compact_async`
        (snapshot-to-host → work off-thread → swap with tombstone
        reconciliation on the caller's thread via :meth:`poll_compaction` /
        :meth:`wait_compaction`), with the off-thread work being *drop dead
        rows, OR-fold N→N' (``j -> j mod N'``), re-gather fill counts* —
        pure host math over the snapshot, never the raw documents. Each
        eligible segment folds independently (no cross-segment merge: the
        inputs may sit at different tiers), tombstones that land mid-fold
        reconcile exactly like mid-merge deletes, and the swap bumps the
        layout epoch so placements rebuild with the new widths. Returns
        False when no segment is eligible.

        ``only`` restricts eligibility to the given sealed-segment indices
        (the lifecycle controller passes its cold set, so a hot segment
        never folds however old it is); None keeps the policy-only
        behaviour.
        """
        self.wait_compaction()  # one background job over the slabs at a time
        base = self.cfg.n_bins
        allow = None if only is None else {int(i) for i in only}
        plan: List[Tuple[int, int]] = []
        for i, seg in enumerate(self.sealed):
            if seg.n_live == 0 or (allow is not None and i not in allow):
                continue
            cur = seg.n_bins if seg.n_bins is not None else base
            age = float(now) - float(seg.born[seg.valid].max())
            tgt = policy.target_width(cur, age, seg.n_live)
            if tgt is not None and tgt < cur:
                plan.append((i, tgt))
        if not plan:
            return False
        snap = []
        for i, tgt in plan:
            seg = self.sealed[i]
            cur = seg.n_bins if seg.n_bins is not None else base
            snap.append((
                i, cur, tgt,
                np.asarray(jax.device_get(seg.sketches)),
                seg.ids.copy(), seg.valid.copy(), seg.born.copy(),
            ))

        band_policy = self.band_policy
        sup = self.supervisor
        rows_in = sum(len(x[4]) for x in snap)
        rows_out = sum(int(x[5].sum()) for x in snap)

        @_in_span("store.distill", rows_in=rows_in, rows_out=rows_out)
        def work():
            faults.inject("distill.work")
            out = []
            for i, cur, tgt, sk, ids, valid, born in snap:
                keep = np.nonzero(valid)[0]  # ids ascend within one segment:
                folded, fills = _fold_packed_host(sk[keep], cur, tgt)
                if faults.fire("distill.corrupt"):
                    # silent corruption: the fold "succeeds" but its output
                    # is garbage — no error for the supervisor to catch;
                    # only the recall probe can see it (guardrail tests)
                    folded = np.zeros_like(folded)
                    fills = np.zeros_like(fills)
                # the folded rows are a *different* signature space (N'
                # bins, fewer words): the tier gets its own index, re-
                # derived from the folded slab — base-width buckets must
                # never serve a distilled segment. As in compaction, a
                # band-build failure degrades (unindexed segment), never
                # fails the fold.
                band_index = None
                if band_policy is not None and band_policy.wants_index(len(keep)):
                    try:
                        band_index = BandIndex.build_from_packed(
                            folded, band_policy.n_bands
                        )
                    except Exception as e:
                        sup.record_degraded(
                            "band_index", f"build failed during distillation: {e}"
                        )
                out.append({  # keep-order == id order, no re-sort needed
                    "group": [i],
                    "n_bins": tgt,
                    "rows_in": len(ids),
                    "sketches": folded,
                    "fills": fills,
                    "ids": ids[keep],
                    "born": born[keep],
                    "src_seg": np.full(len(keep), i, np.int64),
                    "src_row": keep.astype(np.int64),
                    "band_index": band_index,
                })
            if _hold is not None:
                _hold.wait()
            return out

        key = tuple(sorted(i for i, _ in plan))
        job = sup.submit("distill", key, work)
        if job is None:  # quarantined: the tier stays at its current width
            return False
        self._compaction = _CompactionJob(
            job, [self.sealed[i] for i, _ in plan]
        )
        return True

    def poll_compaction(self) -> bool:
        """Swap in a *finished* background compaction, without blocking.
        Called by the engine's query paths, so serving picks the result up
        the moment it is ready; returns True when a swap happened.

        NEVER raises a maintenance error into the caller (the caller is a
        query): the supervisor retries transient failures with backoff
        (each poll advances the state machine), and a terminally-failed or
        abandoned job is dropped — its snapshot discarded, the store left
        serving the consistent pre-swap state it never stopped serving.
        Failures are visible in ``supervisor.health()``, not in queries."""
        job = self._compaction
        if job is None:
            return False
        state = self.supervisor.poll(job.job)
        if state == "running":
            return False
        self._compaction = None
        if state != "succeeded":
            return False  # logged + counted by the supervisor; serve on
        return self._apply_swap(job) is not None

    def wait_compaction(self) -> Optional[Dict[str, int]]:
        """Drive the background compaction (if any) to a terminal state —
        sleeping through retry backoff — and apply its swap; returns the
        compaction stats, or None if no job was pending or the job failed
        (like :meth:`poll_compaction`, failures never raise here)."""
        job = self._compaction
        if job is None:
            return None
        self._compaction = None
        state = self.supervisor.wait(job.job)
        if state != "succeeded":
            return None
        return self._apply_swap(job)

    def abandon_compaction(self, op: Optional[str] = None) -> bool:
        """Abandon the in-flight background job *now* (no swap, no wait).

        ``op`` filters by operation name (``"distill"`` lets the recall
        guardrail kill a distillation without touching a running merge);
        None abandons whatever is pending. The supervisor drops every
        reference to the worker's future result, so even a fold that
        completes after this call can never be swapped in — the store
        keeps serving the consistent pre-swap state. Returns True iff a
        pending job was discarded (a worker that already finished is
        discarded unswapped; the supervisor's ``abandoned`` counter bumps
        only for still-running attempts)."""
        pending = self._compaction
        if pending is None:
            return False
        if op is not None and pending.job.op != op:
            return False
        self._compaction = None
        self.supervisor.abandon(pending.job)
        return True

    def _apply_swap(self, job: "_CompactionJob") -> Optional[Dict[str, int]]:
        """Final guard between a succeeded worker and the query path: a
        swap that itself blows up (it only *mutates* at the very end, so
        the store stays consistent) is recorded, never raised."""
        try:
            return self._swap_compaction(job, job.job.result)
        except Exception as e:
            self.supervisor.record_degraded("compaction_swap", str(e))
            return None

    def _swap_compaction(self, job, results) -> Dict[str, int]:
        """Atomic swap on the caller's thread (step 3 of the pattern).

        The merge ran against a snapshot; the store may have moved on. A
        merged row is still live only if its *source* row is still live
        right now: every mutation that kills a sealed doc mid-merge
        (delete, relocating update/merge, expiry) flips exactly that
        source bitmap bit, and a dead sealed row can never come back (ids
        are never reused, relocation only tombstones) — so liveness is one
        numpy gather per source segment, not a per-row location-map probe.
        Mid-merge casualties therefore come out as tombstones in the new
        segment (reclaimed by the *next* compaction), never as resurrected
        rows; segments sealed after the snapshot are untouched. This runs
        on the serving thread via ``poll_compaction``, hence the
        vectorized reconcile and the batched location-map rebuild.
        """
        for seg in job.segments:  # seal() only appends, compact() is serialized
            assert any(s is seg for s in self.sealed), (
                "sealed segment vanished during background compaction"
            )
        replaced = {id(s) for s in job.segments}
        stats = {
            "segments_in": sum(len(r["group"]) for r in results),
            "rows_in": sum(r["rows_in"] for r in results),
            "rows_out": 0,
            "groups": len(results),
        }
        new_sealed: List[SealedSegment] = []
        for r in results:
            n = len(r["ids"])
            if n == 0:
                continue
            live = np.zeros(n, bool)
            for s in np.unique(r["src_seg"]):
                sel = r["src_seg"] == s
                live[sel] = self.sealed[int(s)].valid[r["src_row"][sel]]
            new_sealed.append(SealedSegment(
                jnp.asarray(r["sketches"]),
                jnp.asarray(r["fills"]),
                r["ids"],
                live,
                r["born"],
                n_bins=r.get("n_bins"),
                band_index=r.get("band_index"),
            ))
            stats["rows_out"] += n
        new_sealed.extend(s for s in self.sealed if id(s) not in replaced)
        self.sealed = new_sealed
        self._loc = {
            g: loc for g, loc in self._loc.items() if loc[0] == _HEAD
        }
        for seg_i, seg in enumerate(self.sealed):
            rows = np.nonzero(seg.valid)[0]
            self._loc.update(
                zip(seg.ids[rows].tolist(),
                    ((seg_i, int(row)) for row in rows))
            )
        self._layout_epoch += 1
        self._valid_epoch += 1
        return stats

    def expire(self, ttl: float, now: float) -> int:
        """Tombstone every live doc aged out at ``now`` — the *same*
        ``born + ttl <= now`` predicate the lazy query-time mask applies,
        so a doc on the boundary cannot be invisible to queries yet
        unreclaimable by the sweep. Space comes back at the next
        seal/compact."""
        h = self.head
        hits = np.nonzero(h.valid[: h.size] & (h.born[: h.size] + ttl <= now))[0]
        dead = [int(g) for g in h.ids[: h.size][hits]]
        for seg in self.sealed:
            hits = np.nonzero(seg.valid & (seg.born + ttl <= now))[0]
            dead.extend(int(g) for g in seg.ids[hits])
        if dead:
            self.delete(dead)
            obs_metrics.inc("lifecycle.expired", len(dead))
        return len(dead)

    # ------------------------------------------------------------ checkpoint
    def checkpoint_tree(self) -> Tuple[dict, dict]:
        """(pytree of arrays, aux metadata) for ``CheckpointManager.save``.

        ``born`` timestamps travel in aux (json doubles are exact float64;
        tree leaves get device_put on restore, which demotes 64-bit dtypes
        under default-precision jax and would blunt TTL resolution). A
        finished background compaction is folded in first; a still-running
        one is *not* waited for — the snapshot captures the consistent
        pre-swap state."""
        self.poll_compaction()
        self._sort_head()
        h = self.head
        tree = {
            "mapping": self.mapping,
            "head": {
                "counters": h.counters[: h.size],
                "packed": h.packed[: h.size],
                "fills": h.fills[: h.size],
                "ids": h.ids[: h.size].copy(),
                "valid": h.valid[: h.size].copy(),
                "exact": h.exact[: h.size].copy(),
                "saturated": h.sat_dev[: h.size],
            },
            "sealed": [
                {
                    "sketches": s.sketches,
                    "fills": s.fills,
                    "ids": s.ids.copy(),
                    "valid": s.valid.copy(),
                }
                for s in self.sealed
            ],
        }
        aux = {
            "kind": "segmented_store",
            "cfg": {"d": self.cfg.d, "n_bins": self.cfg.n_bins, "mode": self.cfg.mode},
            "next_id": int(self.next_id),
            "seal_rows": self.seal_rows,
            "ttl": self.ttl,
            "head_rows": int(h.size),
            "sealed_rows": [s.n_rows for s in self.sealed],
            # per-segment sketch width (null = base): a distilled corpus
            # cold-restores mixed-width — shapes below depend on this
            "sealed_n_bins": [s.n_bins for s in self.sealed],
            "head_born": h.born[: h.size].tolist(),
            "sealed_born": [s.born.tolist() for s in self.sealed],
            # prefilter config only — the BandIndex itself is derived state
            # (pure function of a sealed slab + policy) and is rebuilt from
            # the restored sketches, never serialized
            "band_policy": (
                self.band_policy.to_aux() if self.band_policy else None
            ),
        }
        return tree, aux

    def save(self, manager, step: int, blocking: bool = True) -> None:
        tree, aux = self.checkpoint_tree()
        manager.save(step, tree, aux=aux, blocking=blocking)

    @classmethod
    def restore(cls, manager, step: Optional[int] = None) -> "SegmentedStore":
        """Cold-restore from a checkpoint: shapes come from the aux manifest
        (no live store needed), nothing is re-sketched, and the location
        map / live count rebuild from the restored tombstone bitmaps.

        The step is pinned via ``manager.resolve_step`` first — the newest
        *verifying* generation — so the aux manifest read here and the
        arrays read in ``manager.restore`` come from the same sound
        checkpoint even when the latest write was torn."""
        step = manager.resolve_step(step)
        aux = manager.load_aux(step)
        if aux.get("kind") != "segmented_store":
            raise ValueError(f"checkpoint is not a SegmentedStore snapshot: {aux.get('kind')!r}")
        cfg = binsketch.BinSketchConfig(**aux["cfg"])
        w, n = cfg.n_words, cfg.n_bins
        hr = int(aux["head_rows"])
        # pre-distillation checkpoints have no width manifest: all base
        seg_widths = aux.get("sealed_n_bins") or [None] * len(aux["sealed_rows"])
        map_shape = (cfg.d,) if cfg.mode == "table" else (2,)
        map_dtype = jnp.int32 if cfg.mode == "table" else jnp.uint32
        target = {
            "mapping": jnp.zeros(map_shape, map_dtype),
            "head": {
                "counters": jnp.zeros((hr, n), counting.COUNTER_DTYPE),
                "packed": jnp.zeros((hr, w), jnp.uint32),
                "fills": jnp.zeros((hr,), jnp.int32),
                "ids": np.zeros((hr,), np.int64),
                "valid": np.zeros((hr,), bool),
                "exact": np.zeros((hr,), bool),
                "saturated": jnp.zeros((hr,), jnp.bool_),
            },
            "sealed": [
                {
                    "sketches": jnp.zeros(
                        (r, pk.num_words(nb) if nb else w), jnp.uint32
                    ),
                    "fills": jnp.zeros((r,), jnp.int32),
                    "ids": np.zeros((r,), np.int64),
                    "valid": np.zeros((r,), bool),
                }
                for r, nb in zip(aux["sealed_rows"], seg_widths)
            ],
        }
        tree, _ = manager.restore(step, target)
        store = cls.create(cfg, tree["mapping"], capacity=max(hr, 1),
                           seal_rows=aux["seal_rows"], ttl=aux.get("ttl"),
                           band_policy=BandPolicy.from_aux(aux.get("band_policy")))
        store.next_id = int(aux["next_id"])
        ht = tree["head"]
        h = store.head
        h.counters = h.counters.at[:hr].set(ht["counters"].astype(counting.COUNTER_DTYPE))
        h.packed = h.packed.at[:hr].set(ht["packed"].astype(jnp.uint32))
        h.fills = h.fills.at[:hr].set(ht["fills"].astype(jnp.int32))
        h.ids[:hr] = np.asarray(ht["ids"])
        h.valid[:hr] = np.asarray(ht["valid"])
        h.born[:hr] = np.asarray(aux["head_born"], np.float64)
        h.exact[:hr] = np.asarray(ht["exact"])
        h.sat_dev = h.sat_dev.at[:hr].set(jnp.asarray(ht["saturated"]))
        h.size = hr
        for st, born, nb in zip(tree["sealed"], aux["sealed_born"], seg_widths):
            sk = st["sketches"].astype(jnp.uint32)
            store.sealed.append(SealedSegment(
                sketches=sk,
                fills=st["fills"].astype(jnp.int32),
                # np.array copies: device buffers come back read-only, and
                # the tombstone bitmap must stay mutable
                ids=np.array(st["ids"], np.int64),
                valid=np.array(st["valid"], bool),
                born=np.asarray(born, np.float64),
                n_bins=int(nb) if nb else None,
                # derived state: rebuilt from the restored slab, identical
                # to the pre-checkpoint index (same rows, same hash)
                band_index=store._band_index_for(sk, int(st["sketches"].shape[0])),
            ))
        for seg_i, seg in enumerate(store.sealed):
            for row in np.nonzero(seg.valid)[0]:
                store._loc[int(seg.ids[row])] = (seg_i, int(row))
        for row in np.nonzero(h.valid[:hr])[0]:
            store._loc[int(h.ids[row])] = (_HEAD, int(row))
        store._n_live = len(store._loc)
        return store
