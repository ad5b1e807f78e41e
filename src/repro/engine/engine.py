"""SketchEngine — the streaming, shard-aware serving front-end (DESIGN.md §6).

Composes the three engine pieces into the paper's §IV-B ranking experiment
run as a service:

  * :class:`~repro.engine.store.SketchStore` — packed corpus, incremental
    OR-homomorphic ingest, ingest-time fill-count cache;
  * a :class:`~repro.engine.backends.Backend` — sketch + score kernels
    behind one name (no ``interpret=`` plumbing, no scorer callables);
  * a :class:`~repro.engine.planner.QueryPlanner` — ragged query batches
    bucketed onto a bounded set of jit shapes.

Both query paths are streaming end-to-end (DESIGN.md §7): single-device
``query`` and the per-shard body of ``query_sharded`` go through
``Backend.topk``, so no (Q, C) — or (Q, C_loc) — score matrix is ever
materialized; only O(Q·k) leaves each scoring kernel.

``query_sharded`` on a :class:`SegmentedStore` uses **segment placement**
(DESIGN.md §10): a :class:`~repro.engine.placement.SegmentPlacer` assigns
whole sealed segments to mesh devices (balanced by live-row count, head
replicated), resident slabs are uploaded once per placement epoch, and
each query runs the fused streaming top-k per device over only its
resident rows — one all-gather of O(k) rows per device, not one collective
(plus a corpus re-shard) per segment. On an append-only
:class:`SketchStore` — a single slab with nothing to place — the original
row-sharded path remains: the corpus is sliced across the mesh, padded
with zero sketches whose slots are masked to -inf / -1 (no silent tail
drop for non-divisible C).

Serving is **mixed-width** (DESIGN.md §11): distilled segments live at a
smaller sketch width N', and every query path re-buckets the query batch
once per distinct resident width (``Backend.rebucket``, cached per plan)
before streaming that width's slabs — the fold identity makes the folded
queries exactly the N'-sketches of the raw queries.
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Callable, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

import numpy as np

from .. import obs
from ..core import binsketch
from ..obs import metrics as obs_metrics
from ..obs import trace as obs_trace
from ..obs.trace import span, stage
from . import backends as backends_mod
from .backends import Backend
from .banding import BandPolicy
from .placement import SegmentPlacement, SegmentPlacer, WidthSlab
from .planner import QueryPlanner
from .segments import DistillPolicy, SegmentedStore
from .store import SegmentView, SketchStore
from .supervision import JobSupervisor

__all__ = ["SketchEngine", "merge_segment_topk", "shard_topk"]


def merge_segment_topk(parts_s, parts_i, k: int) -> Tuple[jax.Array, jax.Array]:
    """Merge per-segment (Q, k) top-k partials into one global (Q, k).

    Unlike the chunked merges elsewhere (whose concatenation order encodes
    ascending doc id, so ``lax.top_k``'s positional tie-break is the id
    tie-break), segments of a mutated store can hold *interleaved* id
    ranges — an updated sealed doc relocates into the head under its old,
    low id. Ties must therefore break toward the lower **global id**
    explicitly: two stable sorts (id ascending, then score descending)
    reproduce exactly the ordering a fresh batch-built store would give.
    ``-inf`` slots already carry id -1 and sink to the tail.
    """
    sc = jnp.concatenate(parts_s, axis=1)
    ids = jnp.concatenate(parts_i, axis=1)
    order = jnp.argsort(ids, axis=1, stable=True)
    sc = jnp.take_along_axis(sc, order, axis=1)
    ids = jnp.take_along_axis(ids, order, axis=1)
    order = jnp.argsort(-sc, axis=1, stable=True)
    sc = jnp.take_along_axis(sc, order, axis=1)[:, :k]
    ids = jnp.take_along_axis(ids, order, axis=1)[:, :k]
    return sc, jnp.where(jnp.isneginf(sc), -1, ids)


def shard_topk(
    qs: jax.Array,
    cand: jax.Array,
    n_bins: int,
    measure: str,
    k: int,
    axis: str,
    *,
    backend: Optional[Backend] = None,
    cand_fills: Optional[jax.Array] = None,
    cand_ids: Optional[jax.Array] = None,
    cand_valid: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Per-shard streaming top-k -> O(k·devices) all-gather merge.

    Call *inside* ``shard_map``: ``cand`` (C_loc, W) is this shard's slice of
    the candidates, ``qs`` (Q, W) is replicated. ``cand_ids`` are this
    shard's global doc ids (default: offset arange); ``cand_valid`` masks
    padding rows (their slots become -inf / -1 so they never reach the
    merged top-k). The local pass goes through ``Backend.topk`` — the fused
    streaming kernel on pallas backends, the chunked ``lax.top_k`` merge on
    the oracle — so no shard ever materializes its full (Q, C_loc) score
    matrix. Shared by the engine's sharded path and the recsys retrieval
    tower.
    """
    be = backend if backend is not None else backends_mod.OracleBackend()
    sc, ix = be.topk(
        qs, cand, n_bins, measure, k,
        corpus_fills=cand_fills, corpus_valid=cand_valid,
    )
    if cand_ids is None:
        lo = jax.lax.axis_index(axis) * cand.shape[0]
        ids = jnp.where(ix >= 0, lo + ix, -1)
    else:
        ids = jnp.where(ix >= 0, jnp.take(cand_ids, jnp.maximum(ix, 0), axis=0), -1)
    sc_all = jax.lax.all_gather(sc, axis, axis=1, tiled=True)  # (Q, shards*k)
    ids_all = jax.lax.all_gather(ids, axis, axis=1, tiled=True)
    sc2, pos = jax.lax.top_k(sc_all, k)
    return sc2, jnp.take_along_axis(ids_all, pos, axis=1)


@dataclasses.dataclass
class SketchEngine:
    """Build + serve over a :class:`SketchStore` or :class:`SegmentedStore`
    through one backend."""

    store: "SketchStore | SegmentedStore"
    backend: Backend
    measure: str = "jaccard"
    planner: QueryPlanner = dataclasses.field(default_factory=QueryPlanner)
    placer: SegmentPlacer = dataclasses.field(default_factory=SegmentPlacer)
    # shared obs.Clock (DESIGN.md §14): when set, queries without an
    # explicit ``now`` resolve TTL/age time against it, and metrics/trace
    # timestamps ride the same source — one fake clock drives everything
    clock: Optional[Callable[[], float]] = None
    _placement: Optional[SegmentPlacement] = dataclasses.field(
        default=None, init=False, repr=False
    )
    # observability for the banded prefilter (DESIGN.md §12): per query
    # call, how many sealed rows were considered vs how many candidates
    # survived banding, and how many segments fell back to the exhaustive
    # scan. None until a prefiltered query runs; benches and the smoke gate
    # read it to assert the candidate-fraction ceiling.
    last_prefilter_stats: Optional[dict] = dataclasses.field(
        default=None, init=False, repr=False
    )
    # fallback supervisor for engines over an append-only SketchStore
    # (which has no lifecycle jobs but can still record degraded modes);
    # mutable engines use the store's own — see :attr:`supervisor`
    _own_supervisor: Optional[JobSupervisor] = dataclasses.field(
        default=None, init=False, repr=False
    )
    # the attached LifecycleController (engine/lifecycle.py); set by the
    # controller's own __init__ so ``metrics()`` can expose its state —
    # the engine never calls into it
    controller: Optional[object] = dataclasses.field(
        default=None, init=False, repr=False
    )
    # query calls so far, the ``call`` stat of each ``repro.engine.query``
    # span: it tells one call's spans from the next in a profile
    _calls: int = dataclasses.field(default=0, init=False, repr=False)

    # ------------------------------------------------------------ construct
    @classmethod
    def build(
        cls,
        cfg: binsketch.BinSketchConfig,
        mapping: jax.Array,
        corpus_idx: Optional[jax.Array] = None,
        *,
        backend=None,
        measure: str = "jaccard",
        planner: Optional[QueryPlanner] = None,
        capacity: int = 1024,
        batch: int = 4096,
        mutable: bool = False,
        seal_rows: Optional[int] = None,
        ttl: Optional[float] = None,
        band_policy: Optional[BandPolicy] = None,
        supervisor: Optional[JobSupervisor] = None,
        clock: Optional[Callable[[], float]] = None,
    ) -> "SketchEngine":
        """Create an engine; ``corpus_idx`` (C, P) is ingested if given,
        otherwise the engine starts empty and is fed via :meth:`add`.
        ``mutable=True`` builds over a :class:`SegmentedStore` (counting
        head + sealed segments) so the corpus also supports ``delete`` /
        ``update`` / ``seal`` / ``compact`` / ``expire``; ``seal_rows``
        auto-seals the head at that many rows; ``ttl`` arms lazy expiry —
        queries carrying a ``now`` mask out docs older than ``ttl`` without
        waiting for an ``expire()`` sweep; ``band_policy`` arms the banded
        LSH prefilter — sealed segments grow bucket indexes and queries
        scan only colliding buckets (DESIGN.md §12)."""
        be = backends_mod.get_backend(backend)
        if (seal_rows is not None or ttl is not None
                or band_policy is not None) and not mutable:
            raise ValueError("seal_rows/ttl/band_policy require mutable=True "
                             "(append-only SketchStore has no head to seal, "
                             "no clock, no sealed segments to band)")
        store_cls = SegmentedStore if mutable else SketchStore
        kw = ({"seal_rows": seal_rows, "ttl": ttl, "band_policy": band_policy,
               "supervisor": supervisor, "clock": clock}
              if mutable else {})
        if corpus_idx is not None:
            store = store_cls.from_indices(
                cfg, mapping, corpus_idx, backend=be, batch=batch, **kw
            )
        else:
            store = store_cls.create(cfg, mapping, capacity=capacity, **kw)
        eng = cls(store, be, measure, planner or QueryPlanner(), clock=clock)
        if supervisor is not None and not mutable:
            eng._own_supervisor = supervisor
        return eng

    # -------------------------------------------------------- observability
    @property
    def supervisor(self) -> JobSupervisor:
        """The supervisor governing this engine's background jobs and
        degraded-mode records: the mutable store's own, or a lazily-created
        engine-local one over an append-only store."""
        sup = getattr(self.store, "supervisor", None)
        if sup is not None:
            return sup
        if self._own_supervisor is None:
            self._own_supervisor = JobSupervisor(clock=self.clock)
        return self._own_supervisor

    def health(self) -> dict:
        """Operational snapshot (DESIGN.md §13): background-job counters
        (launched/succeeded/failed/retries/abandoned/refused per op),
        active quarantines, degraded query-path components with reasons,
        last error, and job latencies (p50/p99/max per op). JSON-safe;
        also one section of :meth:`metrics`."""
        return self.supervisor.health()

    def _auto_now(self, now: Optional[float]) -> Optional[float]:
        """Explicit ``now`` wins; else the injected clock (engine's, or the
        store's); else None — the pre-clock convention."""
        if now is not None:
            return float(now)
        c = self.clock if self.clock is not None \
            else getattr(self.store, "clock", None)
        return float(c()) if c is not None else None

    def enable_metrics(self, *, sample: int = 1, capacity: int = 64):
        """Arm the telemetry plane (module-global, like ``faults``) on this
        engine's clock; returns the fresh
        :class:`~repro.obs.metrics.MetricsRegistry`. Disarm with
        ``obs.disable()``."""
        return obs.enable(
            clock=self.clock if self.clock is not None
            else getattr(self.store, "clock", None),
            sample=sample, capacity=capacity,
        )

    def metrics(self, now: Optional[float] = None) -> dict:
        """One JSON-safe telemetry snapshot (DESIGN.md §14) — the surface
        the lifecycle controller (``engine/lifecycle.py``) consumes and
        ``serve.py --metrics-json`` dumps. Composes:

        * the armed registry's counters / gauges / histograms (query-stage
          latencies, lifecycle throughput, degraded-mode counts; empty
          dicts while disarmed),
        * ``lifecycle``: per-segment live/tombstone/width/age/**hits**
          gauges, width mix and tombstone density, computed on demand from
          store state (always available, registry or not),
        * ``health``: the §13 supervision snapshot,
        * ``probe``: the latest online recall reading (gauges
          ``probe.recall`` / ``probe.at``; None until a probe lands),
        * ``controller``: the attached lifecycle controller's state
          machine + action counters (§16; absent when none is attached),
        * ``prefilter`` / ``last_trace`` when available.
        """
        now = self._auto_now(now)
        reg = obs_metrics.active()
        snap = (reg.snapshot() if reg is not None
                else {"at": 0.0, "counters": {}, "gauges": {},
                      "histograms": {}})
        out = {
            "at": float(now) if now is not None else float(snap["at"]),
            "armed": reg is not None,
            "counters": snap["counters"],
            "gauges": snap["gauges"],
            "histograms": snap["histograms"],
            "health": self.health(),
            "probe": {
                "recall": snap["gauges"].get("probe.recall"),
                "at": snap["gauges"].get("probe.at"),
                "runs": int(snap["counters"].get("probe.runs", 0)),
            },
        }
        if isinstance(self.store, SegmentedStore):
            out["lifecycle"] = self.store.lifecycle_snapshot(now=now)
        else:
            n = int(self.store.size)
            out["lifecycle"] = {
                "segments": [], "head": None, "live_docs": n,
                "tombstone_density": 0.0,
                "width_mix": {str(self.cfg.n_bins): n} if n else {},
            }
        if self.controller is not None:
            out["controller"] = self.controller.controller_state()
        if self.last_prefilter_stats is not None:
            out["prefilter"] = dict(self.last_prefilter_stats)
        col = obs_trace.active()
        if col is not None:
            out["last_trace"] = col.last()
        return out

    def _count_view_hits(self) -> None:
        """Per-segment access accounting for the exhaustive paths (one hit
        per segment per scoring pass; the banded path counts inline, since
        it can skip segments). Always-on host ints — see
        ``SealedSegment.hits``."""
        st = self.store
        if not isinstance(st, SegmentedStore):
            return
        for seg in st.sealed:
            if seg.n_rows:
                seg.hits += 1
        if st.head.size:
            st.head_hits += 1

    def _count_slab_hits(self, n_bins: int) -> None:
        """Hit accounting for the placed path: a scored width slab touches
        every sealed segment of that width (slab granularity — the placed
        path never skips individual segments within a slab)."""
        st = self.store
        if not isinstance(st, SegmentedStore):
            return
        base = self.cfg.n_bins
        for seg in st.sealed:
            if seg.n_rows and (
                seg.n_bins if seg.n_bins is not None else base
            ) == n_bins:
                seg.hits += 1

    # ---------------------------------------------------------------- ingest
    @property
    def cfg(self) -> binsketch.BinSketchConfig:
        return self.store.cfg

    def add(self, idx: jax.Array, *, batch: int = 4096, now: float = 0.0) -> range:
        """Stream (B, P) padded sparse docs into the corpus; returns ids.
        ``now`` stamps the docs' birth time on a mutable store (TTL expiry
        measures age against it); append-only stores ignore it."""
        if isinstance(self.store, SegmentedStore):
            return self.store.add(idx, backend=self.backend, batch=batch, now=now)
        return self.store.add(idx, backend=self.backend, batch=batch)

    def merge_rows(self, doc_ids: jax.Array, idx: jax.Array) -> None:
        """OR new content into existing docs (see SketchStore.merge_rows)."""
        self.store.merge_rows(doc_ids, idx, backend=self.backend)

    # ------------------------------------------------- lifecycle (mutable)
    def _mutable_store(self) -> SegmentedStore:
        if not isinstance(self.store, SegmentedStore):
            raise TypeError(
                "this engine serves an append-only SketchStore; build with "
                "mutable=True for delete/update/seal/compact/expire"
            )
        return self.store

    def delete(self, doc_ids) -> int:
        """Tombstone docs (head rows zeroed, sealed rows mask-flipped)."""
        return self._mutable_store().delete(doc_ids)

    def update(self, doc_ids, idx: jax.Array, *, now: float = 0.0) -> None:
        """Replace doc contents in place (ids survive; sealed docs relocate
        into the counting head)."""
        self._mutable_store().update(doc_ids, idx, backend=self.backend, now=now)

    def retract_rows(self, doc_ids, idx: jax.Array) -> None:
        """Decrement elements out of head-resident docs (counting sketch)."""
        self._mutable_store().retract_rows(doc_ids, idx, backend=self.backend)

    def seal(self):
        """Freeze the counting head into a packed sealed segment (building
        its prefilter index at seal time when a band policy is armed)."""
        return self._mutable_store().seal(backend=self.backend)

    def compact(self, *, background: bool = False, _hold=None):
        """Merge sealed segments, dropping tombstones.

        ``background=False`` (default): synchronous global merge; returns
        stats. ``background=True``: start the merge on the checkpoint-style
        worker thread and return immediately (None) — serving continues on
        the old segments and the query paths swap the result in the moment
        it is ready (or call :meth:`wait_compaction` for the stats). When a
        placement is live (a ``query_sharded`` ran), the background merge
        is **device-local**: one group per mesh device over exactly its
        resident segments, so each merged segment lands back on its device
        at the next placement instead of one global slab hot-spotting one
        device."""
        store = self._mutable_store()
        if not background:
            return store.compact()
        # adopt any pending job *before* reading the placement: its swap
        # reindexes the sealed list and bumps the layout epoch, so groups
        # captured earlier would point at the wrong (or vanished) segments
        store.wait_compaction()
        groups = None
        p = self._placement
        if p is not None and p.layout_epoch == store._layout_epoch:
            groups = [g for g in p.assign if g]
        store.compact_async(groups=groups, _hold=_hold)
        return None

    def poll_compaction(self) -> bool:
        """Non-blocking: swap in a finished background compaction."""
        return self._mutable_store().poll_compaction()

    def wait_compaction(self):
        """Join + swap the background compaction; returns its stats."""
        return self._mutable_store().wait_compaction()

    def expire(self, ttl: float, now: float) -> int:
        """Tombstone docs older than ``ttl``."""
        return self._mutable_store().expire(ttl, now)

    def distill(
        self,
        policy: Optional[DistillPolicy] = None,
        *,
        widths=None,
        now: float = 0.0,
        background: bool = True,
        _hold=None,
    ):
        """Re-sketch policy-eligible sealed segments to their next smaller
        width tier (DESIGN.md §11) — memory traded for recall per segment.

        ``policy`` (or the ``widths`` shorthand: an unconditional
        :class:`~repro.engine.segments.DistillPolicy` over those tiers)
        decides which segments drop. ``background=True`` (default) starts
        the fold on the checkpoint-style worker thread and returns whether
        a job started — serving continues on the old segments and the
        query paths swap the result in the moment it is ready;
        ``background=False`` additionally waits and returns the swap stats
        (None if nothing was eligible). Queries after the swap are served
        mixed-width automatically: the engine re-buckets each query batch
        once per distinct resident width.
        """
        store = self._mutable_store()
        if policy is None:
            if widths is None:
                raise ValueError("pass a DistillPolicy or widths=(N', ...)")
            policy = DistillPolicy(widths=tuple(widths))
        started = store.distill_async(policy, now=now, _hold=_hold)
        if not background:
            return store.wait_compaction() if started else None
        return started

    # ----------------------------------------------------------------- query
    def _sketch_queries(self, query_idx: jax.Array) -> jax.Array:
        return self.backend.sketch(self.cfg, self.store.mapping, query_idx)

    def _padded_query_sketches(self, query_idx: jax.Array, padded: int) -> jax.Array:
        q = query_idx.shape[0]
        if padded > q:
            pad = jnp.full((padded - q, query_idx.shape[1]), -1, query_idx.dtype)
            query_idx = jnp.concatenate([query_idx, pad], axis=0)
        return self._sketch_queries(query_idx)

    def score_all(
        self, query_idx: jax.Array, *, use_fill_cache: bool = True
    ) -> jax.Array:
        """(Q, P) padded query rows -> full (Q, C) similarity matrix.

        Materializes O(Q·C) — analysis/benchmark surface only; the serving
        path is :meth:`query`. On a segmented store, column ``j`` is the
        j-th *live* doc in ascending global-id order
        (``store.live_ids[j]``). Query fills are left to the backend so the
        popcount fuses into the jit'd scoring kernel instead of running
        eagerly out here. ``use_fill_cache=False`` forces the legacy
        per-query corpus popcount (benchmark baseline only)."""
        if query_idx.shape[0] == 0:
            return jnp.zeros((0, self.store.size), jnp.float32)
        out = []
        if isinstance(self.store, SegmentedStore):
            corpus, corpus_fills, _ = self.store.live()  # one gather, not two
        else:
            corpus, corpus_fills = self.store.sketches, self.store.fills
        fills = corpus_fills if use_fill_cache else None
        for chunk in self.planner.plan(query_idx.shape[0]):
            qs = self._padded_query_sketches(
                query_idx[chunk.start : chunk.start + chunk.rows], chunk.padded
            )
            s = self.backend.score(
                qs, corpus, self.cfg.n_bins, self.measure, corpus_fills=fills,
            )
            out.append(s[: chunk.rows])
        return jnp.concatenate(out, axis=0)

    def _rebucket_queries(
        self, qs: jax.Array, n_bins: int, cache: Optional[dict]
    ) -> jax.Array:
        """Base-width query sketches folded to ``n_bins``, computed once
        per distinct width per plan (``cache``: width -> folded batch).

        The §11 identity makes this exact: ``Backend.rebucket`` of the
        base sketch equals sketching the raw query under the derived
        mapping ``pi mod n_bins`` — the same construction a distilled
        segment's rows went through — so no second pass over the query's
        raw indices is ever needed."""
        if n_bins == self.cfg.n_bins:
            return qs
        if cache is None:
            return self.backend.rebucket(qs, self.cfg.n_bins, n_bins)
        got = cache.get(n_bins)
        if got is None:
            got = cache[n_bins] = self.backend.rebucket(
                qs, self.cfg.n_bins, n_bins
            )
        return got

    def _views_topk(
        self, qs: jax.Array, views, k: int, *, use_fill_cache: bool = True,
        width_cache: Optional[dict] = None, tr=None,
    ) -> Tuple[jax.Array, jax.Array]:
        """Streaming top-k over a list of segment views + k-slot merge.

        Each view runs ``Backend.topk`` at the *view's* sketch width
        (tombstones in as ``corpus_valid``, fill cache in as
        ``corpus_fills``; distilled views score against the re-bucketed
        query batch), local indices map to global doc ids, and only the
        per-segment (Q, k) partials are merged — no (Q, C) matrix, per
        segment or global, ever exists."""
        if not views:
            return (jnp.full((qs.shape[0], k), -jnp.inf, jnp.float32),
                    jnp.full((qs.shape[0], k), -1, jnp.int32))
        if width_cache is None:
            width_cache = {}
        parts = [
            self._view_part(qs, v, k, use_fill_cache=use_fill_cache,
                            width_cache=width_cache, tr=tr)
            for v in views
        ]
        if len(parts) == 1:
            return parts[0]
        with stage(tr, "merge"):
            return merge_segment_topk([p[0] for p in parts],
                                      [p[1] for p in parts], k)

    def _view_part(
        self, qs: jax.Array, v: SegmentView, k: int, *,
        use_fill_cache: bool, width_cache: dict, tr=None,
    ) -> Tuple[jax.Array, jax.Array]:
        """One view's (Q, k) partial: ``Backend.topk`` at the view's width,
        local indices mapped to global doc ids."""
        nb = v.n_bins if v.n_bins is not None else self.cfg.n_bins
        q_w = self._rebucket_queries(qs, nb, width_cache)
        with stage(tr, "kernel_score"):
            sc, ix = self.backend.topk(
                q_w, v.sketches, nb, self.measure, k,
                corpus_fills=v.fills if use_fill_cache else None,
                corpus_valid=v.valid,
            )
        if tr is not None:
            tr.note_width(nb)
        if v.ids is not None:
            ix = jnp.where(ix >= 0, jnp.take(v.ids, jnp.maximum(ix, 0)), -1)
        return sc, ix

    # ------------------------------------------------------- banded prefilter
    def _query_band_keys(
        self, qs: jax.Array, n_bins: int, rows: int,
        width_cache: dict, qkeys_cache: dict,
    ) -> np.ndarray:
        """(rows, nb_eff) uint32 host band keys of the first ``rows`` query
        rows at width ``n_bins``, hashed once per width per planner chunk
        (``qkeys_cache``: width -> full padded key block). Only real rows
        are returned: a pad row's all-zero sketch hashes to the same key as
        a genuinely-empty band group and would drag that bucket into every
        padded chunk's candidate union."""
        got = qkeys_cache.get(n_bins)
        if got is None:
            q_w = self._rebucket_queries(qs, n_bins, width_cache)
            keys = self.backend.band_hash(
                q_w, self.store.band_policy.n_bands
            )
            got = qkeys_cache[n_bins] = np.asarray(jax.device_get(keys))
        return got[:rows]

    def _segment_candidates(
        self, seg, qkeys: np.ndarray, now, tr=None
    ) -> Optional[np.ndarray]:
        """Live candidate rows of one sealed segment for this query batch
        (ascending), or None when the escape hatch fires — the union
        outgrew ``max_candidate_frac`` of the segment and the exhaustive
        scan is the better deal. Bucket membership is stale-tolerant:
        tombstoned / TTL-expired rows sit in their buckets forever and are
        dropped here against the *current* host bitmaps, the same predicate
        the exhaustive views apply."""
        store: SegmentedStore = self.store
        try:
            cand = seg.band_index.candidates(qkeys)
        except Exception as e:
            # a broken bucket lookup must not break the query: this segment
            # serves exhaustively and the degradation lands in health()
            self.supervisor.record_degraded("band_lookup", f"{e}")
            if tr is not None:
                tr.note_degraded("band_lookup")
            return None
        if len(cand):
            cand = cand[seg.valid[cand]]
            if store.ttl is not None and now is not None:
                cand = cand[seg.born[cand] + store.ttl > now]
        if len(cand) > store.band_policy.max_candidate_frac * seg.n_rows:
            # the escape hatch IS a degraded mode — same fallback (exhaustive
            # scan), different cause (selectivity, not failure); record it so
            # a hot query pattern defeating the prefilter shows up in health
            self.supervisor.record_degraded(
                "prefilter_hatch",
                f"candidate union {len(cand)}/{seg.n_rows} rows exceeded "
                f"max_candidate_frac={store.band_policy.max_candidate_frac}",
            )
            if tr is not None:
                tr.note_degraded("prefilter_hatch")
            return None
        return cand

    def _gathered_part(
        self, qs: jax.Array, seg, cand: np.ndarray, k: int, *,
        use_fill_cache: bool, width_cache: dict, tr=None,
    ) -> Tuple[jax.Array, jax.Array]:
        """Top-k over a candidate gather of one sealed segment.

        Candidates are padded to a power-of-two bucket (bounded jit shapes,
        like the batch axis) and gathered into a compact slab — the whole
        point: the scoring kernel streams O(|candidates|) rows, not O(C).
        ``cand`` ascends and segment rows ascend in id, so the gathered
        slab keeps the positional-==-id tie-break; surviving ids score
        bit-identically to the exhaustive path (same kernel, same width,
        same fills)."""
        nb = seg.n_bins if seg.n_bins is not None else self.cfg.n_bins
        q_w = self._rebucket_queries(qs, nb, width_cache)
        n = len(cand)
        with stage(tr, "candidate_gather"):
            padded = self.planner.candidate_bucket(n, seg.n_rows)
            rows_np = np.zeros(padded, np.int32)
            rows_np[:n] = cand
            rows_dev = jnp.asarray(rows_np)
            sub = jnp.take(seg.sketches, rows_dev, axis=0)
            fills = jnp.take(seg.fills, rows_dev) if use_fill_cache else None
            vmask = jnp.asarray((np.arange(padded) < n).astype(np.int32))
        with stage(tr, "kernel_score"):
            sc, ix = self.backend.topk(
                q_w, sub, nb, self.measure, k,
                corpus_fills=fills, corpus_valid=vmask,
            )
        if tr is not None:
            tr.note_width(nb)
        gids = np.full(padded, -1, np.int64)
        gids[:n] = seg.ids[cand]
        gid_dev = jnp.asarray(gids.astype(np.int32))
        ix = jnp.where(ix >= 0, jnp.take(gid_dev, jnp.maximum(ix, 0)), -1)
        return sc, ix

    def _prefiltered_topk(
        self, qs: jax.Array, rows: int, k: int, *, now, use_fill_cache: bool,
        width_cache: dict, qkeys_cache: dict, stats: dict, tr=None,
    ) -> Tuple[jax.Array, jax.Array]:
        """Banded single-device chunk body: sealed segments scan only their
        colliding buckets; unindexed segments (below ``min_rows``, or
        sealed before the policy was armed) and the mutable head scan
        exhaustively; escape-hatch segments likewise. Results merge under
        the same global (score desc, id asc) contract as `_views_topk` —
        the prefilter changes *which rows score*, never how they score."""
        store: SegmentedStore = self.store
        parts_s, parts_i = [], []
        for seg_i, seg in enumerate(store.sealed):
            if seg.n_rows == 0:
                continue
            if seg.band_index is None:
                stats["unindexed_segments"] += 1
                sc, ix = self._view_part(
                    qs, seg.view(store.ttl, now), k,
                    use_fill_cache=use_fill_cache, width_cache=width_cache,
                    tr=tr,
                )
            else:
                nb = seg.n_bins if seg.n_bins is not None else self.cfg.n_bins
                with stage(tr, "band_lookup"):
                    qkeys = self._query_band_keys(
                        qs, nb, rows, width_cache, qkeys_cache
                    )
                    cand = self._segment_candidates(seg, qkeys, now, tr=tr)
                stats["seg_rows"] += seg.n_rows
                if cand is None:
                    stats["exhaustive_segments"] += 1
                    stats["cand_rows"] += seg.n_rows
                    if tr is not None:
                        tr.note_segment(f"seg{seg_i}", seg.n_rows, seg.n_rows)
                    sc, ix = self._view_part(
                        qs, seg.view(store.ttl, now), k,
                        use_fill_cache=use_fill_cache, width_cache=width_cache,
                        tr=tr,
                    )
                else:
                    stats["banded_segments"] += 1
                    stats["cand_rows"] += len(cand)
                    if tr is not None:
                        tr.note_segment(f"seg{seg_i}", seg.n_rows, len(cand))
                    if len(cand) == 0:
                        continue  # nothing scored: no hit for this segment
                    sc, ix = self._gathered_part(
                        qs, seg, cand, k,
                        use_fill_cache=use_fill_cache, width_cache=width_cache,
                        tr=tr,
                    )
            seg.hits += 1  # scored in this pass (see SealedSegment.hits)
            parts_s.append(sc)
            parts_i.append(ix)
        hv = store.head_view(now)
        if hv is not None:  # head rows are unbanded: always scored
            sc, ix = self._view_part(
                qs, hv, k, use_fill_cache=use_fill_cache,
                width_cache=width_cache, tr=tr,
            )
            store.head_hits += 1
            parts_s.append(sc)
            parts_i.append(ix)
        if not parts_s:
            return (jnp.full((qs.shape[0], k), -jnp.inf, jnp.float32),
                    jnp.full((qs.shape[0], k), -1, jnp.int32))
        if len(parts_s) == 1:
            return parts_s[0], parts_i[0]
        with stage(tr, "merge"):
            return merge_segment_topk(parts_s, parts_i, k)

    def _resolve_prefilter(self, prefilter: Optional[bool]) -> bool:
        on = (isinstance(self.store, SegmentedStore)
              and self.store.band_policy is not None)
        if prefilter is None:
            return on
        if prefilter and not on:
            raise ValueError(
                "prefilter=True needs a mutable store built with a "
                "band_policy (SketchEngine.build(..., mutable=True, "
                "band_policy=BandPolicy(...)))"
            )
        return bool(prefilter)

    @staticmethod
    def _fresh_prefilter_stats() -> dict:
        return {"seg_rows": 0, "cand_rows": 0, "banded_segments": 0,
                "exhaustive_segments": 0, "unindexed_segments": 0}

    def query(
        self,
        query_idx: jax.Array,
        k: int,
        *,
        use_fill_cache: bool = True,
        now: Optional[float] = None,
        prefilter: Optional[bool] = None,
    ) -> Tuple[jax.Array, jax.Array]:
        """(Q, P) padded query rows -> (scores (Q, k), ids (Q, k)).

        Streaming: each planner chunk runs ``Backend.topk`` per segment
        view, so only O(Q·k) scores ever leave a scoring kernel — the
        (Q, C) matrix is never materialized (DESIGN.md §7). Segmented
        stores merge the per-segment k-slot partials with the lower-id
        tie-break (DESIGN.md §9); ids in results are *global* doc ids,
        stable across seal/compact. If ``k`` exceeds the live corpus the
        tail slots hold score -inf / id -1. ``now`` is the query-time
        clock for lazy TTL expiry on a mutable store with a ``ttl``:
        docs with ``born + ttl <= now`` are masked out of every view,
        no ``expire()`` sweep needed.

        ``prefilter`` gates the banded LSH prefilter (DESIGN.md §12):
        ``None`` (default) auto-enables it when the store carries a
        :class:`~repro.engine.banding.BandPolicy`; ``False`` forces the
        exhaustive scan even then (the recall baseline); ``True`` insists
        (and raises without a policy). When on, indexed sealed segments
        score only the candidate union of the query batch's colliding
        buckets — results are a subset of the exhaustive top-k with
        identical scores for surviving ids — and
        :attr:`last_prefilter_stats` records the candidate fraction.
        """
        if query_idx.shape[0] == 0:
            return (jnp.zeros((0, k), jnp.float32),
                    jnp.full((0, k), -1, jnp.int32))
        now = self._auto_now(now)
        if isinstance(self.store, SegmentedStore):
            self.store.poll_compaction()  # adopt a finished background merge
        banded = self._resolve_prefilter(prefilter)
        n_q = int(query_idx.shape[0])
        with self._query_call("query", n_q, k) as tr:
            out_s, out_i = [], []
            views = None if banded else self.store.segment_views(now=now)
            stats = self._fresh_prefilter_stats() if banded else None
            width_cache: dict = {}
            qkeys_cache: dict = {}
            for chunk in self.planner.plan(n_q):
                with stage(tr, "rebucket"):
                    qs = self._padded_query_sketches(
                        query_idx[chunk.start : chunk.start + chunk.rows],
                        chunk.padded,
                    )
                if banded:
                    try:
                        sc, ix = self._prefiltered_topk(
                            qs, chunk.rows, k, now=now,
                            use_fill_cache=use_fill_cache,
                            width_cache=width_cache, qkeys_cache=qkeys_cache,
                            stats=stats, tr=tr,
                        )
                    except Exception as e:
                        # prefilter is an accelerator: any failure here (e.g.
                        # a query-side band hash blowing up) degrades this
                        # chunk to the exhaustive scan — same results, more
                        # rows
                        self.supervisor.record_degraded("prefilter", f"{e}")
                        if tr is not None:
                            tr.note_degraded("prefilter")
                        if views is None:
                            views = self.store.segment_views(now=now)
                        sc, ix = self._views_topk(
                            qs, views, k, use_fill_cache=use_fill_cache,
                            tr=tr,
                        )
                        self._count_view_hits()
                    # per-chunk caches: the padded batch shape changes across
                    # chunks, and with it the cached folded/hashed query
                    # blocks
                    width_cache, qkeys_cache = {}, {}
                else:
                    sc, ix = self._views_topk(
                        qs, views, k, use_fill_cache=use_fill_cache, tr=tr,
                    )
                    self._count_view_hits()
                out_s.append(sc[: chunk.rows])
                out_i.append(ix[: chunk.rows])
            if banded:
                self.last_prefilter_stats = stats
            if k > self.store.size:
                obs_metrics.inc("query.k_overflow")
                if tr is not None:
                    tr.k_overflow = True
            return (jnp.concatenate(out_s, axis=0),
                    jnp.concatenate(out_i, axis=0))

    @contextlib.contextmanager
    def _query_call(self, path: str, n_q: int, k: int) -> Iterator:
        """One query call: the exact ``query.calls`` / ``query.rows``
        counters, the ``repro.engine.query`` span, and the sampled trace
        (None when unsampled or disarmed) for the body to fill."""
        obs_metrics.inc("query.calls")
        obs_metrics.inc("query.rows", n_q)
        self._calls += 1
        tr = obs_trace.start(path, n_q, k)
        try:
            with span("engine.query", rows=n_q, k=k, path=path, call=self._calls):
                yield tr
        finally:
            obs_trace.finish(tr)

    # --------------------------------------------------------------- sharded
    def query_sharded(
        self,
        mesh: Mesh,
        axis: str,
        query_idx: jax.Array,
        k: int,
        *,
        now: Optional[float] = None,
        use_placement: bool = True,
        prefilter: Optional[bool] = None,
    ) -> Tuple[jax.Array, jax.Array]:
        """Candidate-sharded retrieval: local top-k then O(k·devices) merge.

        On a :class:`SegmentedStore` the **segment is the shard unit**
        (DESIGN.md §10): whole sealed segments are placed on devices
        (balanced by live rows, resident across queries), the head is
        replicated, and each device streams only its resident rows —
        per query, the only cross-device traffic is the replicated query
        sketches in and one O(k)-row partial per device out, all-gathered
        and merged with the global lower-id tie-break. Results are
        bit-identical to :meth:`query`. ``use_placement=False`` forces the
        legacy slice-every-segment-across-the-mesh path (benchmark
        baseline). An append-only :class:`SketchStore` always row-shards
        its single slab; pad rows score -inf / id -1 (no silent tail drop
        for non-divisible C).

        ``prefilter`` as in :meth:`query`: on the placed path each device
        gathers and scores only the candidate slots resident in *its* slab
        shard — the bucket lookup runs once per segment on the host, and
        candidate slots route to their owning device through the
        placement's row->slot provenance.
        """
        now = self._auto_now(now)
        n_q = int(query_idx.shape[0])
        with self._query_call("query_sharded", n_q, k) as tr:
            if k > self.store.size:
                obs_metrics.inc("query.k_overflow")
                if tr is not None:
                    tr.k_overflow = True
            if isinstance(self.store, SegmentedStore):
                self.store.poll_compaction()
                if use_placement:
                    pf = self._resolve_prefilter(prefilter)  # misuse raises pre-try
                    try:
                        # planner chunks as in query(): the banded candidate
                        # set is the union over one chunk's queries, so the
                        # chunking is part of the result
                        stats = self._fresh_prefilter_stats() if pf else None
                        out_s, out_i = [], []
                        for chunk in self.planner.plan(n_q):
                            with stage(tr, "rebucket"):
                                qs = self._padded_query_sketches(
                                    query_idx[chunk.start : chunk.start + chunk.rows],
                                    chunk.padded,
                                )
                            sc, ix = self._query_placed(
                                mesh, axis, qs, chunk.rows, k, now=now,
                                stats=stats, tr=tr,
                            )
                            out_s.append(sc[: chunk.rows])
                            out_i.append(ix[: chunk.rows])
                        if pf:
                            self.last_prefilter_stats = stats
                        return (jnp.concatenate(out_s, axis=0),
                                jnp.concatenate(out_i, axis=0))
                    except Exception as e:
                        # placement (build or mask refresh) is an accelerator:
                        # on failure, drop the cached placement and serve this
                        # query through the sliced exhaustive path below —
                        # bit-identical results, worse data movement
                        self.supervisor.record_degraded("placement", f"{e}")
                        if tr is not None:
                            tr.note_degraded("placement")
                        self._placement = None
            views = self.store.segment_views(now=now)
            with stage(tr, "rebucket"):
                qs = self._sketch_queries(query_idx)
            if not views:
                return (jnp.full((qs.shape[0], k), -jnp.inf, jnp.float32),
                        jnp.full((qs.shape[0], k), -1, jnp.int32))
            self._count_view_hits()
            cache: dict = {}
            with stage(tr, "kernel_score"):
                parts = [
                    self._sharded_view_topk(mesh, axis, qs, v, k, width_cache=cache)
                    for v in views
                ]
            if tr is not None:
                for v in views:
                    tr.note_width(v.n_bins if v.n_bins is not None
                                  else self.cfg.n_bins)
            if len(parts) == 1:
                return parts[0]
            with stage(tr, "merge"):
                return merge_segment_topk(
                    [p[0] for p in parts], [p[1] for p in parts], k
                )

    def place(self, mesh: Mesh, axis: str) -> SegmentPlacement:
        """Place the sealed segments on ``mesh`` now (``query_sharded``
        otherwise places lazily at its first call) and return the
        placement. A background :meth:`compact` issued while this placement
        is live merges device-locally — one output segment per device."""
        return self._ensure_placement(mesh, axis)

    def _ensure_placement(self, mesh: Mesh, axis: str) -> SegmentPlacement:
        """Current placement, rebuilt only when the sealed-segment *set*
        changed (seal/compact/background swap) or the mesh did; tombstone
        flips alone never re-upload slabs — just the validity mask."""
        store = self.store
        p = self._placement
        if (p is None or p.mesh != mesh or p.axis != axis
                or p.layout_epoch != store._layout_epoch):
            p = self.placer.place(store, mesh, axis)
            self._placement = p
        return p

    def _slab_candidates(
        self, slab: WidthSlab, qkeys: np.ndarray, now, stats: dict, tr=None,
    ) -> Optional[np.ndarray]:
        """Slab-slot candidates of one width slab for this query batch
        (sorted ascending, live-only), or None when any resident indexed
        segment trips the escape hatch — the whole slab then falls back to
        the exhaustive shard_map pass (per-segment fallback would still
        stream the full slab, so partial banding buys nothing here).

        Unindexed segments (below ``min_rows``) contribute *all* their
        live rows — they are small by policy, and folding them into the
        same gather keeps the pass count at one per slab. Candidates are
        host-filtered against the current tombstone/TTL predicate, so the
        prefiltered pass needs no device validity mask beyond pad slots.
        """
        store: SegmentedStore = self.store
        base = self.cfg.n_bins
        segs = [
            (i, s) for i, s in enumerate(store.sealed)
            if s.n_rows > 0
            and (s.n_bins if s.n_bins is not None else base) == slab.n_bins
        ]
        pend = []  # (seg_i, seg, cand rows) — stats commit only if no hatch
        seg_rows = cand_rows = banded = unindexed = 0
        for seg_i, seg in segs:
            if seg.band_index is None:
                cand = np.nonzero(seg.valid)[0].astype(np.int64)
                if store.ttl is not None and now is not None:
                    cand = cand[seg.born[cand] + store.ttl > now]
                unindexed += 1
            else:
                cand = self._segment_candidates(seg, qkeys, now, tr=tr)
                if cand is None:  # escape hatch: whole slab goes exhaustive
                    for s_i, s in segs:
                        if s.band_index is not None:
                            stats["seg_rows"] += s.n_rows
                            stats["cand_rows"] += s.n_rows
                            stats["exhaustive_segments"] += 1
                        else:
                            stats["unindexed_segments"] += 1
                        if tr is not None:
                            tr.note_segment(f"seg{s_i}", s.n_rows, s.n_rows)
                    return None
                seg_rows += seg.n_rows
                cand_rows += len(cand)
                banded += 1
            if tr is not None:
                tr.note_segment(f"seg{seg_i}", seg.n_rows, len(cand))
            pend.append((seg_i, seg, cand))
        stats["seg_rows"] += seg_rows
        stats["cand_rows"] += cand_rows
        stats["banded_segments"] += banded
        stats["unindexed_segments"] += unindexed
        slots = []
        for seg_i, seg, cand in pend:
            if not len(cand):
                continue
            s = slab.row_slots(seg_i, seg.n_rows)[cand]
            slots.append(s[s >= 0])
        if not slots:
            return np.zeros((0,), np.int64)
        # slots of distinct segments are disjoint; ascending order makes
        # per-device gathers id-ascending (slabs are id-sorted)
        return np.sort(np.concatenate(slots))

    def _prefiltered_slab_topk(
        self, q_w: jax.Array, slab: WidthSlab, slots: np.ndarray, k: int,
        mesh: Mesh, axis: str, n_devices: int, tr=None,
    ) -> Tuple[jax.Array, jax.Array]:
        """One width slab's all-gathered (Q, k·D) partial, scoring only
        ``slots`` — each device gathers the candidate slots resident in
        its own shard (O(|local candidates|) rows streamed, zero corpus
        bytes moved) and pads to a planner bucket so distinct candidate
        counts share jit traces. Per-device slots ascend, so the gathered
        sub-slab keeps the slab's id-ascending tie-break order."""
        measure, backend = self.measure, self.backend
        with stage(tr, "candidate_gather"):
            dev = slots // slab.n_local
            loc = slots % slab.n_local
            counts = np.bincount(dev, minlength=n_devices)
            l_c = self.planner.candidate_bucket(int(counts.max()), slab.n_local)
            idx = np.zeros((n_devices, l_c), np.int32)
            msk = np.zeros((n_devices, l_c), np.int32)
            for d in range(n_devices):
                ld = loc[dev == d]  # ascending: slots are globally sorted
                idx[d, : len(ld)] = ld
                msk[d, : len(ld)] = 1

        def local(q_rep, sl, fills, ids, idx_loc, idx_valid, nb=slab.n_bins):
            sub = jnp.take(sl, idx_loc, axis=0)
            sc, ix = backend.topk(
                q_rep, sub, nb, measure, k,
                corpus_fills=jnp.take(fills, idx_loc),
                corpus_valid=idx_valid,
            )
            gids = jnp.where(
                ix >= 0,
                jnp.take(ids, jnp.take(idx_loc, jnp.maximum(ix, 0))),
                -1,
            )
            return (jax.lax.all_gather(sc, axis, axis=1, tiled=True),
                    jax.lax.all_gather(gids, axis, axis=1, tiled=True))

        fn = jax.shard_map(
            local,
            mesh=mesh,
            in_specs=(P(), P(axis, None), P(axis), P(axis), P(axis), P(axis)),
            out_specs=(P(), P()),
            check_vma=False,
        )
        with stage(tr, "kernel_score"):
            return fn(
                q_w, slab.sketches, slab.fills, slab.ids,
                jnp.asarray(idx.reshape(-1)), jnp.asarray(msk.reshape(-1)),
            )

    def _query_placed(
        self,
        mesh: Mesh,
        axis: str,
        qs: jax.Array,
        rows: int,
        k: int,
        *,
        now: Optional[float] = None,
        stats: Optional[dict] = None,
        tr=None,
    ) -> Tuple[jax.Array, jax.Array]:
        """Segment-placed sharded query body for one planner chunk of query
        sketches ``qs``, ``rows`` of them real (see :meth:`query_sharded`);
        ``stats`` (prefilter counters) turns the prefilter on.

        One shard_map pass per resident sketch **width** (base + every
        distilled tier): each device streams the fused top-k over its
        width slab against the query batch re-bucketed to that width, and
        the per-device (Q, k) partials are all-gathered. The head partial
        (replicated — computed once, outside the mesh) and all width
        partials then merge under the global (score desc, id asc)
        tie-break.

        Why this is exact (scores *and* ids): each device/width slab is
        merge-sorted by global id at placement build, so ``Backend.topk``'s
        positional tie-break *is* the id tie-break locally — among ties
        each slab keeps exactly the lowest-id candidates, which are the
        only ones the global merge could ever need; the global top-k holds
        at most k docs of any one slab shard, so the union of per-shard
        top-k lists (plus the head partial) always contains it.

        With ``prefilter`` the same structure holds, but each slab pass
        gathers only the candidate slots of the query batch's colliding
        buckets (``_slab_candidates``) — candidate slots route to their
        owning device through the placement's row->slot provenance, so the
        bucket lookup stays host-side and per-query device work drops to
        O(|local candidates|).
        """
        store: SegmentedStore = self.store
        placement = self._ensure_placement(mesh, axis)
        prefilter = stats is not None
        hv = store.head_view(now)
        if not placement.slabs:
            # no sealed rows anywhere: the head is the whole corpus
            if hv is not None:
                store.head_hits += 1
            return self._views_topk(
                qs, [hv] if hv is not None else [], k, tr=tr
            )
        measure, backend = self.measure, self.backend
        cache: dict = {}
        qkeys_cache: dict = {}
        parts_s, parts_i = [], []
        for slab in placement.slabs:
            q_w = self._rebucket_queries(qs, slab.n_bins, cache)
            if tr is not None:
                tr.note_width(slab.n_bins)
            slots = None
            if prefilter:
                with stage(tr, "band_lookup"):
                    qkeys = self._query_band_keys(
                        qs, slab.n_bins, rows, cache, qkeys_cache
                    )
                    slots = self._slab_candidates(slab, qkeys, now, stats, tr=tr)
                if slots is not None:
                    if len(slots) == 0:
                        continue
                    self._count_slab_hits(slab.n_bins)
                    sc_all, ids_all = self._prefiltered_slab_topk(
                        q_w, slab, slots, k, mesh, axis, placement.n_devices,
                        tr=tr,
                    )
                    parts_s.append(sc_all)
                    parts_i.append(ids_all)
                    continue
            self._count_slab_hits(slab.n_bins)
            valid = slab.valid_mask(store, now=now)

            def local(q_rep, sl, fills, ids, vmask, nb=slab.n_bins):
                sc, ix = backend.topk(
                    q_rep, sl, nb, measure, k,
                    corpus_fills=fills, corpus_valid=vmask,
                )
                gids = jnp.where(ix >= 0, jnp.take(ids, jnp.maximum(ix, 0)), -1)
                return (jax.lax.all_gather(sc, axis, axis=1, tiled=True),
                        jax.lax.all_gather(gids, axis, axis=1, tiled=True))

            fn = jax.shard_map(
                local,
                mesh=mesh,
                in_specs=(P(), P(axis, None), P(axis), P(axis), P(axis)),
                out_specs=(P(), P()),
                check_vma=False,
            )
            with stage(tr, "kernel_score"):
                sc_all, ids_all = fn(q_w, slab.sketches, slab.fills, slab.ids, valid)
            parts_s.append(sc_all)
            parts_i.append(ids_all)
        if hv is not None:  # replicated head: scored once, counted once
            store.head_hits += 1
            h_sc, h_ids = self._views_topk(qs, [hv], k, width_cache=cache,
                                           tr=tr)
            parts_s.append(h_sc)
            parts_i.append(h_ids)
        if not parts_s:  # prefilter skipped every slab and the head is empty
            return (jnp.full((qs.shape[0], k), -jnp.inf, jnp.float32),
                    jnp.full((qs.shape[0], k), -1, jnp.int32))
        # always merge: slab partials are (Q, k·D) all-gathers, crop to k
        with stage(tr, "merge"):
            return merge_segment_topk(parts_s, parts_i, k)

    def _sharded_view_topk(
        self, mesh: Mesh, axis: str, qs: jax.Array, view: SegmentView, k: int,
        *, width_cache: Optional[dict] = None,
    ) -> Tuple[jax.Array, jax.Array]:
        c = int(view.sketches.shape[0])
        shards = mesh.shape[axis]
        n_local = -(-c // shards)
        c_pad = n_local * shards
        corpus, fills = view.sketches, view.fills
        in_range = jnp.arange(c_pad, dtype=jnp.int32) < c
        ids = (jnp.arange(c_pad, dtype=jnp.int32) if view.ids is None
               else jnp.pad(view.ids.astype(jnp.int32), (0, c_pad - c),
                            constant_values=-1))
        valid = (in_range if view.valid is None
                 else in_range & (jnp.pad(view.valid, (0, c_pad - c)) != 0))
        if c_pad > c:
            corpus = jnp.pad(corpus, ((0, c_pad - c), (0, 0)))
            fills = jnp.pad(fills, (0, c_pad - c))
        n_bins = view.n_bins if view.n_bins is not None else self.cfg.n_bins
        qs = self._rebucket_queries(qs, n_bins, width_cache)
        measure = self.measure
        backend = self.backend  # same scoring path as the single-device query

        def local(q_rep, cand, cand_fills, cand_ids, cand_valid):
            return shard_topk(
                q_rep, cand, n_bins, measure, k, axis,
                backend=backend, cand_fills=cand_fills,
                cand_ids=cand_ids, cand_valid=cand_valid,
            )

        fn = jax.shard_map(
            local,
            mesh=mesh,
            in_specs=(P(), P(axis, None), P(axis), P(axis), P(axis)),
            out_specs=(P(), P()),
            check_vma=False,
        )
        return fn(qs, corpus, fills, ids, valid)
