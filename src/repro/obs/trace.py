"""Profiler spans and sampled per-query traces (DESIGN.md §14).

`span` opens a ``repro.<name>`` annotation on the profiler's host timeline
-- the clock the device trace uses -- at each layer boundary of the engine
and the store (``repro.engine.query``, ``repro.store.add``, ...). With no
profiler running a span is one enter and exit, about a microsecond. Its
stats are host values already at hand (rows, docs, k); never a device
value, whose read would block the dispatch stream being measured.

A `QueryTrace` is one query call's worth of structure: per-stage host
seconds (``rebucket`` -> ``band_lookup`` -> ``candidate_gather`` ->
``kernel_score`` -> ``merge``), the candidate fraction each segment
contributed, the sketch widths touched, which degraded modes fired, and
whether ``k`` overflowed the live corpus. Each stage site is one
``with stage(tr, name)``: a ``repro.query.<name>`` span, and the host
seconds added to the trace when one is armed (``tr`` not None).

Reading the two: ``stages_s`` is dispatch time. jax dispatch is async, so
a stage that merely enqueues device work reads near zero there. What a
stage costs the device comes from a profile: each device program is
charged to the spans open on the host thread that launched it (the
profile's flow stats, or on the CPU its ``run_id``, link the program to
its launch call), so the union of the device operations of the programs
launched under ``repro.query.kernel_score`` is that stage's device time.

The collector keeps the last ``capacity`` traces in a ring and, when a
`MetricsRegistry` is attached, folds every finished trace into it:
``query.stage.<stage>_s`` histograms, ``query.candidate_frac`` and
``query.degraded.<component>`` counters. (``query.calls`` /
``query.rows`` / ``query.k_overflow`` counters come from the engine itself
so they stay exact under sampling.)
"""

from __future__ import annotations

import contextlib
import threading
import time
from collections import deque
from typing import Callable, Dict, Iterator, List, Optional

from jax.profiler import TraceAnnotation

from . import metrics as _metrics
from .clock import Clock, ensure_clock

__all__ = [
    "QueryTrace",
    "TraceCollector",
    "STAGES",
    "active",
    "clear",
    "finish",
    "install",
    "scoped",
    "span",
    "stage",
    "start",
]

#: Canonical stage names, in pipeline order. A single-segment unbanded
#: query legitimately skips band_lookup/candidate_gather; a banded
#: multi-segment query exercises all five.
STAGES = ("rebucket", "band_lookup", "candidate_gather", "kernel_score",
          "merge")

#: Prefix of every span the program opens.
SPAN_PREFIX = "repro."


def span(name: str, **stats) -> TraceAnnotation:
    """A ``repro.<name>`` profiler span with host-value ``stats``."""
    return TraceAnnotation(SPAN_PREFIX + name, **stats)


@contextlib.contextmanager
def stage(tr: Optional["QueryTrace"], name: str) -> Iterator[None]:
    """Query stage ``name``: a ``repro.query.<name>`` span, plus its host
    seconds in ``tr`` when a sampled trace is armed."""
    with span("query." + name):
        if tr is None:
            yield
            return
        t0 = time.perf_counter()
        yield
        tr.add_stage(name, time.perf_counter() - t0)


class QueryTrace:
    """One sampled query call. Mutated in place by the engine, then
    handed back to `finish`."""

    __slots__ = ("path", "n_queries", "k", "started_at", "duration_s",
                 "stages", "segments", "widths", "degraded", "k_overflow",
                 "_t0")

    def __init__(self, path: str, n_queries: int, k: int,
                 started_at: float):
        self.path = path  # "query" | "query_sharded" | "query_placed"
        self.n_queries = int(n_queries)
        self.k = int(k)
        self.started_at = float(started_at)
        self.duration_s = 0.0
        self.stages: Dict[str, float] = {}
        # per-segment candidate stats: (label, rows, candidates)
        self.segments: List[dict] = []
        self.widths: List[int] = []
        self.degraded: List[str] = []
        self.k_overflow = False
        self._t0 = time.perf_counter()

    # -- engine-side recording hooks ------------------------------------
    def add_stage(self, name: str, dt: float) -> None:
        self.stages[name] = self.stages.get(name, 0.0) + float(dt)

    def note_segment(self, label: str, rows: int, candidates: int) -> None:
        self.segments.append({
            "segment": label,
            "rows": int(rows),
            "candidates": int(candidates),
            "candidate_frac": float(candidates) / float(rows) if rows else 0.0,
        })

    def note_width(self, n_bins: int) -> None:
        if int(n_bins) not in self.widths:
            self.widths.append(int(n_bins))

    def note_degraded(self, component: str) -> None:
        self.degraded.append(str(component))

    # -- derived --------------------------------------------------------
    @property
    def candidate_frac(self) -> Optional[float]:
        rows = sum(s["rows"] for s in self.segments)
        if rows == 0:
            return None
        return sum(s["candidates"] for s in self.segments) / rows

    def snapshot(self) -> dict:
        """JSON-safe record — the trace schema documented in §14."""
        return {
            "path": self.path,
            "n_queries": self.n_queries,
            "k": self.k,
            "started_at": self.started_at,
            "duration_s": self.duration_s,
            "stages_s": {k: float(v) for k, v in self.stages.items()},
            "segments": list(self.segments),
            "candidate_frac": self.candidate_frac,
            "widths": sorted(self.widths),
            "degraded": list(self.degraded),
            "k_overflow": bool(self.k_overflow),
        }


class TraceCollector:
    """Sampling + retention + registry export for query traces."""

    def __init__(self, sample: int = 1, capacity: int = 64,
                 clock: Optional[Callable[[], float]] = None,
                 registry: Optional[_metrics.MetricsRegistry] = None):
        if sample < 1:
            raise ValueError(f"sample must be >= 1, got {sample}")
        self.sample = int(sample)
        self.clock: Clock = ensure_clock(clock)
        self.registry = registry
        self._lock = threading.Lock()
        self._calls = 0
        self._ring: deque = deque(maxlen=int(capacity))

    def maybe_start(self, path: str, n_queries: int, k: int
                    ) -> Optional[QueryTrace]:
        with self._lock:
            self._calls += 1
            if (self._calls - 1) % self.sample != 0:
                return None
        return QueryTrace(path, n_queries, k, started_at=self.clock())

    def finish(self, tr: QueryTrace) -> None:
        tr.duration_s = time.perf_counter() - tr._t0
        with self._lock:
            self._ring.append(tr)
        reg = self.registry
        if reg is None:
            return
        # query.calls / query.rows are incremented unconditionally by the
        # engine (exact even when sample > 1); the collector only exports
        # what it can observe: the sampled trace itself.
        reg.observe(f"query.{tr.path}_s", tr.duration_s)
        for name, dt in tr.stages.items():
            reg.observe(f"query.stage.{name}_s", dt)
        cf = tr.candidate_frac
        if cf is not None:
            reg.observe("query.candidate_frac", cf)
        for component in tr.degraded:
            reg.inc(f"query.degraded.{component}")
        # query.k_overflow is engine-side too, same exactness argument

    def traces(self) -> List[dict]:
        with self._lock:
            return [t.snapshot() for t in self._ring]

    def last(self) -> Optional[dict]:
        with self._lock:
            return self._ring[-1].snapshot() if self._ring else None


# --------------------------------------------------------------------------
# Module-global arming, mirroring metrics/faults.

_ACTIVE: Optional[TraceCollector] = None


def install(collector: TraceCollector) -> TraceCollector:
    global _ACTIVE
    _ACTIVE = collector
    return collector


def clear() -> None:
    global _ACTIVE
    _ACTIVE = None


def active() -> Optional[TraceCollector]:
    return _ACTIVE


@contextlib.contextmanager
def scoped(collector: TraceCollector) -> Iterator[TraceCollector]:
    prev = active()
    install(collector)
    try:
        yield collector
    finally:
        install(prev) if prev is not None else clear()


def start(path: str, n_queries: int, k: int) -> Optional[QueryTrace]:
    col = _ACTIVE
    if col is None:
        return None
    return col.maybe_start(path, n_queries, k)


def finish(tr: Optional[QueryTrace]) -> None:
    if tr is None:
        return
    col = _ACTIVE
    if col is not None:
        col.finish(tr)
