"""Online recall probe — the lifecycle controller's accuracy guardrail.

The ROADMAP's controller wants to distill/compact aggressively *until
recall dips*; that requires an online measurement, not an end-of-run
report. `RecallProbe` samples queries from the live catalog, computes
exact Jaccard top-k ground truth on a background `JobSupervisor` job
(the expensive half — O(Q·C·d/64) membership matmuls — runs off the
serving thread over a host snapshot, the same snapshot/work pattern
compaction uses), then scores the engine's own answers against it on
the caller thread at poll time (engine/device access stays
single-threaded, per the store's threading contract). The reading
lands in the metrics registry as the ``probe.recall`` gauge.

`exact_topk` is the one shared ground-truth helper — `serve.py`'s
final report and this probe both call it (it previously lived in
serve.py as ``exact_topk_jaccard``; serve re-exports that name).
"""

from __future__ import annotations

from typing import Callable, Optional

import numpy as np

from . import metrics as _metrics
from .clock import Clock, ensure_clock

__all__ = ["RecallProbe", "exact_topk"]


def exact_topk(corpus_idx, query_idx, k):
    """Host-side exact Jaccard top-k (ground truth; small query sets).

    Rows are sets: distinct indices padded with -1, as ``data.synthetic``
    emits them. Only words that some query holds can intersect, so the
    membership columns are the queries' vocabulary V, not all d words:
    |q ∩ c| is a (Q, V) x (V, C) matmul over {0,1} membership rows, |c| is
    the row's index count, and |q ∪ c| follows by inclusion-exclusion — no
    per-pair Python set loop. The corpus membership matrix is built per row
    chunk so peak memory stays ~64 MB however large C grows; only the
    (Q, C) sims matrix is held whole.

    Returns (Q, k) *positions* into ``corpus_idx`` (score desc, position
    asc on ties) — callers map positions to global ids themselves.
    """
    corpus_idx = np.asarray(corpus_idx)
    query_idx = np.asarray(query_idx)
    d = int(max(corpus_idx.max(initial=0), query_idx.max(initial=0))) + 1
    vocab = np.unique(query_idx[query_idx >= 0])
    col = np.full(d, -1, np.int64)
    col[vocab] = np.arange(len(vocab))

    def member(idx):
        c = np.where(idx >= 0, col[np.maximum(idx, 0)], -1)
        m = np.zeros((idx.shape[0], max(len(vocab), 1)), np.float32)
        rows, slots = np.nonzero(c >= 0)
        m[rows, c[rows, slots]] = 1.0
        return m

    qm = member(query_idx)
    q_sizes = qm.sum(axis=1)[:, None]
    c_chunk = max(1, (1 << 24) // qm.shape[1])  # ~64 MB of membership
    sims = np.empty((len(query_idx), len(corpus_idx)), np.float32)
    for lo in range(0, len(corpus_idx), c_chunk):
        chunk = corpus_idx[lo : lo + c_chunk]
        inter = qm @ member(chunk).T  # float32 matmul is exact for counts << 2^24
        union = q_sizes + (chunk >= 0).sum(axis=1)[None, :] - inter
        sims[:, lo : lo + len(chunk)] = inter / np.maximum(union, 1.0)
    return np.argsort(-sims, axis=1, kind="stable")[:, :k]


class RecallProbe:
    """Sampled recall@k vs exact ground truth, supervised + off-thread.

    Lifecycle::

        probe = RecallProbe(engine, k=10, sample=64, seed=0)
        probe.launch(surv_ids, surv_rows, queries)   # snapshot + submit
        ...                                          # serve traffic
        probe.poll(now=serve_now)                    # cheap; heartbeat
        recall = probe.wait(now=serve_now)           # block for reading

    ``launch`` snapshots the catalog arrays (the probe's truth is the
    catalog *as of launch*; later mutations measure as recall loss,
    which is exactly the drift signal the controller wants) and submits
    the ground-truth matmul as op ``"probe"`` on the engine's
    `JobSupervisor` — retries/backoff/quarantine come for free, and a
    failing probe degrades (gauge goes stale) instead of raising into
    serving. ``poll`` runs the engine query on the caller's thread once
    truth is ready, then publishes ``probe.recall`` / ``probe.at`` and
    bumps ``probe.runs``.
    """

    def __init__(self, engine, k: int = 10, sample: int = 64,
                 seed: int = 0,
                 clock: Optional[Callable[[], float]] = None):
        self.engine = engine
        self.k = int(k)
        self.sample = int(sample)
        self.seed = int(seed)
        self.clock: Clock = ensure_clock(
            clock if clock is not None else getattr(engine, "clock", None))
        self.last_recall: Optional[float] = None
        self.last_at: Optional[float] = None
        self.runs = 0
        self._job = None  # the in-flight SupervisedJob handle
        self._queries = None
        self._truth_ids = None  # set when the background job lands

    @property
    def running(self) -> bool:
        return self._queries is not None

    def launch(self, surv_ids, surv_rows, queries=None) -> bool:
        """Snapshot the catalog + sample queries, submit the truth job.

        ``surv_ids``/``surv_rows`` are the live catalog (global ids and
        raw index rows, aligned); ``queries`` defaults to a seeded
        sample of catalog rows — pass the serve query set to probe the
        exact traffic distribution instead. No-op (False) while a
        previous probe is still in flight, the catalog is empty, or the
        supervisor has the probe op quarantined.
        """
        if self._queries is not None or len(surv_ids) == 0:
            return False
        surv_ids = np.asarray(surv_ids).copy()
        surv_rows = np.asarray(surv_rows).copy()
        if queries is None:
            rng = np.random.default_rng(self.seed + self.runs)
            pick = rng.choice(len(surv_ids), min(self.sample, len(surv_ids)),
                              replace=False)
            queries = surv_rows[pick]
        else:
            queries = np.asarray(queries)
            if len(queries) > self.sample:
                rng = np.random.default_rng(self.seed + self.runs)
                queries = queries[rng.choice(len(queries), self.sample,
                                             replace=False)]
        k = min(self.k, len(surv_ids))

        def work():
            pos = exact_topk(surv_rows, queries, k)
            return surv_ids[pos]  # positions -> global doc ids

        job = self.engine.supervisor.submit("probe", ("recall", self.runs),
                                            work)
        if job is None:  # quarantined: skip this round, gauge stays stale
            return False
        self._job, self._queries = job, queries
        return True

    def poll(self, now: Optional[float] = None) -> Optional[float]:
        """Heartbeat: drive the supervisor; when truth has landed, score
        the engine against it and publish. Returns the fresh recall on
        the tick it completes, else None."""
        if self._queries is None:
            return None
        sup = self.engine.supervisor
        if self._truth_ids is None:
            st = sup.poll(self._job)
            if st == "running":
                return None
            if st == "failed":
                # supervisor already recorded the failure/quarantine;
                # drop this run — the gauge keeps its last value
                self._job = self._queries = None
                return None
            self._truth_ids = np.asarray(self._job.result)
            self._job = None
        truth_ids = self._truth_ids
        queries, k = self._queries, truth_ids.shape[1]
        self._queries = self._truth_ids = None
        _, ids = self.engine.query(queries, k, now=now)
        ids = np.asarray(ids)
        hits = sum(
            len(set(ids[i].tolist()) & set(truth_ids[i].tolist()))
            for i in range(len(queries))
        )
        recall = hits / float(len(queries) * k)
        self.runs += 1
        self.last_recall = recall
        self.last_at = float(now) if now is not None else self.clock()
        _metrics.set_gauge("probe.recall", recall)
        _metrics.set_gauge("probe.at", self.last_at)
        _metrics.inc("probe.runs")
        return recall

    def wait(self, now: Optional[float] = None,
             timeout: float = 60.0) -> Optional[float]:
        """Block (politely — supervisor-driven) until the in-flight probe
        completes or ``timeout`` real seconds pass. Returns the reading,
        or the last one if nothing was in flight.

        The deadline is *real* time on purpose — it bounds how long the
        caller physically blocks on the worker thread, so it reads
        ``MONOTONIC`` (the system clock singleton) rather than the
        injected probe clock: under ``ManualClock`` an injected deadline
        would never advance and this would hang forever."""
        import time as _time

        from .clock import MONOTONIC

        deadline = MONOTONIC() + timeout
        while self._queries is not None and MONOTONIC() < deadline:
            got = self.poll(now=now)
            if got is not None:
                return got
            _time.sleep(0.005)
        return self.last_recall

    def snapshot(self) -> dict:
        return {
            "recall": self.last_recall,
            "at": self.last_at,
            "runs": int(self.runs),
            "k": self.k,
            "sample": self.sample,
            "running": self.running,
        }
