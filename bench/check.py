"""The comparisons that decide ``correct``, each number beside its limit.

Every answer is judged against the corpus as it stood when its query was
sent: every write acknowledged before it and none after (``History``).

Query answers (every op that returns a top-k):

* ``rank_gap``: over every checked answer slot, how far the reference's
  float64 BinSketch estimate of the returned doc lies below the
  reference's own k-th best for that query. A slot that holds no doc, a
  doc repeated in its row, or a doc not yet acknowledged when the query
  was sent reads 1. Catches a scan that skips documents, a wrong id map,
  a wrong sketch, an altered answer and an answer from stale contents.
* ``score_gap``: over the same slots, the distance between the returned
  score and the reference's float64 estimate for that doc. Catches a
  wrong estimator or a wrong fill count that keeps the ranking.

Inserts and updates (every op that stores documents):

* ``lost_docs``: acknowledged docs that the store does not hold live, plus
  live docs that were never acknowledged, plus docs live more than once
  (an updated doc left live in its old row as well).
* ``wrong_sketches``: sampled acknowledged docs whose stored packed row
  differs in any bit from the reference's sketch of the doc's newest
  contents.

``failed_requests``: requests that raised instead of answering.

Each limit is set in ``LIMITS`` from the readings that ``PERF.md`` lists:
the largest over a dozen sound seeds below, the smallest of the control
above.
"""

from __future__ import annotations

import numpy as np

from . import reference as ref

#: number -> limit; a run is correct when every number is at most its limit
LIMITS = {
    "failed_requests": 0,
    "rank_gap": 0.0015,
    "score_gap": 0.003,
    "lost_docs": 0,
    "wrong_sketches": 0,
}


class History:
    """Every acknowledged write of a run, so that the check can say what any
    doc held in any version of the corpus: version ``v`` holds the standing
    corpus, every acknowledged insert, and the first ``v`` acknowledged
    update requests."""

    def __init__(self, standing: np.ndarray, pool):
        self.standing, self.pool = standing, pool
        self.n_docs = self.hi = len(standing)  # ids below ``hi`` were acknowledged
        self.inserts = []  # (lo, hi, first pool row) of each insert
        self.updates = []  # (ids, pool rows) of each update
        self._src = (0, None)  # (inserts seen, pool row of each id from n_docs or -1)

    @property
    def version(self) -> int:
        """The version that every write acknowledged so far makes."""
        return len(self.updates)

    def insert(self, lo: int, hi: int, pool_lo: int) -> None:
        self.inserts.append((lo, hi, pool_lo))
        self.hi = max(self.hi, hi)

    def update(self, ids: np.ndarray, pool_lo: int) -> None:
        rows = (pool_lo + np.arange(len(ids))) % len(self.pool)
        self.updates.append((np.asarray(ids, np.int64), rows))

    def inserted_ids(self) -> np.ndarray:
        return np.concatenate([np.zeros(0, np.int64)] +
                              [np.arange(lo, hi) for lo, hi, _ in self.inserts])

    def updated_ids(self) -> np.ndarray:
        return np.unique(np.concatenate([np.zeros(0, np.int64)] +
                                        [ids for ids, _ in self.updates]))

    def first(self, ids) -> np.ndarray:
        """The word rows each of ``ids`` held before any update: standing or
        inserted contents, empty for an id never acknowledged."""
        ids = np.asarray(ids, np.int64)
        out = np.full((len(ids), self.standing.shape[1]), -1, np.int32)
        st = ids < self.n_docs
        out[st] = self.standing[ids[st]]
        ins = np.nonzero(~st & (ids < self.hi))[0]
        if len(ins):
            if self._src[0] != len(self.inserts):  # built once the writes are done
                src = np.full(self.hi - self.n_docs, -1, np.int64)
                for lo, hi, plo in self.inserts:
                    src[lo - self.n_docs : hi - self.n_docs] = \
                        (plo + np.arange(hi - lo)) % len(self.pool)
                self._src = (len(self.inserts), src)
            rows = self._src[1][ids[ins] - self.n_docs]
            out[ins[rows >= 0]] = self.pool[rows[rows >= 0]]
        return out

    def _events(self):
        """(ids, version made, pool row) of every doc an update wrote,
        ascending by id and then by version."""
        if not self.updates:
            return np.zeros((3, 0), np.int64)
        ev = np.concatenate([np.stack([ids, np.full(len(ids), v + 1), rows])
                             for v, (ids, rows) in enumerate(self.updates)], axis=1)
        return ev[:, np.lexsort((ev[1], ev[0]))]

    def at(self, ids, vers) -> np.ndarray:
        """The word rows each of ``ids`` held in version ``vers`` (one each)."""
        ids = np.asarray(ids, np.int64)
        out = self.first(ids)
        e_id, e_ver, e_row = self._events()
        if len(e_id):
            span = self.version + 1
            j = np.searchsorted(e_id * span + e_ver, ids * span + np.asarray(vers),
                                side="right") - 1
            hit = (j >= 0) & (e_id[np.maximum(j, 0)] == ids)
            out[hit] = self.pool[e_row[j[hit]]]
        return out

    def newest(self, ids) -> np.ndarray:
        return self.at(ids, np.full(len(ids), self.version))

    def chunks(self) -> list:
        """Every version of every acknowledged doc on the device
        (``reference.device_chunks``), ascending by id."""
        e_id, e_ver, e_row = self._events()
        ids = np.setdiff1d(np.arange(self.hi), e_id)  # docs no update wrote
        born = np.zeros(len(ids), np.int64)
        dead = np.full(len(ids), ref.LAST)
        src = np.full(len(ids), -1)
        if len(e_id):
            u, start = np.unique(e_id, return_index=True)
            same = np.r_[e_id[1:] == e_id[:-1], False]  # the next write is to this doc
            ids = np.concatenate([ids, u, e_id])  # untouched, first contents, each update
            born = np.concatenate([born, np.zeros(len(u), np.int64), e_ver])
            dead = np.concatenate([dead, e_ver[start], np.where(same, np.r_[e_ver[1:], 0],
                                                                ref.LAST)])
            src = np.concatenate([src, np.full(len(u), -1), e_row])
            order = np.lexsort((born, ids))
            ids, born, dead, src = ids[order], born[order], dead[order], src[order]

        def rows(lo, hi):
            out, s = self.first(ids[lo:hi]), src[lo:hi]
            if (s >= 0).any():
                out[s >= 0] = self.pool[s[s >= 0]]
            return out

        return ref.device_chunks(rows, len(ids), ids=ids, born=born, dead=dead)


def query_numbers(q_idx, limits, vers, got_s, got_i, content, chunks, *, pi, n_bins, k):
    """``rank_gap`` and ``score_gap`` of the answers ``(got_s, got_i)``
    (Q, k) to queries ``q_idx`` (Q, P). ``content(ids, vers)`` gives the
    word rows of any ids in any version of the corpus (``History.at``),
    ``chunks`` every version of every stored doc on the device
    (``History.chunks``); ``limits`` (Q,) the ids acknowledged when each
    query was sent, and ``vers`` (Q,) the version of the corpus then."""
    if len(q_idx) == 0:
        return {"rank_gap": 0.0, "score_gap": 0.0}
    _, ids, inter, csize, qsize = ref.scan_topk(
        q_idx, limits, chunks, kind="binsketch", universe=n_bins, pi=pi, n_bins=n_bins, k=k,
        vers=vers)
    est = np.where(ids >= 0, ref.binsketch_jaccard64(qsize[:, None], csize, inter, n_bins),
                   -np.inf)
    kth = ref.rank(ids, est, k)[1][:, k - 1]

    got_i = np.asarray(got_i, np.int64)
    ok = (got_i >= 0) & (got_i < np.asarray(limits)[:, None])
    earlier = np.tril(np.ones((k, k), bool), -1)  # [j, j'] for j' < j
    ok &= ~((got_i[:, :, None] == got_i[:, None, :]) & earlier).any(axis=2)
    rows = content(np.where(ok, got_i, 0).ravel(),
                   np.repeat(np.asarray(vers), k)).reshape(len(q_idx), k, -1)
    q_bins = ref.bin_rows(q_idx, pi)
    r_bins = ref.bin_rows(rows.reshape(-1, rows.shape[-1]), pi).reshape(rows.shape)
    nab = ref.pair_counts(q_bins, r_bins, n_bins)
    nb = (r_bins >= 0).sum(axis=2)
    na = ref.set_sizes(q_bins)[:, None]
    est_got = ref.binsketch_jaccard64(na, nb, nab, n_bins)
    gap = np.where(ok, np.maximum(kth[:, None] - est_got, 0.0), 1.0)
    # no doc is owed where the reference has fewer than k admitted
    gap = np.where(~ok & np.isneginf(kth)[:, None] & (got_i < 0), 0.0, gap)
    sgap = np.where(ok, np.abs(np.asarray(got_s, np.float64) - est_got), 0.0)
    return {"rank_gap": float(gap.max()), "score_gap": float(sgap.max())}


def store_numbers(views, expected_ids, sample_ids, content, *, pi, n_bins):
    """``lost_docs`` and ``wrong_sketches`` of a store given as its query
    views ``(sketches, ids or None, valid or None)``; ``expected_ids`` are
    every acknowledged id, ``sample_ids`` those whose rows are compared."""
    all_ids, where = [], []
    for v, (sk, ids, valid) in enumerate(views):
        n = sk.shape[0]
        ids = np.arange(n) if ids is None else np.asarray(ids, np.int64)
        live = np.ones(n, bool) if valid is None else np.asarray(valid) != 0
        rows = np.nonzero(live)[0]
        all_ids.append(ids[rows])
        where.append(np.stack([np.full(len(rows), v), rows], 1))
    all_ids = np.concatenate(all_ids) if all_ids else np.zeros(0, np.int64)
    where = np.concatenate(where) if where else np.zeros((0, 2), np.int64)
    expected = np.unique(np.asarray(expected_ids, np.int64))
    live_u, counts = np.unique(all_ids, return_counts=True)
    lost = (len(np.setdiff1d(expected, live_u)) + len(np.setdiff1d(live_u, expected))
            + int((counts - 1).sum()))

    sample = np.asarray(sample_ids, np.int64)
    order = np.argsort(all_ids, kind="stable")
    pos = np.searchsorted(all_ids[order], sample)
    found = (pos < len(order)) & (all_ids[order][np.minimum(pos, len(order) - 1)] == sample)
    want = ref.pack_rows(ref.bin_rows(content(sample), pi), n_bins)
    wrong = int((~found).sum())
    hit = np.nonzero(found)[0]
    loc = where[order[pos[hit]]]
    for v in np.unique(loc[:, 0]):
        sel = hit[loc[:, 0] == v]
        got = np.asarray(_take(views[v][0], loc[loc[:, 0] == v, 1]))
        if got.shape[1] != want.shape[1]:
            wrong += len(sel)
        else:
            wrong += int((got != want[sel]).any(axis=1).sum())
    return {"lost_docs": int(lost), "wrong_sketches": wrong}


def _take(sketches, rows):
    import jax.numpy as jnp

    return jnp.take(sketches, jnp.asarray(rows.astype(np.int32)), axis=0)


def verdict(numbers: dict) -> bool:
    return all(numbers[n] <= LIMITS[n] for n in numbers)


def report(numbers: dict) -> dict:
    """``{name: {"value": v, "limit": l}}`` in a fixed order."""
    return {n: {"value": numbers[n], "limit": LIMITS[n]} for n in LIMITS if n in numbers}
