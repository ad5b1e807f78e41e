"""The comparisons that decide ``correct``, each number beside its limit.

Query answers (every op that returns a top-k):

* ``rank_gap``: over every checked answer slot, how far the reference's
  float64 BinSketch estimate of the returned doc lies below the
  reference's own k-th best for that query. A slot that holds no doc, a
  doc repeated in its row, or a doc not yet acknowledged when the query
  was sent reads 1. Catches a scan that skips documents, a wrong id map,
  a wrong sketch and an altered answer.
* ``score_gap``: over the same slots, the distance between the returned
  score and the reference's float64 estimate for that doc. Catches a
  wrong estimator or a wrong fill count that keeps the ranking.

Inserts (every op that stores documents):

* ``lost_docs``: acknowledged docs that the store does not hold live, plus
  live docs that were never acknowledged.
* ``wrong_sketches``: sampled acknowledged docs whose stored packed row
  differs in any bit from the reference's sketch of the doc sent.

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


def query_numbers(q_idx, limits, got_s, got_i, content, chunks, *, pi, n_bins, k):
    """``rank_gap`` and ``score_gap`` of the answers ``(got_s, got_i)``
    (Q, k) to queries ``q_idx`` (Q, P). ``content(ids)`` gives the word rows
    of any ids, ``chunks`` every stored doc on the device
    (``reference.device_chunks``); ``limits`` (Q,) the ids acknowledged when
    each query was sent."""
    if len(q_idx) == 0:
        return {"rank_gap": 0.0, "score_gap": 0.0}
    _, ids, inter, csize, qsize = ref.scan_topk(
        q_idx, limits, chunks, kind="binsketch", universe=n_bins, pi=pi, n_bins=n_bins, k=k)
    est = np.where(ids >= 0, ref.binsketch_jaccard64(qsize[:, None], csize, inter, n_bins),
                   -np.inf)
    kth = ref.rank(ids, est, k)[1][:, k - 1]

    got_i = np.asarray(got_i, np.int64)
    ok = (got_i >= 0) & (got_i < np.asarray(limits)[:, None])
    earlier = np.tril(np.ones((k, k), bool), -1)  # [j, j'] for j' < j
    ok &= ~((got_i[:, :, None] == got_i[:, None, :]) & earlier).any(axis=2)
    rows = content(np.where(ok, got_i, 0).ravel()).reshape(len(q_idx), k, -1)
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
