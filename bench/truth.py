"""Exact Jaccard top-k: the truth that ``recall_at_10`` is measured against.

``exact_topk`` is a copy of the program's host ground truth
(``repro.obs.probe.exact_topk``), kept here so that the yardstick cannot
change under a later change to the program. At the benchmark's sizes it
is too slow on the host (some seconds per 32 queries over 300,000 docs),
so runs use ``device_exact_topk``: the same intersection counts from the
reference's one-hot scan on the device, and the same float32 arithmetic
and the same tie-break (score desc, position asc) on the host. A test
holds the two equal.
"""

from __future__ import annotations

import numpy as np

from .reference import jaccard32, rank, scan_topk


def exact_topk(corpus_idx, query_idx, k):
    """Host exact Jaccard top-k over distinct padded rows; (Q, k) positions
    into ``corpus_idx`` (score desc, position asc on ties)."""
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


def device_exact_topk(q_idx, limits, chunks, k, *, vocab: int, vers=None):
    """``exact_topk`` by the reference's device scan over ``chunks``
    (``reference.device_chunks``): (Q, k) ids among ``[0, limits[i])`` for
    query ``i``, over the corpus as it stood in version ``vers[i]``."""
    s32, ids, inter, csize, qsize = scan_topk(
        np.asarray(q_idx), np.asarray(limits), chunks, kind="jaccard", universe=vocab, k=k,
        vers=vers)
    exact = np.where(ids >= 0, jaccard32(qsize[:, None], csize, inter), -np.inf)
    return rank(ids, exact, k)[0]


def recall(got_ids: np.ndarray, truth_ids: np.ndarray) -> float:
    """Mean over queries of |got ∩ truth| / k."""
    k = truth_ids.shape[1]
    hits = [len(np.intersect1d(g[g >= 0], t[t >= 0])) for g, t in zip(got_ids, truth_ids)]
    return float(np.mean(hits) / k) if hits else float("nan")
