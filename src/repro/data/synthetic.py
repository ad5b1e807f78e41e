"""Synthetic sparse-binary corpora with the statistics of the paper's datasets.

The UCI/BBC corpora the paper evaluates on are not redistributable offline,
so we generate Zipf-distributed bag-of-words corpora matched on (n, d, psi):
word frequencies follow a power law (the paper's own motivation, §I) and
per-document lengths are log-normal. The paper's 5,000-doc samples draw
``length`` words with replacement (so a doc holds fewer distinct words than
its drawn length); the published-scale entries match the published
*distinct*-word statistics exactly (``DatasetSpec.distinct``). The similar-pair generator produces
pairs at a controlled similarity level for the MSE benchmarks (paper §IV-A
extracts pairs above a similarity threshold; we construct them directly so
every threshold bucket is populated).

Everything host-side is numpy (data loading is not device work);
outputs are padded int32 index matrices ready for the sketching kernels.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import numpy as np

__all__ = ["DatasetSpec", "DATASETS", "generate_corpus", "generate_similar_pairs"]


@dataclasses.dataclass(frozen=True)
class DatasetSpec:
    """Statistics mirroring the paper's §IV datasets."""

    name: str
    n_points: int
    d: int
    mean_nnz: int  # typical document length (distinct words)
    max_nnz: int  # sparsity bound psi
    zipf_a: float = 1.3  # word-frequency power-law exponent
    # lengths count distinct words (mean_nnz / max_nnz are the published
    # per-doc distinct-word statistics) rather than with-replacement draws
    distinct: bool = False


DATASETS: Dict[str, DatasetSpec] = {
    # paper: NYTimes n=300000 d=102660 (5000 sampled), Enron n=39861 d=28102,
    # KOS n=3430 d=6906, BBC n=2225 d=9635
    "nytimes": DatasetSpec("nytimes", 5000, 102660, 230, 870),
    # UCI Bag-of-Words NYTimes at its published scale: 300,000 docs over
    # 102,660 words, 230 distinct words per doc on average, psi = 870
    "nytimes-full": DatasetSpec("nytimes-full", 300000, 102660, 230, 870,
                                distinct=True),
    "enron": DatasetSpec("enron", 5000, 28102, 90, 680),
    "kos": DatasetSpec("kos", 3430, 6906, 100, 460),
    "bbc": DatasetSpec("bbc", 2225, 9635, 120, 530),
    # small spec for unit tests
    "tiny": DatasetSpec("tiny", 256, 2048, 40, 96),
}


def _zipf_weights(d: int, a: float) -> np.ndarray:
    w = 1.0 / np.arange(1, d + 1, dtype=np.float64) ** a
    return w / w.sum()


def _lognormal_lengths(rng, spec: DatasetSpec) -> np.ndarray:
    sigma = 0.5
    mu = np.log(spec.mean_nnz) - sigma**2 / 2
    return np.clip(rng.lognormal(mu, sigma, spec.n_points), 1,
                   spec.max_nnz).astype(np.int32)


def _distinct_corpus(rng, spec: DatasetSpec, chunk: int = 16384):
    """Docs of exactly ``length`` distinct words, vectorized per row chunk.

    Word ranks come from the continuous power law ``x^-zipf_a`` on
    ``[1, d + 1)`` by inverse CDF; each row's draws are sorted and every
    collision is pushed to the next free rank (``x'_j = j + cummax(x_j -
    j)``, then capped so the last of L ids is at most d - 1). A row thus
    holds L distinct ids with the frequent head over-represented, as in a
    bag of words, and rows come out sorted ascending."""
    lengths = _lognormal_lengths(rng, spec)
    pad, d, a = int(spec.max_nnz), spec.d, spec.zipf_a
    tail = 1.0 - (d + 1.0) ** (1.0 - a)
    j = np.arange(pad)
    idx = np.empty((spec.n_points, pad), np.int32)
    for lo in range(0, spec.n_points, chunk):
        ln = lengths[lo : lo + chunk, None]
        u = rng.random((ln.shape[0], pad))
        ranks = np.floor((1.0 - u * tail) ** (1.0 / (1.0 - a))).astype(np.int64) - 1
        ranks = np.sort(np.where(j < ln, np.minimum(ranks, d - 1), d), axis=1)
        ranks = np.maximum.accumulate(ranks - j, axis=1) + j
        ranks = np.minimum(ranks, d - ln + j)
        idx[lo : lo + chunk] = np.where(j < ln, ranks, -1)
    return idx, lengths


def generate_corpus(spec: DatasetSpec, seed: int = 0) -> Tuple[np.ndarray, np.ndarray]:
    """Returns (idx (n, P) padded int32 [pad=-1], lengths (n,) int32)."""
    rng = np.random.default_rng(seed)
    if spec.distinct:
        return _distinct_corpus(rng, spec)
    probs = _zipf_weights(spec.d, spec.zipf_a)
    lengths = _lognormal_lengths(rng, spec)
    pad = int(spec.max_nnz)
    idx = np.full((spec.n_points, pad), -1, np.int32)
    # vectorized sampling: draw max_nnz words per doc at once, dedupe per row
    draws = rng.choice(spec.d, size=(spec.n_points, pad), p=probs)
    for i in range(spec.n_points):
        uniq = np.unique(draws[i, : lengths[i]])
        idx[i, : len(uniq)] = uniq
        lengths[i] = len(uniq)
    return idx, lengths


def generate_similar_pairs(
    spec: DatasetSpec, jaccard: float, n_pairs: int, seed: int = 0
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Pairs (a_idx, b_idx) each (n_pairs, P) with E[JS(a,b)] ~= jaccard.

    Construction: |common| = round(J/(1+J) * 2m), each side padded with
    disjoint unique extras to m elements; exact JS = c / (2m - c).
    """
    rng = np.random.default_rng(seed)
    m = spec.mean_nnz
    c = int(round(2 * m * jaccard / (1.0 + jaccard)))
    c = min(c, m)
    extra = m - c
    pad = int(spec.max_nnz)
    a_idx = np.full((n_pairs, pad), -1, np.int32)
    b_idx = np.full((n_pairs, pad), -1, np.int32)
    probs = _zipf_weights(spec.d, spec.zipf_a)
    for i in range(n_pairs):
        words = rng.choice(spec.d, size=c + 2 * extra + 64, replace=False, p=probs)
        words = words[: c + 2 * extra]
        a = np.sort(np.concatenate([words[:c], words[c : c + extra]]))
        b = np.sort(np.concatenate([words[:c], words[c + extra :]]))
        a_idx[i, : len(a)] = a
        b_idx[i, : len(b)] = b
    true_js = c / max(2 * m - c, 1)
    return a_idx, b_idx, np.full(n_pairs, true_js, np.float64)
