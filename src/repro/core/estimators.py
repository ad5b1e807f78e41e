"""Algorithms 1-4: similarity estimators operating on BinSketch sketches.

Key simplification used throughout (exact algebra, not an approximation):
with ``n = 1 - 1/N`` and ``n_a = ln(1 - |a_s|/N) / ln(n)`` (Alg 1 line 3),
``n^{n_a} = 1 - |a_s|/N`` identically. Substituting into Alg 1 line 4:

    n^{n_a} + n^{n_b} + <a_s,b_s>/N - 1 = 1 - (|a_s| + |b_s| - <a_s,b_s|)/N
                                        = 1 - |a_s OR b_s| / N

so the inner-product estimator collapses to inclusion-exclusion over
*estimated cardinalities*:

    IP_est = card(|a_s|) + card(|b_s|) - card(|a_s OR b_s|)

where ``card(c) = ln(1 - c/N)/ln(1 - 1/N)`` estimates the pre-image set size
from the sketch fill count. This is what we implement: it is numerically
nicer (single transform), mathematically identical to Alg 1, and it maps
onto the packed popcount kernels (|OR| = |a|+|b|-|AND| needs only the AND
popcount the kernel already produces).

Hamming convention (see DESIGN.md §1): symmetric difference
``|a XOR b| = |a| + |b| - 2 IP`` by default; the paper's literal Alg 2
(``n_a + n_b - n_ab``) behind ``convention="paper"``.
"""

from __future__ import annotations

import math
from typing import Dict

import jax.numpy as jnp

from . import packed as pk

__all__ = [
    "cardinality_from_fill",
    "estimates_from_counts",
    "pairwise_counts",
    "pairwise_similarity",
]


def cardinality_from_fill(count: jnp.ndarray, n_bins: int) -> jnp.ndarray:
    """Estimate |a| from the sketch fill count |a_s| (Alg 1 line 3).

    ``card = ln(1 - c/N) / ln(1 - 1/N)``. The log is ``log1p(-c/N)`` below
    half fill, where sketches are sized to live, and ``ln(N - c) - ln N``
    above it: each form keeps fp32 precision on its side (the difference of
    logs alone loses ~1e-4 of the result at c << N). A full sketch (c == N)
    is clipped to c = N - 0.5 (estimate saturates, mirroring the paper's
    requirement that N be sized to keep fill < 1/2).

    Plain element-wise jnp, so the Pallas kernels call this same function
    in-kernel: kernel and oracle then round alike on any device. (On a TPU
    v5e the f32 ``log``/``log1p`` are coarser than on the CPU, ~1e-4
    absolute, in XLA and in Mosaic alike.)
    """
    n = float(n_bins)
    c = jnp.clip(count.astype(jnp.float32), 0.0, n - 0.5)
    low = jnp.log1p(-c / jnp.float32(n))
    high = jnp.log(n - c) - jnp.float32(math.log(n))
    # ln(1 - 1/N), -inf at N = 1 (every estimate 0). A division, not a
    # multiply by the reciprocal: XLA may fuse a multiply into the caller's
    # sums as an FMA, and then results vary with the shape it compiled for
    ln_n = math.log1p(-1.0 / n) if n > 1 else -math.inf
    return jnp.where(c < n / 2, low, high) / jnp.float32(ln_n)


def estimates_from_counts(
    na_s: jnp.ndarray,
    nb_s: jnp.ndarray,
    nab_s: jnp.ndarray,
    n_bins: int,
    convention: str = "symmetric",
) -> Dict[str, jnp.ndarray]:
    """All four estimators from sketch statistics.

    Args:
      na_s: |a_s| fill counts, any broadcastable shape.
      nb_s: |b_s| fill counts.
      nab_s: <a_s, b_s> AND-popcounts.
      n_bins: sketch length N.
      convention: "symmetric" (|a XOR b|) or "paper" (Alg 2 literal).

    Returns dict with "ip", "hamming", "jaccard", "cosine".
    """
    n_a = cardinality_from_fill(na_s, n_bins)
    n_b = cardinality_from_fill(nb_s, n_bins)
    union_s = na_s + nb_s - nab_s  # |a_s OR b_s|
    n_union = cardinality_from_fill(union_s, n_bins)

    ip = n_a + n_b - n_union  # Alg 1 (see module docstring)
    ip = jnp.maximum(ip, 0.0)
    union = jnp.maximum(n_union, 1e-9)
    if convention == "symmetric":
        hamming = jnp.maximum(n_a + n_b - 2.0 * ip, 0.0)
    elif convention == "paper":
        hamming = jnp.maximum(n_a + n_b - ip, 0.0)
    else:
        raise ValueError(f"unknown convention {convention!r}")
    jaccard = jnp.clip(ip / union, 0.0, 1.0)
    cosine = jnp.clip(ip / jnp.sqrt(jnp.maximum(n_a * n_b, 1e-18)), 0.0, 1.0)
    return {"ip": ip, "hamming": hamming, "jaccard": jaccard, "cosine": cosine}


def pairwise_counts(
    a_packed: jnp.ndarray,
    b_packed: jnp.ndarray,
    a_fills: jnp.ndarray = None,
    b_fills: jnp.ndarray = None,
):
    """(|a_s| (Q,), |b_s| (C,), <a_s,b_s> (Q,C)) via the pure-jnp oracle path.

    ``a_fills``/``b_fills`` are optional precomputed fill counts (e.g. the
    ``SketchStore`` ingest-time cache); ``None`` popcounts that side here.
    """
    na = a_fills if a_fills is not None else pk.row_popcount(a_packed)
    nb = b_fills if b_fills is not None else pk.row_popcount(b_packed)
    nab = pk.and_popcount_pairwise(a_packed, b_packed)
    return na, nb, nab


def pairwise_similarity(
    a_packed: jnp.ndarray,
    b_packed: jnp.ndarray,
    n_bins: int,
    measure: str = "jaccard",
    convention: str = "symmetric",
    *,
    a_fills: jnp.ndarray = None,
    b_fills: jnp.ndarray = None,
) -> jnp.ndarray:
    """(Q, C) estimated similarity matrix from packed sketches (oracle path).

    The production path for large C is ``repro.kernels.ops.sketch_score``,
    which fuses AND-popcount and this estimator epilogue in VMEM. Precomputed
    fill counts (the store's ingest-time cache) skip the per-call popcount.
    """
    na, nb, nab = pairwise_counts(a_packed, b_packed, a_fills, b_fills)
    est = estimates_from_counts(na[:, None], nb[None, :], nab, n_bins, convention)
    if measure not in est:
        raise ValueError(f"unknown measure {measure!r}; have {sorted(est)}")
    return est[measure]
