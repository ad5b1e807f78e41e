"""The benchmark's corpus generator and the sketch mapping, both from a seed.

A copy of ``repro.data.synthetic``'s published-scale generator (the
``distinct=True`` path), so that the benchmark's data cannot change under a
later change to the program: word ranks follow the continuous power law
``x^-zipf_a`` on ``[1, d + 1)`` by inverse CDF, lengths are log-normal with
``sigma = 0.5`` around the configured mean and clipped to ``[1, psi]``, and
each row holds exactly ``length`` distinct word ids, ascending, padded with
-1 to ``psi`` columns.

``device_corpus`` is a ``jnp`` copy of the same generator that runs on the
device (the host copy takes about a minute at 300,000 docs on the chip's
host); its rows are downloaded once, so the reference sees exactly what the
system under test was given. It follows the host generator step for step
in float32, so it draws a different sample of the same distribution; a
test holds their statistics together. Every seed draws its own documents,
as it draws its own sketch map and traffic.

Nothing here imports the program.
"""

from __future__ import annotations

import functools
import math

import numpy as np

#: sub-streams of one run's seed, so that the corpus, the mapping, the
#: traffic and the check's sample never share random numbers; a new draw
#: takes a new stream at the end, so that no earlier draw moves
STREAM_CORPUS, STREAM_MAPPING, STREAM_POOL, STREAM_TRAFFIC, STREAM_CHECK = range(5)
#: a mix's choice of op per request, its Zipf keys, and the check's draws
#: among updated ids
STREAM_OPS, STREAM_KEYS, STREAM_UPDATE_CHECK = range(5, 8)


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """A generator for one named sub-stream of ``seed`` (any int >= 0)."""
    return np.random.default_rng([int(stream), int(seed)])


def theorem1_n_bins(psi: int, rho: float) -> int:
    """Sketch length from the paper's Theorem 1: ``psi * sqrt(psi/2 ln(2/rho))``."""
    return int(math.ceil(psi * math.sqrt(psi / 2.0 * math.log(2.0 / rho))))


def n_words(n_bins: int) -> int:
    """uint32 words per packed sketch row."""
    return (int(n_bins) + 31) // 32


def lognormal_lengths(rng, n: int, mean: float, psi: int, sigma: float) -> np.ndarray:
    mu = np.log(mean) - sigma**2 / 2
    return np.clip(rng.lognormal(mu, sigma, n), 1, psi).astype(np.int32)


def distinct_rows(rng, n: int, d: int, mean: float, psi: int, zipf_a: float,
                  sigma: float = 0.5, chunk: int = 16384):
    """(idx (n, psi) int32 padded with -1, lengths (n,) int32).

    Rows of exactly ``length`` distinct ids: each row's power-law draws are
    sorted and every collision is pushed to the next free rank
    (``x'_j = j + cummax(x_j - j)``), then capped so the last of L ids is at
    most d - 1."""
    lengths = lognormal_lengths(rng, n, mean, psi, sigma)
    pad, a = int(psi), float(zipf_a)
    tail = 1.0 - (d + 1.0) ** (1.0 - a)
    j = np.arange(pad)
    idx = np.empty((n, pad), np.int32)
    for lo in range(0, n, chunk):
        ln = lengths[lo : lo + chunk, None]
        u = rng.random((ln.shape[0], pad))
        ranks = np.floor((1.0 - u * tail) ** (1.0 / (1.0 - a))).astype(np.int64) - 1
        ranks = np.sort(np.where(j < ln, np.minimum(ranks, d - 1), d), axis=1)
        ranks = np.maximum.accumulate(ranks - j, axis=1) + j
        ranks = np.minimum(ranks, d - ln + j)
        idx[lo : lo + chunk] = np.where(j < ln, ranks, -1)
    return idx, lengths


def corpus(cfg: dict, seed: int):
    """The configuration's standing corpus for ``seed``."""
    return distinct_rows(rng_for(seed, STREAM_CORPUS), cfg["n_docs"],
                         cfg["vocab"], cfg["mean_distinct"], cfg["psi"],
                         cfg["zipf_a"], cfg["length_sigma"])


def mapping(cfg: dict, seed: int) -> np.ndarray:
    """The sketch map pi: (d,) int32 of uniform bins in [0, N)."""
    return rng_for(seed, STREAM_MAPPING).integers(
        0, cfg["n_bins"], size=cfg["vocab"], dtype=np.int32)


def _key(seed: int, stream: int):
    import jax

    bits = rng_for(seed, stream).integers(0, 2**32, size=2, dtype=np.uint64)
    return jax.random.wrap_key_data(np.asarray(bits, np.uint32), impl="threefry2x32")


@functools.lru_cache(maxsize=None)
def _device_fns(n: int, d: int, mean: float, psi: int, zipf_a: float, sigma: float,
                chunk: int):
    import jax
    import jax.numpy as jnp

    tail = 1.0 - (d + 1.0) ** (1.0 - zipf_a)
    mu = math.log(mean) - sigma**2 / 2

    @jax.jit
    def lengths(key):
        ln = jnp.exp(mu + sigma * jax.random.normal(key, (n,), jnp.float32))
        ln = jnp.clip(ln, 1, psi).astype(jnp.int32)
        return jnp.pad(ln, (0, -n % chunk))  # rows past n are empty

    @jax.jit
    def rows(key, ln):
        j = jnp.arange(psi)
        L = ln[:, None]
        u = jax.random.uniform(key, (chunk, psi), jnp.float32)
        x = jnp.floor((1.0 - u * tail) ** (1.0 / (1.0 - zipf_a)))
        ranks = jnp.clip(x.astype(jnp.int32) - 1, 0, d - 1)
        ranks = jnp.sort(jnp.where(j < L, ranks, d), axis=1)
        ranks = jax.lax.cummax(ranks - j, axis=1) + j
        ranks = jnp.minimum(ranks, d - L + j)
        return jnp.where(j < L, ranks, -1).astype(jnp.int32)

    return lengths, rows


def device_rows(seed: int, stream: int, n: int, d: int, mean: float, psi: int,
                zipf_a: float, sigma: float = 0.5, chunk: int = 4096, on_chunk=None):
    """The generator on the device: (idx (n, psi) host int32, lengths (n,)).

    ``on_chunk(lo, rows)`` sees each device chunk of ``chunk`` rows (the last
    one cut to ``n``) as it is made, so that a build can consume it there."""
    import jax

    lengths, rows = _device_fns(n, d, float(mean), psi, float(zipf_a), float(sigma), chunk)
    k_len, k_rows = jax.random.split(_key(seed, stream))
    ln = lengths(k_len)
    out = np.empty((n, psi), np.int32)
    for c, lo in enumerate(range(0, n, chunk)):
        hi = min(lo + chunk, n)
        r = rows(jax.random.fold_in(k_rows, c), ln[lo : lo + chunk])
        if hi - lo < chunk:
            r = r[: hi - lo]
        if on_chunk is not None:
            on_chunk(lo, r)
        out[lo:hi] = np.asarray(r)
    return out, np.asarray(ln[:n])


def device_corpus(cfg: dict, seed: int, on_chunk=None, chunk: int = 4096):
    """The configuration's standing corpus for ``seed``, made on the device."""
    return device_rows(seed, STREAM_CORPUS, cfg["n_docs"], cfg["vocab"], cfg["mean_distinct"],
                       cfg["psi"], cfg["zipf_a"], cfg["length_sigma"], chunk, on_chunk)


def device_pool(cfg: dict, seed: int, n: int):
    """``n`` fresh docs from the same distribution (ingest contents)."""
    return device_rows(seed, STREAM_POOL, n, cfg["vocab"], cfg["mean_distinct"], cfg["psi"],
                       cfg["zipf_a"], cfg["length_sigma"], min(4096, n))[0]
