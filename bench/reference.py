"""The plain reference: BinSketch top-k and exact Jaccard top-k, from the raw sets.

It imports nothing of the program and takes nothing that the program has
made. Its inputs are the benchmark's own: the raw documents (padded word-id
rows) and the sketch map pi that the benchmark drew from the seed.

* A document's sketch is the set of bins ``{pi(w) : w in doc}`` (paper,
  Definition 4), packed 32 bins to a uint32 word, bin ``j`` at bit
  ``j % 32`` of word ``j // 32``.
* Set-intersection counts over any universe (bins for the sketch, words for
  exact Jaccard) come from one-hot rows multiplied on the device in bf16
  with f32 accumulation: every product is 0 or 1 and every sum is at most
  ``psi``, so the counts are exact integers.
* The BinSketch Jaccard estimate is the paper's Algorithms 1 and 4,
  evaluated in float64 on the host from those integer counts:
  ``card(c) = ln(1 - c/N) / ln(1 - 1/N)``,
  ``ip = card(|a_s|) + card(|b_s|) - card(|a_s OR b_s|)``,
  ``J = ip / card(|a_s OR b_s|)``, clipped to [0, 1].
* A full scan ranks every stored document on the device in float32 and
  keeps ``k + margin`` candidates per query with their integer counts; the
  host ranks the candidates again in float64 (BinSketch) or in exactly the
  float32 arithmetic of ``exact_topk`` (exact Jaccard), ties to the lower id.
"""

from __future__ import annotations

import functools

import numpy as np

#: candidates kept per query beyond k by the device scan, so that float32
#: rounding there cannot push a float64 top-k member out of the candidates
MARGIN = 32


# ------------------------------------------------------------------- sets
def bin_rows(idx: np.ndarray, pi: np.ndarray) -> np.ndarray:
    """(B, P) padded word rows -> (B, P) padded bin rows, each bin once."""
    bins = np.where(idx >= 0, pi[np.maximum(idx, 0)], -1)
    s = np.sort(bins, axis=1)
    dup = np.zeros_like(s, bool)
    dup[:, 1:] = (s[:, 1:] == s[:, :-1]) & (s[:, 1:] >= 0)
    return np.where(dup, -1, s)


def set_sizes(rows: np.ndarray) -> np.ndarray:
    return (rows >= 0).sum(axis=1).astype(np.int64)


def pack_rows(bins: np.ndarray, n_bins: int) -> np.ndarray:
    """(B, P) padded bin rows -> (B, W) uint32 packed sketches."""
    w = (n_bins + 31) // 32
    out = np.zeros((bins.shape[0], w), np.uint32)
    r, c = np.nonzero(bins >= 0)
    b = bins[r, c].astype(np.int64)
    np.bitwise_or.at(out, (r, b // 32), (np.uint32(1) << (b % 32).astype(np.uint32)))
    return out


def pair_counts(q_rows: np.ndarray, rows: np.ndarray, universe: int) -> np.ndarray:
    """|q_i ∩ rows_ij| for q_rows (Q, P) and rows (Q, k, P) -> (Q, k) int64."""
    dense = np.zeros((q_rows.shape[0], universe + 1), bool)
    r, c = np.nonzero(q_rows >= 0)
    dense[r, q_rows[r, c]] = True
    safe = np.where(rows >= 0, rows, universe)  # column `universe` stays False
    return dense[np.arange(q_rows.shape[0])[:, None, None], safe].sum(axis=2)


# -------------------------------------------------------------- estimators
def card64(c, n_bins: int) -> np.ndarray:
    """Paper Alg. 1 line 3 in float64; a full sketch saturates at N - 1/2."""
    c = np.minimum(np.asarray(c, np.float64), n_bins - 0.5)
    return np.log1p(-c / n_bins) / np.log1p(-1.0 / n_bins)


def binsketch_jaccard64(na, nb, nab, n_bins: int) -> np.ndarray:
    """BinSketch Jaccard estimate (Alg. 1 + Alg. 4) from integer counts."""
    na, nb, nab = (np.asarray(x, np.float64) for x in (na, nb, nab))
    union = card64(na + nb - nab, n_bins)
    ip = np.maximum(card64(na, n_bins) + card64(nb, n_bins) - union, 0.0)
    return np.clip(ip / np.maximum(union, 1e-9), 0.0, 1.0)


def jaccard32(qs, cs, inter) -> np.ndarray:
    """Exact Jaccard in exactly ``exact_topk``'s float32 arithmetic."""
    inter = np.asarray(inter, np.float32)
    union = np.asarray(qs, np.float32) + np.asarray(cs, np.float32) - inter
    return inter / np.maximum(union, np.float32(1.0))


# ------------------------------------------------------------ device scan
@functools.lru_cache(maxsize=None)
def _scan_fns(universe: int, n_bins: int, kind: str, kc: int, dtype: str):
    import jax
    import jax.numpy as jnp

    def onehot(rows, pi):
        """Padded word rows -> (B, universe) bf16 one-hot of their sets
        (bins through ``pi`` for the sketch; words themselves for Jaccard);
        a bin hit twice is still one."""
        if kind == "binsketch":
            rows = jnp.where(rows >= 0, pi[jnp.maximum(rows, 0)], -1)
        b = rows.shape[0]
        r = jnp.broadcast_to(jnp.arange(b)[:, None], rows.shape)
        c = jnp.where(rows >= 0, rows, universe)
        hot = jnp.zeros((b, universe), jnp.bfloat16).at[r, c].set(1, mode="drop")
        return hot, hot.sum(axis=1, dtype=jnp.float32)

    dt = jnp.dtype(dtype)

    def card(c):
        c = jnp.minimum(c, dt.type(n_bins - 0.5))
        return jnp.log1p(-c / dt.type(n_bins)) / dt.type(np.log1p(-1.0 / n_bins))

    def score(qs, cs, inter):
        if kind == "binsketch":
            qs, cs, inter = (x.astype(dt) for x in (qs, cs, inter))
            union = card(qs + cs - inter)
            ip = jnp.maximum(card(qs) + card(cs) - union, dt.type(0))
            est = jnp.clip(ip / jnp.maximum(union, dt.type(1e-9)), 0, 1)
            return est.astype(jnp.float32)
        return inter / jnp.maximum(qs + cs - inter, 1.0)

    @jax.jit
    def step(q_hot, q_size, limit, ver, rows, ids, born, dead, pi, best):
        c_hot, c_size = onehot(rows, pi)
        inter = jax.lax.dot_general(
            q_hot, c_hot, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        s = score(q_size[:, None], c_size[None, :], inter)
        seen = ((ids[None, :] >= 0) & (ids[None, :] < limit[:, None])
                & (born[None, :] <= ver[:, None]) & (ver[:, None] < dead[None, :]))
        s = jnp.where(seen, s, -jnp.inf)
        nq, nc = inter.shape
        cand = (jnp.concatenate([best[0], s], 1),
                jnp.concatenate([best[1], jnp.broadcast_to(ids, (nq, nc))], 1),
                jnp.concatenate([best[2], inter.astype(jnp.int32)], 1),
                jnp.concatenate([best[3], jnp.broadcast_to(
                    c_size.astype(jnp.int32), (nq, nc))], 1))
        # chunks come in ascending id order and ``best`` holds only lower
        # ids (or another version of the same id, which no query sees with
        # this one), so top_k's lower-position tie-break is the lower-id one
        _, pos = jax.lax.top_k(cand[0], kc)
        return tuple(jnp.take_along_axis(x, pos, 1) for x in cand)

    return jax.jit(onehot), step


#: the version after every other: a row that nothing replaced
LAST = np.iinfo(np.int32).max


def device_chunks(fetch_rows, n_rows: int, chunk: int = 2048, *, ids=None, born=None,
                  dead=None) -> list:
    """Rows ``[0, n_rows)`` uploaded once as ``(rows, ids, born, dead)``
    device chunks of one shape: ``fetch_rows(lo, hi)`` gives the word rows
    ``[lo, hi)``, ``ids`` their doc ids in ascending order (default: the row
    numbers), and a row is its doc's contents in the versions ``[born,
    dead)`` of the corpus (default: all). The last chunk is padded with
    empty rows of id -1."""
    import jax.numpy as jnp

    ids = np.arange(n_rows) if ids is None else np.asarray(ids)
    born = np.zeros(n_rows, np.int64) if born is None else np.asarray(born)
    dead = np.full(n_rows, LAST) if dead is None else np.asarray(dead)
    out = []
    for lo in range(0, n_rows, chunk):
        hi = min(lo + chunk, n_rows)
        rows = fetch_rows(lo, hi)
        meta = np.zeros((3, chunk), np.int32)
        meta[0] = -1
        meta[:, : hi - lo] = ids[lo:hi], born[lo:hi], dead[lo:hi]
        if hi - lo < chunk:
            rows = np.concatenate(
                [rows, np.full((chunk - (hi - lo), rows.shape[1]), -1, rows.dtype)])
        out.append((jnp.asarray(rows), *(jnp.asarray(m) for m in meta)))
    return out


def scan_topk(q_idx: np.ndarray, limits: np.ndarray, chunks: list, *, kind: str,
              universe: int, pi: np.ndarray | None = None, n_bins: int = 0, k: int = 10,
              dtype: str = "float32", vers: np.ndarray | None = None):
    """Candidates for the top ``k`` of every query over the stored ``chunks``
    (from ``device_chunks``).

    ``q_idx`` (Q, P) are the queries' padded word rows; ``limits`` (Q,)
    admit only ids below it for each query (what had been acknowledged when
    the query was sent), and ``vers`` (Q,) (default 0) only the rows that
    held their doc's contents in that version of the corpus. ``kind`` is
    ``"binsketch"`` (sets of bins through ``pi``, universe N) or
    ``"jaccard"`` (sets of words, universe d). Returns
    host arrays (Q, k + MARGIN): device scores (computed in ``dtype``), ids,
    intersection counts, candidate set sizes; and (Q,) query set sizes."""
    import jax.numpy as jnp

    kc = k + MARGIN
    onehot, step = _scan_fns(universe, n_bins, kind, kc, dtype)
    nq = len(q_idx)
    pad = -nq % 256  # few query shapes, so few compiles
    q_idx = np.concatenate([q_idx, np.full((pad, q_idx.shape[1]), -1, q_idx.dtype)])
    limits = np.concatenate([limits, np.zeros(pad, limits.dtype)])
    vers = np.zeros(nq, np.int32) if vers is None else np.asarray(vers)
    ver = jnp.asarray(np.concatenate([vers, np.zeros(pad, vers.dtype)]).astype(np.int32))
    pi_dev = jnp.asarray(pi if pi is not None else np.zeros(1, np.int32))
    q_hot, q_size = onehot(jnp.asarray(q_idx), pi_dev)
    limit = jnp.asarray(limits.astype(np.int32))
    n = len(q_idx)
    best = (jnp.full((n, kc), -jnp.inf, jnp.float32), jnp.full((n, kc), -1, jnp.int32),
            jnp.zeros((n, kc), jnp.int32), jnp.zeros((n, kc), jnp.int32))
    for rows, ids, born, dead in chunks:
        best = step(q_hot, q_size, limit, ver, rows, ids, born, dead, pi_dev, best)
    out = [np.asarray(x)[:nq] for x in best]
    return out + [np.asarray(q_size)[:nq].astype(np.int64)]


def rank(ids: np.ndarray, scores: np.ndarray, k: int):
    """Per row: the k best (score desc, id asc) of the candidates."""
    s = np.where(ids >= 0, scores, -np.inf)
    ids = np.where(np.isneginf(s), -1, ids)
    order = np.lexsort((np.where(ids >= 0, ids, np.iinfo(np.int64).max), -s), axis=1)[:, :k]
    return np.take_along_axis(ids, order, 1), np.take_along_axis(s, order, 1)
