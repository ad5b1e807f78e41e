"""Backend protocol + registry for the sketch engine.

Replaces the two ad-hoc dispatch mechanisms the retrieval stack grew:
the ``scorer: Optional[Callable]`` plumbed through ``core.index`` and the
``interpret=`` flags threaded by hand into ``kernels.ops``. A backend owns
both halves of the data path — *sketch* (construction) and *score*
(AND-popcount + estimator epilogue) — so callers pick a name once:

  * ``oracle``            pure-jnp reference (scatter build, materialized
                          (Q, C, W) scoring) — small problems, shard_map
                          bodies, ground truth.
  * ``pallas``            Pallas kernels, ``interpret`` auto-resolved from
                          the platform (compiled on TPU, interpret off-TPU).
  * ``pallas-tpu``        Pallas kernels, compiled (TPU only).
  * ``pallas-interpret``  Pallas kernels forced to interpret mode.
  * ``auto``              alias for ``pallas``.

``score`` takes optional precomputed fill counts; when the caller holds a
:class:`~repro.engine.store.SketchStore` the corpus fills come from its
ingest-time cache instead of an O(C·W) popcount per query (DESIGN.md §6).

``topk`` is the serving hot path (DESIGN.md §7): score -> k best per query
without ever materializing the (Q, C) matrix. The oracle backend is the
chunked ``lax.top_k``-merge reference; the pallas backends run the fused
streaming kernel (``kernels.topk_stream``). Both honor ``corpus_valid``
masks (masked rows return score -inf / id -1) and the -inf/-1 padding
contract for ``k`` larger than the retrievable corpus.
"""

from __future__ import annotations

from typing import Callable, Dict, Optional, Protocol, Tuple

import jax
import jax.numpy as jnp

from ..core import binsketch, counting, estimators, packed as pk

__all__ = ["Backend", "register_backend", "get_backend", "available_backends",
           "from_legacy_scorer"]


class Backend(Protocol):
    """Both halves of the sketch data path behind one name."""

    name: str

    def sketch(
        self, cfg: binsketch.BinSketchConfig, mapping: jax.Array, idx: jax.Array
    ) -> jax.Array:
        """(B, P) padded sparse rows -> (B, W) packed sketches."""
        ...

    def count(
        self, cfg: binsketch.BinSketchConfig, mapping: jax.Array, idx: jax.Array
    ) -> jax.Array:
        """(B, P) padded sparse rows -> (B, N) int32 per-bin occupancy.

        The counting-BinSketch construction (``core.counting``): the
        mutable head segment's insert/retract deltas. ``counters > 0``
        packs to exactly what :meth:`sketch` returns.
        """
        ...

    def score(
        self,
        q: jax.Array,
        corpus: jax.Array,
        n_bins: int,
        measure: str,
        *,
        q_fills: Optional[jax.Array] = None,
        corpus_fills: Optional[jax.Array] = None,
    ) -> jax.Array:
        """Packed (Q, W) x (C, W) -> (Q, C) float32 similarity.

        ``q_fills`` / ``corpus_fills`` are optional precomputed |row_s|
        vectors; ``None`` means the backend popcounts that side itself.
        """
        ...

    def topk(
        self,
        q: jax.Array,
        corpus: jax.Array,
        n_bins: int,
        measure: str,
        k: int,
        *,
        q_fills: Optional[jax.Array] = None,
        corpus_fills: Optional[jax.Array] = None,
        corpus_valid: Optional[jax.Array] = None,
    ) -> Tuple[jax.Array, jax.Array]:
        """Packed (Q, W) x (C, W) -> (scores (Q, k), ids (Q, k)), streaming.

        Never materializes the full (Q, C) matrix. Rows sorted descending,
        ties broken toward the lower doc id (``lax.top_k`` convention);
        ``corpus_valid`` masks rows out entirely; slots beyond the
        retrievable corpus hold score -inf / id -1.
        """
        ...

    def rebucket(
        self, packed: jax.Array, n_bins: int, n_bins_new: int
    ) -> jax.Array:
        """Packed (B, W) rows at ``n_bins`` -> (B, W') rows at the smaller
        ``n_bins_new``, OR-folding bin ``j`` into ``j mod n_bins_new``.

        The sketch-space re-bucketing identity (DESIGN.md §11): the result
        equals sketching the underlying sets under ``pi mod n_bins_new``,
        so mixed-width serving re-sketches a query batch once per distinct
        segment width from the base-width sketch alone.
        """
        ...

    def band_hash(self, packed: jax.Array, n_bands: int) -> jax.Array:
        """Packed (B, W) rows -> (B, nb_eff) uint32 LSH band keys.

        Band ``t`` hashes words ``[t*wpb, (t+1)*wpb)`` (``wpb = ceil(W /
        n_bands)``); two rows collide on a band iff that word group is
        identical. Feeds the banded prefilter's bucket index (DESIGN.md
        §12). ``n_bands`` clamps to W — callers size off the output shape.
        """
        ...


def _masked_topk_merge(parts_s, parts_i, k):
    """Final merge of per-chunk (Q, k) top-k lists; -inf slots get id -1."""
    sc_all = jnp.concatenate(parts_s, axis=1)
    ix_all = jnp.concatenate(parts_i, axis=1)
    sc, pos = jax.lax.top_k(sc_all, k)
    ids = jnp.take_along_axis(ix_all, pos, axis=1)
    return sc, jnp.where(jnp.isneginf(sc), -1, ids)


class OracleBackend:
    """Pure-jnp reference path (also the body used inside shard_map).

    ``topk_crossover``: below this corpus-row count :meth:`topk` skips the
    chunked streaming merge and runs one materialize + ``lax.top_k`` — at
    small C the merge bookkeeping is pure overhead (measured on a quiet
    single-core host: materialize 1.07–1.15x faster at 256–2048 rows,
    dead even at 4096, then the chunked arm wins 1.4x at 8192 and >3x
    from 16384 up) while the (Q, C) transient is still tiny. Identical
    results either way (chunk order preserves global index order, so the
    tie-break already matches a full ``lax.top_k``). Override
    per-instance: ``be.topk_crossover = 0`` forces the streaming path
    everywhere.
    """

    name = "oracle"
    topk_chunk = 4096  # corpus rows scored per chunk in the streaming top-k
    topk_crossover = 4096  # below: materialize + one top_k, no chunk merge

    def sketch(self, cfg, mapping, idx):
        return binsketch.sketch_indices(cfg, mapping, idx)

    def count(self, cfg, mapping, idx):
        return counting.count_indices_dense(cfg, mapping, idx)

    def score(self, q, corpus, n_bins, measure, *, q_fills=None, corpus_fills=None):
        return estimators.pairwise_similarity(
            q, corpus, n_bins, measure, a_fills=q_fills, b_fills=corpus_fills
        )

    def topk(self, q, corpus, n_bins, measure, k, *, q_fills=None,
             corpus_fills=None, corpus_valid=None):
        """Chunked ``lax.top_k`` merge: scores ``topk_chunk`` corpus rows at a
        time, keeps k per chunk, merges once — peak transient O(Q·chunk), not
        O(Q·C). Chunk order preserves global index order, so tie-breaks match
        a full ``lax.top_k`` over the materialized matrix exactly."""
        nq, c = q.shape[0], corpus.shape[0]
        if c == 0:
            return (jnp.full((nq, k), -jnp.inf, jnp.float32),
                    jnp.full((nq, k), -1, jnp.int32))
        qf = q_fills if q_fills is not None else pk.row_popcount(q)
        if c < self.topk_crossover:
            s = self.score(q, corpus, n_bins, measure,
                           q_fills=qf, corpus_fills=corpus_fills)
            if corpus_valid is not None:
                s = jnp.where(corpus_valid[None, :] != 0, s, -jnp.inf)
            kk = min(int(k), c)
            sc, ix = jax.lax.top_k(s, kk)
            pad = ((0, 0), (0, int(k) - kk))
            sc = jnp.pad(sc, pad, constant_values=-jnp.inf)
            ix = jnp.pad(ix, pad, constant_values=-1)
            return sc, jnp.where(jnp.isneginf(sc), -1, ix)
        parts_s, parts_i = [], []
        for lo in range(0, c, self.topk_chunk):
            hi = min(lo + self.topk_chunk, c)
            cf = corpus_fills[lo:hi] if corpus_fills is not None else None
            s = self.score(q, corpus[lo:hi], n_bins, measure,
                           q_fills=qf, corpus_fills=cf)
            if corpus_valid is not None:
                s = jnp.where(corpus_valid[lo:hi][None, :] != 0, s, -jnp.inf)
            kk = min(k, hi - lo)
            sc, ix = jax.lax.top_k(s, kk)
            pad = ((0, 0), (0, k - kk))
            parts_s.append(jnp.pad(sc, pad, constant_values=-jnp.inf))
            parts_i.append(jnp.pad(ix + lo, pad, constant_values=-1))
        return _masked_topk_merge(parts_s, parts_i, k)

    def rebucket(self, packed, n_bins, n_bins_new):
        return pk.fold_packed(packed, n_bins, n_bins_new)

    def band_hash(self, packed, n_bands):
        return pk.band_hash(packed, n_bands)


class PallasBackend:
    """Pallas kernel path; ``interpret=None`` resolves per-platform.

    ``topk_crossover``: below this corpus-row count the fused streaming
    kernel's sort-network overhead loses to a plain materialize +
    ``lax.top_k`` (BENCH_engine topk_sweep: fused speedup 0.93 at 4096
    rows, >1.25 from 16384 up), so :meth:`topk` auto-selects the
    materialize path for ``C < topk_crossover``. In **interpret mode**
    the crossover inverts entirely — emulation cost scales with the fused
    kernel's grid, and the materialize composition wins 4–240x at every
    size — so whenever the effective interpret flag is set, auto routing
    takes the materialize path regardless of C. Both paths share the
    score epilogue and the (score desc, id asc) tie-break, so results are
    identical. Override per-instance (``be.topk_crossover = 0`` forces the
    fused kernel everywhere, interpret included, e.g. for kernel tests).
    """

    topk_crossover = 8192

    def __init__(self, name: str, interpret: Optional[bool]):
        self.name = name
        self.interpret = interpret

    @property
    def interpreted(self) -> bool:
        """Whether the kernels run in Pallas interpret mode: the forced
        flag, else resolved from the platform (compiled only on a TPU)."""
        from ..kernels import ops

        return (ops._interpret_default() if self.interpret is None
                else bool(self.interpret))

    def sketch(self, cfg, mapping, idx):
        from ..kernels import ops

        bins = binsketch.map_indices(cfg, mapping, idx)
        return ops.build_sketch(bins, cfg.n_bins, interpret=self.interpret)

    def count(self, cfg, mapping, idx):
        from ..kernels import ops

        bins = binsketch.map_indices(cfg, mapping, idx)
        return ops.count_bins(bins, cfg.n_bins, interpret=self.interpret)

    def score(self, q, corpus, n_bins, measure, *, q_fills=None, corpus_fills=None):
        from ..kernels import ops

        return ops.sketch_score(
            q, corpus, n_bins=n_bins, measure=measure,
            a_fills=q_fills, b_fills=corpus_fills, interpret=self.interpret,
        )

    def topk(self, q, corpus, n_bins, measure, k, *, q_fills=None,
             corpus_fills=None, corpus_valid=None):
        from ..kernels import ops

        c = corpus.shape[0]
        if 0 < c and (c < self.topk_crossover
                      or (self.interpreted and self.topk_crossover > 0)):
            # materialize path: one (Q, C) score tile + lax.top_k — faster
            # than the streaming sort network on small corpora and at every
            # size under interpret-mode emulation; identical results (same
            # epilogue, same lowest-id tie-break). topk_crossover = 0 still
            # forces the fused kernel (kernel tests).
            s = self.score(q, corpus, n_bins, measure,
                           q_fills=q_fills, corpus_fills=corpus_fills)
            if corpus_valid is not None:
                s = jnp.where(corpus_valid[None, :] != 0, s, -jnp.inf)
            kk = min(int(k), c)
            sc, ix = jax.lax.top_k(s, kk)
            pad = ((0, 0), (0, int(k) - kk))
            sc = jnp.pad(sc, pad, constant_values=-jnp.inf)
            ix = jnp.pad(ix, pad, constant_values=-1)
            return sc, jnp.where(jnp.isneginf(sc), -1, ix)
        return ops.sketch_topk(
            q, corpus, n_bins=n_bins, measure=measure, k=int(k),
            a_fills=q_fills, b_fills=corpus_fills, b_valid=corpus_valid,
            interpret=self.interpret,
        )

    def rebucket(self, packed, n_bins, n_bins_new):
        from ..kernels import ops

        return ops.rebucket(
            packed, int(n_bins), int(n_bins_new), interpret=self.interpret
        )

    def band_hash(self, packed, n_bands):
        from ..kernels import ops

        return ops.band_hash(packed, int(n_bands), interpret=self.interpret)


class _LegacyScorerBackend:
    """Adapter for the deprecated ``SketchIndex.scorer`` callable (sketching
    falls back to the oracle; cached fills cannot be streamed through the
    two-argument closure and are ignored)."""

    name = "legacy-scorer"

    def __init__(self, scorer: Callable[[jax.Array, jax.Array], jax.Array]):
        self._scorer = scorer
        self._oracle = OracleBackend()

    def sketch(self, cfg, mapping, idx):
        return self._oracle.sketch(cfg, mapping, idx)

    def count(self, cfg, mapping, idx):
        return self._oracle.count(cfg, mapping, idx)

    def score(self, q, corpus, n_bins, measure, *, q_fills=None, corpus_fills=None):
        return self._scorer(q, corpus)

    def topk(self, q, corpus, n_bins, measure, k, *, q_fills=None,
             corpus_fills=None, corpus_valid=None):
        # legacy closures can only produce the full matrix; mask + top_k here
        s = self._scorer(q, corpus)
        if corpus_valid is not None:
            s = jnp.where(corpus_valid[None, :] != 0, s, -jnp.inf)
        kk = min(int(k), corpus.shape[0])
        sc, ix = jax.lax.top_k(s, kk)
        pad = ((0, 0), (0, int(k) - kk))
        sc = jnp.pad(sc, pad, constant_values=-jnp.inf)
        ix = jnp.pad(ix, pad, constant_values=-1)
        return sc, jnp.where(jnp.isneginf(sc), -1, ix)

    def rebucket(self, packed, n_bins, n_bins_new):
        return self._oracle.rebucket(packed, n_bins, n_bins_new)

    def band_hash(self, packed, n_bands):
        return self._oracle.band_hash(packed, n_bands)


_REGISTRY: Dict[str, Callable[[], Backend]] = {}


def register_backend(name: str, factory: Callable[[], Backend]) -> None:
    _REGISTRY[name] = factory


def available_backends():
    return sorted(_REGISTRY)


def get_backend(name: Optional[str] = None) -> Backend:
    """Resolve a backend by name; ``None``/"auto" -> the Pallas kernels with
    interpret auto-resolved (compiled on TPU, interpret elsewhere)."""
    if name is None:
        name = "auto"
    if isinstance(name, str):
        try:
            return _REGISTRY[name]()
        except KeyError:
            raise ValueError(
                f"unknown backend {name!r}; have {available_backends()}"
            ) from None
    return name  # already a Backend instance


def from_legacy_scorer(scorer) -> Backend:
    return _LegacyScorerBackend(scorer)


register_backend("oracle", OracleBackend)
register_backend("pallas", lambda: PallasBackend("pallas", None))
register_backend("auto", lambda: PallasBackend("pallas", None))
register_backend("pallas-tpu", lambda: PallasBackend("pallas-tpu", False))
register_backend("pallas-interpret", lambda: PallasBackend("pallas-interpret", True))
