"""SketchStore — packed, capacity-managed sketch corpus with incremental ingest.

The store owns the (C, W) packed corpus plus the *fill-count cache*: the
per-row popcount |a_s| every estimator epilogue needs. The legacy path
(``ops.sketch_score`` called cold) recomputed ``row_popcount`` over the whole
corpus on every query — O(C·W) per call; the store computes fills exactly
once at ingest and the query path streams the cached vector into the scorer
(DESIGN.md §6).

Ingest is incremental: ``add`` appends rows into preallocated capacity with
amortized-doubling growth, so a streaming producer pays O(1) amortized
device-concat per document instead of a rebuild-from-scratch. Because
BinSketch is an OR-homomorphism, updates to an *existing* document and
merges of two shard-local stores are both plain bitwise ORs (``merge_rows``,
``merge``) — no second pass over raw data, ever.
"""

from __future__ import annotations

import dataclasses
from typing import List, NamedTuple, Optional, TYPE_CHECKING

import jax
import jax.numpy as jnp

from ..core import binsketch, packed as pk
from ..obs.trace import span

if TYPE_CHECKING:  # pragma: no cover - typing only
    from .backends import Backend

__all__ = ["SegmentView", "SketchStore"]


class SegmentView(NamedTuple):
    """One scoreable slab of corpus, as the query path sees it.

    Both store kinds speak this: ``SketchStore`` is a single view whose row
    index *is* the doc id; a ``SegmentedStore`` yields one view per sealed
    segment plus the mutable head. ``ids is None`` means identity mapping;
    ``valid is None`` means no tombstones (all rows retrievable).
    ``n_bins is None`` means the store's base sketch width; a *distilled*
    segment (DESIGN.md §11) carries its smaller width here, and the engine
    re-buckets the query sketches to match before scoring the view.
    """

    sketches: jax.Array  # (n, W) uint32 packed rows
    fills: jax.Array  # (n,) int32 ingest-time fill cache
    ids: Optional[jax.Array]  # (n,) int32 global doc ids, or None
    valid: Optional[jax.Array]  # (n,) int32/bool tombstone mask, or None
    n_bins: Optional[int] = None  # sketch width, or None = store base width


def _grow(arr: jax.Array, new_capacity: int) -> jax.Array:
    pads = [(0, new_capacity - arr.shape[0])] + [(0, 0)] * (arr.ndim - 1)
    return jnp.pad(arr, pads)


@dataclasses.dataclass
class SketchStore:
    """Packed sketch corpus + fill-count cache, doc id == row index."""

    cfg: binsketch.BinSketchConfig
    mapping: jax.Array
    _sketches: jax.Array  # (capacity, W) uint32; rows >= size are zero
    _fills: jax.Array  # (capacity,) int32; rows >= size are zero
    size: int = 0
    # add calls so far, the ``call`` stat of each ``repro.store.add`` span
    _adds: int = dataclasses.field(default=0, init=False, repr=False)

    # ------------------------------------------------------------ construct
    @classmethod
    def create(
        cls,
        cfg: binsketch.BinSketchConfig,
        mapping: jax.Array,
        capacity: int = 1024,
    ) -> "SketchStore":
        capacity = max(int(capacity), 1)
        return cls(
            cfg,
            mapping,
            jnp.zeros((capacity, cfg.n_words), jnp.uint32),
            jnp.zeros((capacity,), jnp.int32),
            0,
        )

    @classmethod
    def from_indices(
        cls,
        cfg: binsketch.BinSketchConfig,
        mapping: jax.Array,
        corpus_idx: jax.Array,
        *,
        backend: Optional["Backend"] = None,
        batch: int = 4096,
    ) -> "SketchStore":
        """Batch build: sketch (C, P) padded sparse rows in ``batch`` chunks."""
        store = cls.create(cfg, mapping, capacity=max(int(corpus_idx.shape[0]), 1))
        store.add(corpus_idx, backend=backend, batch=batch)
        return store

    @classmethod
    def from_sketches(
        cls,
        cfg: binsketch.BinSketchConfig,
        mapping: jax.Array,
        sketches: jax.Array,
    ) -> "SketchStore":
        """Wrap pre-built packed sketches (fills computed here, once)."""
        sketches = sketches.astype(jnp.uint32)
        return cls(cfg, mapping, sketches, pk.row_popcount(sketches), sketches.shape[0])

    # ------------------------------------------------------------ properties
    @property
    def capacity(self) -> int:
        return int(self._sketches.shape[0])

    @property
    def sketches(self) -> jax.Array:
        """(size, W) packed corpus view."""
        return self._sketches[: self.size]

    @property
    def fills(self) -> jax.Array:
        """(size,) cached |row_s| fill counts — computed at ingest."""
        return self._fills[: self.size]

    def segment_views(self, now: Optional[float] = None) -> List[SegmentView]:
        """The whole store as one segment (row index == doc id, no mask).
        ``now`` is accepted for surface parity with ``SegmentedStore`` and
        ignored — an append-only store has no lifecycle clock."""
        if self.size == 0:
            return []
        return [SegmentView(self.sketches, self.fills, None, None)]

    # ---------------------------------------------------------------- ingest
    def _ensure_capacity(self, n: int) -> None:
        cap = self.capacity
        if n <= cap:
            return
        while cap < n:
            cap *= 2  # amortized doubling
        self._sketches = _grow(self._sketches, cap)
        self._fills = _grow(self._fills, cap)

    def _sketch_rows(self, idx: jax.Array, backend: Optional["Backend"]) -> jax.Array:
        if backend is not None:
            return backend.sketch(self.cfg, self.mapping, idx)
        return binsketch.sketch_indices(self.cfg, self.mapping, idx)

    def add(
        self,
        idx: jax.Array,
        *,
        backend: Optional["Backend"] = None,
        batch: int = 4096,
    ) -> range:
        """Sketch (B, P) padded sparse rows and append; returns assigned ids.

        Each chunk streams straight into capacity via :meth:`add_sketches` —
        no concatenation of all chunks into one (B, W) temporary, so peak
        device memory during a large ingest is one batch, not the whole
        corpus twice."""
        lo = self.size
        self._adds += 1
        with span("store.add", docs=int(idx.shape[0]), call=self._adds):
            for s in range(0, idx.shape[0], batch):
                self.add_sketches(self._sketch_rows(idx[s : s + batch], backend))
        return range(lo, self.size)

    def add_sketches(self, sketches: jax.Array) -> range:
        """Append pre-built packed rows; fills enter the cache here (once)."""
        b = int(sketches.shape[0])
        if b == 0:
            return range(self.size, self.size)
        self._ensure_capacity(self.size + b)
        sketches = sketches.astype(jnp.uint32)
        lo = self.size
        self._sketches = jax.lax.dynamic_update_slice_in_dim(
            self._sketches, sketches, lo, axis=0
        )
        self._fills = jax.lax.dynamic_update_slice_in_dim(
            self._fills, pk.row_popcount(sketches), lo, axis=0
        )
        self.size += b
        return range(lo, self.size)

    def merge_rows(
        self,
        doc_ids: jax.Array,
        idx: jax.Array,
        *,
        backend: Optional["Backend"] = None,
    ) -> None:
        """OR new content into *existing* docs (streaming updates).

        ``doc_ids: (B,)`` existing row ids, ``idx: (B, P)`` padded sparse rows.
        sketch(old | new) == sketch(old) | sketch(new), so this is one OR plus
        a fill refresh on the B touched rows — never a corpus rebuild.
        """
        import numpy as np

        upd = self._sketch_rows(idx, backend)
        # scatter-with-set keeps only one write per index, so duplicate doc
        # ids must be OR-combined first: segment-OR over packed words,
        # O(B·W) — not the dense (U, B, W) one-hot broadcast mask
        uniq, inv = np.unique(np.asarray(doc_ids, np.int32), return_inverse=True)
        if len(uniq) < len(inv):
            upd = pk.segment_or(upd, jnp.asarray(inv), len(uniq))
        doc_ids = jnp.asarray(uniq)
        merged = self._sketches[doc_ids] | upd
        self._sketches = self._sketches.at[doc_ids].set(merged)
        self._fills = self._fills.at[doc_ids].set(pk.row_popcount(merged))

    def merge(self, other: "SketchStore") -> "SketchStore":
        """OR-merge two stores row-aligned (sketch of per-row unions).

        Shard-local ingestion: each shard sketches its slice of every doc
        independently; the merged store equals sketching the union directly
        (the OR-homomorphism). Sizes may differ — the shorter store's missing
        rows are treated as empty sets.
        """
        n = max(self.size, other.size)
        self._ensure_capacity(n)
        merged = self._sketches.at[: other.size].set(
            self._sketches[: other.size] | other.sketches
        )
        self._sketches = merged
        self.size = n
        touched = merged[:n]
        self._fills = self._fills.at[:n].set(pk.row_popcount(touched))
        return self
