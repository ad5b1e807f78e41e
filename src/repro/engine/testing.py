"""Top-k equivalence checking under floating-point tie wobble.

Two scoring paths that consume bit-identical sketches can still disagree
by one f32 ulp on a *transcendental* estimator epilogue (the cardinality
inversion runs ``log`` over block-padded arrays, and XLA's CPU
vectorization picks different lane layouts for different shapes — the
same document scored inside a 3-row head view and inside a 114-row fresh
slab may differ in the last bit). Where two distinct documents land
within that ulp of each other at the top-k boundary, the id tie-break
legitimately resolves differently per path.

``assert_topk_equivalent`` encodes the exact contract the engine does
guarantee: scores agree to tolerance everywhere, ids agree exactly at
every unambiguous slot, and any slot where two paths disagree must be a
*provable score tie* — both ids' materialized ground-truth scores within
tolerance of each other. A wrong id with a coincidentally plausible slot
score cannot pass, because the check is against the reference engine's
own full score row, not the returned value.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

__all__ = ["assert_topk_equivalent", "score_ids", "topk_truth"]


def topk_truth(engine, query_idx, id_map=None) -> List[Dict[int, float]]:
    """Per-query ``{global doc id: exact score}`` from the materialized path.

    ``score_all`` columns follow ascending live-id order on a segmented
    store and row index == id on an append-only one; ``id_map`` remaps
    positional ids (e.g. a fresh rebuild's row numbers) to global ids.
    """
    s = np.asarray(engine.score_all(query_idx))
    store = engine.store
    ids = np.asarray(getattr(store, "live_ids", np.arange(store.size)))
    if id_map is not None:
        ids = np.asarray(id_map)[ids]
    return [
        {int(g): float(s[r, j]) for j, g in enumerate(ids)}
        for r in range(s.shape[0])
    ]


def score_ids(engine, query_idx, ids) -> List[Dict[int, float]]:
    """Per-query ``{global doc id: score}`` for just the docs in ``ids``
    ((Q, m), -1 skipped), scored by ``engine``'s backend at each doc's own
    sketch width — the :func:`topk_truth` of a corpus too large for
    ``score_all``'s (Q, C) matrix. Pass the union of two results' ids for
    :func:`assert_topk_equivalent`'s tie check."""
    import jax.numpy as jnp

    from .segments import _HEAD, SegmentedStore

    store, be = engine.store, engine.backend
    base = engine.cfg.n_bins
    qs = be.sketch(engine.cfg, store.mapping, jnp.asarray(query_idx))
    out: List[Dict[int, float]] = []
    for r, row in enumerate(np.asarray(ids)):
        scores: Dict[int, float] = {}
        for g in sorted({int(x) for x in row if x >= 0}):
            if not isinstance(store, SegmentedStore):
                doc, nb = store.sketches[g : g + 1], base
            else:
                seg_i, pos = store._locate(g)
                seg = store.head if seg_i == _HEAD else store.sealed[seg_i]
                doc = (seg.packed if seg_i == _HEAD else seg.sketches)[pos : pos + 1]
                nb = getattr(seg, "n_bins", None) or base
            q = qs[r : r + 1] if nb == base else be.rebucket(qs[r : r + 1], base, nb)
            scores[g] = float(be.score(q, doc, nb, engine.measure)[0, 0])
        out.append(scores)
    return out


def assert_topk_equivalent(
    got, want, truth: Optional[List[Dict[int, float]]] = None,
    rtol: float = 1e-5, atol: float = 1e-6, err_msg: str = "",
) -> None:
    """``got``/``want``: (scores (Q, k), ids (Q, k)) pairs to compare.

    Scores must be allclose slot-for-slot; ids must be equal except at
    slots whose two ids are score-tied within tolerance in ``truth`` (the
    reference's materialized per-query score maps — see :func:`topk_truth`).
    With ``truth=None`` any id mismatch fails (use for paths expected to
    be bit-identical).
    """
    sc_g, id_g = np.asarray(got[0]), np.asarray(got[1])
    sc_w, id_w = np.asarray(want[0]), np.asarray(want[1])
    np.testing.assert_allclose(sc_g, sc_w, rtol=rtol, atol=atol,
                               err_msg=err_msg)
    if (id_g == id_w).all():
        return
    if truth is None:
        np.testing.assert_array_equal(id_g, id_w, err_msg=err_msg)  # fails
    for r, c in zip(*np.nonzero(id_g != id_w)):
        g, w = int(id_g[r, c]), int(id_w[r, c])
        assert g in truth[r] and w in truth[r], (
            f"{err_msg}: row {r} slot {c}: id {g if g not in truth[r] else w} "
            "is not a live document"
        )
        tg, tw = truth[r][g], truth[r][w]
        assert abs(tg - tw) <= atol + rtol * abs(tw), (
            f"{err_msg}: row {r} slot {c}: ids {g} ({tg}) vs {w} ({tw}) "
            "differ but are not score-tied"
        )
