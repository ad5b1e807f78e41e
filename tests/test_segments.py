"""Mutable corpus lifecycle (engine/segments.py + core/counting.py):
counting-sketch construction, delete/update/retract semantics, seal and
compaction invariants, TTL expiry, checkpoint snapshot/restore, and
query-identity with a fresh batch build after arbitrary mutation."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    BinSketchConfig,
    counting,
    make_mapping,
    packed,
    sketch_indices,
)
from repro.data.synthetic import DATASETS, generate_corpus
from repro.engine import SegmentedStore, SketchEngine, SketchStore, get_backend

from conftest import corpus as _fixture

SPEC = DATASETS["tiny"]


def _pad_rows(rows, pad=96):
    out = np.full((len(rows), pad), -1, np.int32)
    for i, r in enumerate(rows):
        u = np.unique(np.asarray(sorted(r), np.int32))
        out[i, : len(u)] = u
    return jnp.asarray(out)


# ----------------------------------------------------------- counting core
def test_counting_backend_parity_and_pack():
    """Pallas compare-reduce occupancy == oracle scatter-add, both mapping
    modes; ``counters > 0`` packs to exactly the binary sketch."""
    for mode in ("table", "hash"):
        cfg = BinSketchConfig(d=SPEC.d, n_bins=300, mode=mode)
        mapping = make_mapping(cfg, jax.random.PRNGKey(1))
        _, _, idx = _fixture()
        rows = jnp.asarray(idx[:16])
        co = get_backend("oracle").count(cfg, mapping, rows)
        cp = get_backend("pallas-interpret").count(cfg, mapping, rows)
        np.testing.assert_array_equal(np.asarray(co), np.asarray(cp))
        np.testing.assert_array_equal(
            np.asarray(counting.counters_to_packed(co)),
            np.asarray(sketch_indices(cfg, mapping, rows)),
        )
        np.testing.assert_array_equal(
            np.asarray(counting.counter_fills(co)),
            np.asarray(packed.row_popcount(sketch_indices(cfg, mapping, rows))),
        )


def test_counting_multiplicity():
    """Two elements in one bin -> count 2; retracting one keeps the bin set,
    retracting both clears it (the mutability the OR-sketch cannot give)."""
    cfg = BinSketchConfig(d=8, n_bins=4)
    # craft a mapping where ids 0 and 1 share bin 2, id 2 sits alone in bin 0
    mapping = jnp.asarray([2, 2, 0, 1, 1, 3, 3, 0], jnp.int32)
    counts = counting.count_indices_dense(
        cfg, mapping, jnp.asarray([[0, 1, 2, -1]], jnp.int32)
    )
    np.testing.assert_array_equal(np.asarray(counts), [[1, 0, 2, 0]])
    store = SegmentedStore.create(cfg, mapping, capacity=2)
    store.add(jnp.asarray([[0, 1, 2, -1]], jnp.int32))
    store.retract_rows([0], jnp.asarray([[1, -1, -1, -1]], jnp.int32))
    # bin 2 still set: element 0 remains
    np.testing.assert_array_equal(
        np.asarray(packed.unpack_bits(store.sketches, 4)), [[1, 0, 1, 0]]
    )
    store.retract_rows([0], jnp.asarray([[0, -1, -1, -1]], jnp.int32))
    np.testing.assert_array_equal(
        np.asarray(packed.unpack_bits(store.sketches, 4)), [[1, 0, 0, 0]]
    )


def test_retract_matches_shrunken_sketch():
    cfg, mapping, idx = _fixture()
    store = SegmentedStore.from_indices(cfg, mapping, jnp.asarray(idx[:4]))
    row = idx[2][idx[2] >= 0]
    drop, keep = row[: len(row) // 2], row[len(row) // 2 :]
    store.retract_rows([2], _pad_rows([drop], pad=idx.shape[1]))
    want = sketch_indices(cfg, mapping, _pad_rows([keep], pad=idx.shape[1]))[0]
    got = store.sketches[2]  # live() is id-ordered; ids 0..3 intact
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_retract_after_merge_raises():
    """merge_rows may double-count elements already present (the overlap is
    unknowable from sketches), so a merged row loses its exact mark and
    retraction is refused rather than silently wrong."""
    cfg = BinSketchConfig(d=8, n_bins=4)
    mapping = jnp.asarray([2, 2, 0, 1, 1, 3, 3, 0], jnp.int32)
    store = SegmentedStore.create(cfg, mapping, capacity=2)
    store.add(jnp.asarray([[0, -1, -1, -1]], jnp.int32))
    store.merge_rows([0], jnp.asarray([[0, -1, -1, -1]], jnp.int32))  # overlap
    with pytest.raises(ValueError, match="exact head row"):
        store.retract_rows([0], jnp.asarray([[0, -1, -1, -1]], jnp.int32))
    store.update([0], jnp.asarray([[0, 3, -1, -1]], jnp.int32))  # restores exactness
    store.retract_rows([0], jnp.asarray([[3, -1, -1, -1]], jnp.int32))
    np.testing.assert_array_equal(
        np.asarray(packed.unpack_bits(store.sketches, 4)), [[0, 0, 1, 0]]
    )


def test_retract_sealed_raises():
    cfg, mapping, idx = _fixture()
    store = SegmentedStore.from_indices(cfg, mapping, jnp.asarray(idx[:4]))
    store.seal()
    with pytest.raises(ValueError, match="exact head row"):
        store.retract_rows([2], jnp.asarray(idx[2:3]))


def test_duplicate_indices_insert_retract_roundtrip():
    """Rows are sets: duplicate indices in a padded row are collapsed at
    every counting entry point, so insert->retract round-trips on
    non-deduplicated rows leave neither phantom occupancy nor a wrong
    binary sketch (the multiplicity-corruption bug)."""
    cfg = BinSketchConfig(d=8, n_bins=4)
    mapping = jnp.asarray([2, 2, 0, 1, 1, 3, 3, 0], jnp.int32)
    store = SegmentedStore.create(cfg, mapping, capacity=2)
    store.add(jnp.asarray([[0, 0, 0, 1, -1]], jnp.int32))  # {0, 1}, 0 thrice
    # occupancy counts *distinct* elements: ids 0 and 1 share bin 2 -> 2
    np.testing.assert_array_equal(np.asarray(store.head.counters[0]),
                                  [0, 0, 2, 0])
    store.retract_rows([0], jnp.asarray([[0, -1, -1, -1, -1]], jnp.int32))
    np.testing.assert_array_equal(
        np.asarray(packed.unpack_bits(store.sketches, 4)), [[0, 0, 1, 0]]
    )
    # duplicated retraction row decrements once, clearing the bin exactly
    store.retract_rows([0], jnp.asarray([[1, 1, -1, -1, -1]], jnp.int32))
    np.testing.assert_array_equal(
        np.asarray(packed.unpack_bits(store.sketches, 4)), [[0, 0, 0, 0]]
    )
    assert np.asarray(store.head.counters[0]).sum() == 0  # no phantom counts


def test_saturated_counters_refuse_retraction(monkeypatch, tmp_path):
    """Once a bin clamps at COUNTER_MAX the true occupancy is gone, so a
    decrement would silently under-count — retraction is refused on the
    saturated row (and the flag survives a checkpoint), while update()
    restores exactness."""
    from repro.checkpoint.manager import CheckpointManager

    monkeypatch.setattr(counting, "COUNTER_MAX", 3)
    cfg = BinSketchConfig(d=8, n_bins=4)
    all_bin0 = jnp.zeros(8, jnp.int32)  # every element maps to bin 0
    store = SegmentedStore.create(cfg, all_bin0, capacity=2)
    store.add(jnp.asarray([[0, 1, 2, 3, 4, -1]], jnp.int32))  # occupancy 5 > 3
    store.add(jnp.asarray([[5, 6, -1, -1, -1, -1]], jnp.int32))  # occupancy 2
    assert store.head.saturated[0] and not store.head.saturated[1]
    with pytest.raises(ValueError, match="saturated"):
        store.retract_rows([0], jnp.asarray([[0, -1, -1, -1, -1, -1]], jnp.int32))
    # the healthy row still retracts fine
    store.retract_rows([1], jnp.asarray([[5, -1, -1, -1, -1, -1]], jnp.int32))
    # merge_rows pushing a row over the clamp marks it too (sticky)
    store.merge_rows([1], jnp.asarray([[0, 1, 2, 7, -1, -1]], jnp.int32))
    assert store.head.saturated[1]
    # the flag rides the checkpoint: a restored store still refuses
    mgr = CheckpointManager(str(tmp_path))
    store.save(mgr, step=1)
    back = SegmentedStore.restore(mgr)
    with pytest.raises(ValueError, match="saturated"):
        back.retract_rows([0], jnp.asarray([[0, -1, -1, -1, -1, -1]], jnp.int32))
    # overwrite re-counts from scratch below the clamp: exact again
    back.update([0], jnp.asarray([[0, 1, -1, -1, -1, -1]], jnp.int32))
    assert not back.head.saturated[list(back.head.ids[: back.head.size]).index(0)]
    back.retract_rows([0], jnp.asarray([[0, -1, -1, -1, -1, -1]], jnp.int32))


# ----------------------------------------------------- store surface parity
def test_segmented_add_matches_sketchstore():
    """Same ``add`` surface: the counting head's packed view and fill cache
    are bit-for-bit the append-only store's, across capacity doublings."""
    cfg, mapping, idx = _fixture()
    plain = SketchStore.from_indices(cfg, mapping, jnp.asarray(idx[:100]))
    seg = SegmentedStore.create(cfg, mapping, capacity=4)
    for lo, hi in [(0, 3), (3, 40), (40, 41), (41, 100)]:
        seg.add(jnp.asarray(idx[lo:hi]))
    assert seg.size == plain.size == 100
    np.testing.assert_array_equal(np.asarray(seg.sketches), np.asarray(plain.sketches))
    np.testing.assert_array_equal(np.asarray(seg.fills), np.asarray(plain.fills))


def test_head_capacity_capped_at_seal_rows():
    """An auto-sealing head is allocated at ``seal_rows`` rows however large
    the requested capacity (its u16 counters cost 2·N bytes per row), keeps
    that size across seals, and the store answers exactly like a fresh
    build over the same docs."""
    cfg, mapping, idx = _fixture()
    engine = SketchEngine.build(cfg, mapping, backend="oracle", mutable=True,
                                capacity=100_000, seal_rows=16)
    head = engine.store.head
    assert head.capacity == 16 and head.counters.shape == (16, cfg.n_bins)
    for lo in range(0, 60, 8):
        engine.add(jnp.asarray(idx[lo : min(lo + 8, 60)]))
    assert len(engine.store.sealed) == 3 and engine.store.head.size == 12
    assert engine.store.head.capacity == 16
    _shadow_equal(engine, {i: idx[i] for i in range(60)},
                  backends=("oracle", "pallas-interpret"))


# -------------------------------------------------- the head's append path
def _head_arrays(head):
    return [np.asarray(a) for a in (head.counters, head.packed, head.fills,
                                    head.sat_dev)]


def _random_counts(rng, b, n_bins, clamp):
    counts = rng.integers(0, 3, (b, n_bins)).astype(np.int32)
    if clamp:  # some rows hold a bin above COUNTER_MAX: the clamp flag is set
        counts[rng.random(b) < 0.5, rng.integers(0, n_bins)] = (
            counting.COUNTER_MAX + 7)
    return jnp.asarray(counts)


@pytest.mark.parametrize("capacity,batches,clamp", [
    (64, [8, 8, 16, 4], True),  # several offsets, clamped rows among them
    (64, [5, 11, 1, 30], False),  # unaligned offsets
    (8, [5, 6, 20], True),  # appends that grow the capacity, twice
    (16, [8, 8], False),  # fills the head to capacity exactly
])
def test_head_append_matches_scatter_path(capacity, batches, clamp):
    """The in-place append writes bit-for-bit what the scatter path
    (``set_counts`` on the same rows) writes: counters, packed rows, fills
    and clamp flags, at every offset and across capacity growth."""
    from repro.engine.segments import _Head

    n_bins = 300  # packs to 10 words, the last one partial
    rng = np.random.default_rng(sum(batches) + capacity)
    fast = _Head.create(n_bins, packed.num_words(n_bins), capacity)
    slow = _Head.create(n_bins, packed.num_words(n_bins), capacity)
    for b in batches:
        counts = _random_counts(rng, b, n_bins, clamp)
        rows = fast.append(counts)
        assert rows == range(slow.size, slow.size + b)
        slow.ensure_capacity(slow.size + b)
        slow.set_counts(np.arange(rows.start, rows.stop), counts)
        fast.size = slow.size = rows.stop
        assert fast.capacity == slow.capacity
        for got, want in zip(_head_arrays(fast), _head_arrays(slow)):
            np.testing.assert_array_equal(got, want)
    assert fast.size == sum(batches)
    assert fast.saturated.any() == clamp


def test_head_append_fills_to_seal_rows_like_a_fresh_build():
    """Appends that fill the head to ``seal_rows`` exactly seal the very
    rows, bit for bit, that the append-only store builds."""
    cfg, mapping, idx = _fixture()
    plain = SketchStore.from_indices(cfg, mapping, jnp.asarray(idx[:32]))
    store = SegmentedStore.create(cfg, mapping, capacity=16, seal_rows=16)
    for lo in range(0, 32, 8):
        store.add(jnp.asarray(idx[lo : lo + 8]))
    assert len(store.sealed) == 2 and store.head.size == 0
    for seg, lo in zip(store.sealed, (0, 16)):
        np.testing.assert_array_equal(np.asarray(seg.sketches),
                                      np.asarray(plain.sketches[lo : lo + 16]))
        np.testing.assert_array_equal(np.asarray(seg.fills),
                                      np.asarray(plain.fills[lo : lo + 16]))


def test_head_append_compiles_once_across_offsets():
    """``lo`` is traced: appends of one shape at new offsets reuse the one
    compiled program."""
    from repro.engine import segments

    n_bins = 200
    head = segments._Head.create(n_bins, packed.num_words(n_bins), 40)
    counts = jnp.ones((4, n_bins), jnp.int32)
    head.append(counts)
    head.size += 4
    compiled = segments._append_rows._cache_size()
    for _ in range(9):
        head.append(counts)
        head.size += 4
    assert segments._append_rows._cache_size() == compiled
    assert np.asarray(head.fills[:40]).tolist() == [n_bins] * 40


def _snapshot(store):
    """Host copies of every device array a head view, ``_parts()`` and a
    checkpoint tree hold, with the live device arrays themselves."""
    tree, _ = store.checkpoint_tree()
    hv = store.head_view()
    held = [hv.sketches, hv.fills, *store._parts()[-1][:2],
            *(tree["head"][k] for k in ("counters", "packed", "fills",
                                        "saturated"))]
    return held, [np.asarray(a) for a in held]


@pytest.mark.parametrize("capacity,first,then,seal_rows", [
    (8, 8, [4, 4], None),  # size == capacity: the views hold the buffers
    (16, 8, [4, 2], None),  # size < capacity: the views are copies
    (16, 8, [8, 4], 16),  # the next append reaches seal_rows and seals
])
def test_head_views_survive_later_appends(capacity, first, then, seal_rows):
    """A head view, ``_parts()`` and a checkpoint tree taken before further
    appends stay readable and unchanged after them, though the append
    donates the head's buffers."""
    cfg, mapping, idx = _fixture()
    store = SegmentedStore.create(cfg, mapping, capacity=capacity,
                                  seal_rows=seal_rows)
    store.add(jnp.asarray(idx[:first]))
    assert (store.head.size == store.head.capacity) == (first == capacity)
    held, want = _snapshot(store)
    own = store.head.packed  # the buffer itself, not a slice: donated
    lo = first
    for b in then:
        store.add(jnp.asarray(idx[lo : lo + b]))
        lo += b
    for arr, host in zip(held, want):
        assert not arr.is_deleted()
        np.testing.assert_array_equal(np.asarray(arr), host)
    if first < capacity:  # proves the donation happened, so this test bites
        assert own.is_deleted()
    np.testing.assert_array_equal(
        np.asarray(store.sketches),
        np.asarray(SketchStore.from_indices(cfg, mapping,
                                            jnp.asarray(idx[:lo])).sketches))


def test_head_write_counters():
    """``store.head.rows_appended`` counts the rows each add / add_sketches
    / relocation appends; ``store.head.rows_rewritten`` the rows update,
    retract and delete rewrite in place."""
    from repro.obs import metrics as obs_metrics

    cfg, mapping, idx = _fixture()
    rows = obs_metrics.MetricsRegistry()
    with obs_metrics.scoped(rows):
        store = SegmentedStore.create(cfg, mapping, capacity=8)
        store.add(jnp.asarray(idx[:12]), batch=5)  # batches of 5, 5, 2
        assert rows.counter("store.head.rows_appended") == 12
        store.add_sketches(
            sketch_indices(cfg, mapping, jnp.asarray(idx[12:15])))
        assert rows.counter("store.head.rows_appended") == 15
        assert rows.counter("store.head.rows_rewritten") == 0
        store.update([1, 2], jnp.asarray(idx[20:22]))  # head rows: rewritten
        assert rows.counter("store.head.rows_rewritten") == 2
        store.retract_rows([3], jnp.asarray(idx[3:4]))
        assert rows.counter("store.head.rows_rewritten") == 3
        store.delete([4, 5, 6])
        assert rows.counter("store.head.rows_rewritten") == 6
        store.seal()
        store.update([7], jnp.asarray(idx[23:24]))  # sealed: relocates
        store.delete([8])  # sealed: a tombstone, no row write
        assert rows.counter("store.head.rows_appended") == 16
        assert rows.counter("store.head.rows_rewritten") == 6


def test_add_sketches_and_merge_by_id():
    cfg, mapping, idx = _fixture()
    base = SketchStore.from_indices(cfg, mapping, jnp.asarray(idx[:8]))
    seg = SegmentedStore.create(cfg, mapping)
    seg.add_sketches(base.sketches)
    np.testing.assert_array_equal(np.asarray(seg.sketches), np.asarray(base.sketches))
    # merge another segmented store: shared ids OR, fresh ids append
    other = SegmentedStore.from_indices(cfg, mapping, jnp.asarray(idx[8:12]))
    seg.merge(other)  # ids 0..3 of `other` OR into ours
    assert seg.size == 8 and seg.next_id == 8
    want_or = np.asarray(base.sketches[:4]) | np.asarray(
        sketch_indices(cfg, mapping, jnp.asarray(idx[8:12]))
    )
    np.testing.assert_array_equal(np.asarray(seg.sketches[:4]), want_or)


# ------------------------------------------------------------ lifecycle ops
def _shadow_equal(engine, contents, backends=("oracle",), measures=("jaccard",),
                  k=5, n_queries=6, seed=11):
    """Engine results == fresh batch build over the shadow catalog, exactly
    (ids) and numerically (scores), for every backend x measure asked."""
    cfg, mapping = engine.cfg, engine.store.mapping
    surv = np.asarray(sorted(contents))
    rng = np.random.default_rng(seed)
    qsets = [rng.choice(SPEC.d, rng.integers(1, 40), replace=False)
             for _ in range(n_queries)]
    if len(surv):  # include a live doc's exact content: guarantees ties/hits
        row = contents[int(surv[0])]
        qsets.append(row[row >= 0])
    q = _pad_rows(qsets, pad=SPEC.max_nnz)
    for backend in backends:
        be = get_backend(backend)
        seg_eng = SketchEngine(engine.store, be, "jaccard")
        if len(surv):
            fresh_rows = jnp.asarray(np.stack([contents[int(g)] for g in surv]))
            fresh_store = SketchStore.from_indices(cfg, mapping, fresh_rows, backend=be)
        else:
            fresh_store = SketchStore.create(cfg, mapping)
        for measure in measures:
            seg_eng.measure = measure
            fresh_eng = SketchEngine(fresh_store, be, measure)
            sc_m, id_m = seg_eng.query(q, k)
            sc_f, id_f = fresh_eng.query(q, k)
            id_f = np.where(np.asarray(id_f) >= 0,
                            surv[np.maximum(np.asarray(id_f), 0)] if len(surv) else -1,
                            -1)
            np.testing.assert_array_equal(
                np.asarray(id_m), id_f, err_msg=f"{backend}/{measure}"
            )
            np.testing.assert_allclose(
                np.asarray(sc_m), np.asarray(sc_f), rtol=1e-5, atol=1e-6,
                err_msg=f"{backend}/{measure}",
            )


def test_delete_update_seal_compact_query_identical():
    """The acceptance sequence: ingest -> delete -> update (head + sealed) ->
    seal -> compact answers exactly like a fresh build over survivors, on
    oracle and pallas-interpret, all four measures."""
    cfg, mapping, idx = _fixture()
    engine = SketchEngine.build(cfg, mapping, jnp.asarray(idx[:60]),
                                backend="oracle", mutable=True)
    contents = {i: idx[i] for i in range(60)}
    engine.seal()
    engine.add(jnp.asarray(idx[60:80]))
    contents.update({i: idx[i] for i in range(60, 80)})
    engine.delete([0, 13, 59, 71])
    for g in (0, 13, 59, 71):
        contents.pop(g)
    # update: id 5 is sealed (relocates into the head, breaking the naive
    # id order), id 75 is head-resident (in-place counter overwrite)
    engine.update([5, 75], jnp.asarray(idx[200:202]))
    contents[5], contents[75] = idx[200], idx[201]
    _shadow_equal(engine, contents,
                  backends=("oracle", "pallas-interpret"),
                  measures=("jaccard", "ip", "cosine", "hamming"))
    engine.seal()
    _shadow_equal(engine, contents)
    stats = engine.compact()
    assert stats["rows_out"] == len(contents)
    assert len(engine.store.sealed) == 1
    _shadow_equal(engine, contents,
                  backends=("oracle", "pallas-interpret"),
                  measures=("jaccard", "ip", "cosine", "hamming"))


def test_random_interleavings_query_identical():
    """Seeded random op soup (insert/delete/update/seal/compact) — the
    tier-1 twin of the hypothesis property test in test_properties.py."""
    cfg, mapping, idx = _fixture()
    for seed in range(3):
        rng = np.random.default_rng(seed)
        store = SegmentedStore.create(cfg, mapping, capacity=8)
        engine = SketchEngine(store, get_backend("oracle"))
        contents = {}
        cursor = 0
        for _ in range(rng.integers(8, 14)):
            live = sorted(contents)
            op = rng.choice(["insert", "delete", "update", "seal", "compact"])
            if op == "insert" or not live:
                b = int(rng.integers(1, 6))
                rows = idx[cursor : cursor + b]
                ids = engine.add(jnp.asarray(rows))
                contents.update({int(g): rows[j] for j, g in enumerate(ids)})
                cursor += b
            elif op == "delete":
                g = int(rng.choice(live))
                engine.delete([g])
                contents.pop(g)
            elif op == "update":
                g = int(rng.choice(live))
                row = idx[cursor]
                cursor += 1
                engine.update([g], jnp.asarray(row[None]))
                contents[g] = row
            elif op == "seal":
                engine.seal()
            else:
                engine.compact()
        _shadow_equal(engine, contents, seed=seed + 100)
        assert engine.store.size == len(contents)


def test_empty_after_total_deletion():
    cfg, mapping, idx = _fixture()
    engine = SketchEngine.build(cfg, mapping, jnp.asarray(idx[:10]),
                                backend="oracle", mutable=True)
    engine.seal()
    engine.delete(list(range(10)))
    assert engine.store.size == 0
    sc, ids = engine.query(jnp.asarray(idx[:3]), k=4)
    assert (np.asarray(ids) == -1).all() and np.isneginf(np.asarray(sc)).all()
    stats = engine.compact()
    assert stats["rows_out"] == 0 and engine.store.sealed == []
    # ids are never reused after compaction dropped everything
    new_ids = engine.add(jnp.asarray(idx[10:12]))
    assert list(new_ids) == [10, 11]


def test_delete_unknown_id_raises():
    cfg, mapping, idx = _fixture()
    store = SegmentedStore.from_indices(cfg, mapping, jnp.asarray(idx[:4]))
    with pytest.raises(KeyError):
        store.delete([99])
    # batch with a bad id is atomic: the valid ids stay live, counts intact
    with pytest.raises(KeyError):
        store.delete([1, 99])
    assert store.size == 4 and sorted(store.live_ids.tolist()) == [0, 1, 2, 3]
    store.delete([2])
    with pytest.raises(KeyError):  # double delete
        store.delete([2])
    assert store.size == 3


def test_ttl_expiry():
    cfg, mapping, idx = _fixture()
    store = SegmentedStore.create(cfg, mapping)
    store.add(jnp.asarray(idx[:4]), now=0.0)
    store.seal()
    store.add(jnp.asarray(idx[4:8]), now=10.0)
    assert store.expire(ttl=5.0, now=11.0) == 4  # the sealed batch aged out
    assert store.size == 4
    assert sorted(store.live_ids.tolist()) == [4, 5, 6, 7]
    assert store.expire(ttl=5.0, now=11.0) == 0  # idempotent
    store.compact()
    assert store.sealed == []  # the fully-tombstoned sealed batch is gone


def test_lazy_ttl_expiry_before_sweep():
    """With a store-level ttl, a doc older than ttl at query time never
    appears in top-k — even though nobody has called expire() — across the
    head, sealed segments, and the sharded path; the eager sweep then
    changes nothing about query results."""
    cfg, mapping, idx = _fixture()
    store = SegmentedStore.create(cfg, mapping, ttl=5.0)
    engine = SketchEngine(store, get_backend("oracle"))
    engine.add(jnp.asarray(idx[:4]), now=0.0)   # sealed, old
    engine.seal()
    engine.add(jnp.asarray(idx[4:6]), now=0.0)  # head, old
    engine.add(jnp.asarray(idx[6:10]), now=10.0)  # head, fresh
    q = jnp.asarray(idx[:10])

    # no `now`: the clock is off, everything retrievable (k covers all)
    _, ids_all = engine.query(q, 10)
    assert set(np.asarray(ids_all).ravel().tolist()) == set(range(10))

    # now=11: docs born at 0 have aged out (0 + 5 <= 11) — masked lazily
    sc, ids = engine.query(q, 10, now=11.0)
    got = set(np.asarray(ids).ravel().tolist()) - {-1}
    assert got == {6, 7, 8, 9}, got
    assert store.size == 10  # still live bookkeeping-wise: no sweep ran

    # the sharded path applies the same mask (k covers every live doc, so
    # per-row id *sets* are shape-wobble-proof; scores stay allclose)
    mesh = jax.make_mesh((1,), ("data",))
    sc_s, ids_s = engine.query_sharded(mesh, "data", q, 10, now=11.0)
    np.testing.assert_allclose(np.sort(np.asarray(sc), axis=1),
                               np.sort(np.asarray(sc_s), axis=1),
                               rtol=1e-5, atol=1e-6)
    for r in range(np.asarray(ids).shape[0]):
        assert set(np.asarray(ids)[r].tolist()) == set(np.asarray(ids_s)[r].tolist())

    # the eager sweep reclaims space but cannot change what queries see
    assert engine.expire(ttl=5.0, now=11.0) == 6
    sc2, ids2 = engine.query(q, 10, now=11.0)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(ids2))
    np.testing.assert_allclose(np.asarray(sc), np.asarray(sc2),
                               rtol=1e-5, atol=1e-6)
    assert store.size == 4


def test_ttl_survives_checkpoint(tmp_path):
    from repro.checkpoint.manager import CheckpointManager

    cfg, mapping, idx = _fixture()
    store = SegmentedStore.create(cfg, mapping, ttl=7.5)
    store.add(jnp.asarray(idx[:4]), now=1.0)
    mgr = CheckpointManager(str(tmp_path))
    store.save(mgr, step=2)
    back = SegmentedStore.restore(mgr)
    assert back.ttl == 7.5
    engine = SketchEngine(back, get_backend("oracle"))
    _, ids = engine.query(jnp.asarray(idx[:2]), 4, now=9.0)  # 1 + 7.5 <= 9
    assert (np.asarray(ids) == -1).all()


def test_merge_rows_preserves_born():
    """A merge grows a doc, it doesn't re-create it: relocating a sealed doc
    into the head via merge_rows keeps the original birth time, so TTL
    expiry is unaffected by the merge."""
    cfg, mapping, idx = _fixture()
    store = SegmentedStore.create(cfg, mapping)
    store.add(jnp.asarray(idx[:3]), now=100.0)
    store.seal()
    store.merge_rows([1], jnp.asarray(idx[5:6]))
    row = list(store.head.ids[: store.head.size]).index(1)
    assert store.head.born[row] == 100.0
    # age 51 > ttl 50 for all three — had the merge re-stamped born=200,
    # the merged doc would survive this expiry and break the count
    assert store.expire(ttl=50.0, now=151.0) == 3


def test_compaction_reclaims_tombstones():
    cfg, mapping, idx = _fixture()
    store = SegmentedStore.from_indices(cfg, mapping, jnp.asarray(idx[:30]))
    store.seal()
    store.add(jnp.asarray(idx[30:40]))
    store.seal()
    store.delete(list(range(0, 30, 2)))
    stats = store.compact()
    assert stats["segments_in"] == 2
    assert stats["rows_in"] == 40 and stats["rows_out"] == 25
    assert len(store.sealed) == 1
    seg = store.sealed[0]
    assert seg.valid.all() and list(seg.ids) == sorted(seg.ids.tolist())


# ------------------------------------------------------------- checkpoint
def test_checkpoint_roundtrip(tmp_path):
    from repro.checkpoint.manager import CheckpointManager

    cfg, mapping, idx = _fixture()
    store = SegmentedStore.from_indices(cfg, mapping, jnp.asarray(idx[:40]))
    store.delete([3, 9])
    store.seal()
    store.add(jnp.asarray(idx[40:50]))
    store.update([7], jnp.asarray(idx[100:101]))  # sealed relocation in head
    contents = {i: idx[i] for i in range(50) if i not in (3, 9)}
    contents[7] = idx[100]

    mgr = CheckpointManager(str(tmp_path))
    store.save(mgr, step=5)
    back = SegmentedStore.restore(mgr)
    assert back.size == store.size and back.next_id == store.next_id
    np.testing.assert_array_equal(back.live_ids, store.live_ids)
    np.testing.assert_array_equal(np.asarray(back.sketches), np.asarray(store.sketches))
    engine = SketchEngine(back, get_backend("oracle"))
    _shadow_equal(engine, contents)
    # the restored store is still mutable: counters survived the roundtrip
    row = idx[45][idx[45] >= 0]
    back.retract_rows([45], _pad_rows([row[:5]], pad=idx.shape[1]))
    want = sketch_indices(cfg, mapping, _pad_rows([row[5:]], pad=idx.shape[1]))[0]
    got_row = np.asarray(back.sketches)[list(back.live_ids).index(45)]
    np.testing.assert_array_equal(got_row, np.asarray(want))


def test_checkpoint_load_aux_rejects_foreign(tmp_path):
    from repro.checkpoint.manager import CheckpointManager

    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, {"x": np.arange(3)}, aux={"kind": "other"})
    assert mgr.load_aux()["kind"] == "other"
    with pytest.raises(ValueError, match="not a SegmentedStore"):
        SegmentedStore.restore(mgr)


# ----------------------------------------------------------------- sharded
def test_query_sharded_segmented(multidevice):
    """Sharded retrieval over a mutated, multi-segment store matches the
    single-device path (tombstones masked, global ids preserved)."""
    out = multidevice(
        """
import numpy as np, jax, jax.numpy as jnp
from repro.core import BinSketchConfig, make_mapping
from repro.engine import SketchEngine
from repro.data.synthetic import DATASETS, generate_corpus

spec = DATASETS["tiny"]
idx, lens = generate_corpus(spec, seed=0)
cfg = BinSketchConfig.from_sparsity(spec.d, int(lens.max()), rho=0.05)
mapping = make_mapping(cfg, jax.random.PRNGKey(0))
engine = SketchEngine.build(cfg, mapping, jnp.asarray(idx[:29]), backend="oracle",
                            mutable=True)
engine.seal()
engine.add(jnp.asarray(idx[29:40]))
engine.delete([2, 35])
engine.update([4], jnp.asarray(idx[100:101]))

mesh = jax.make_mesh((8,), ("data",))
q = jnp.asarray(idx[5:13])
sc1, ids1 = engine.query(q, k=4)
sc8, ids8 = engine.query_sharded(mesh, "data", q, k=4)
np.testing.assert_array_equal(np.asarray(ids1), np.asarray(ids8))
np.testing.assert_allclose(np.asarray(sc1), np.asarray(sc8), rtol=1e-5, atol=1e-6)
print("SEGMENTED_SHARDED_OK")
""",
        8,
    )
    assert "SEGMENTED_SHARDED_OK" in out
