"""Banded LSH prefilter (engine/banding.py + kernels/band_hash.py,
DESIGN.md §12): band-hash parity across numpy / jnp / Pallas, BandIndex
bucket semantics, prefiltered-query subset-with-identical-scores and
escape-hatch exactness, lifecycle safety (tombstones never resurrect
through stale buckets across seal -> delete -> compact -> distill), the
auto topk crossover, and single-device / placed / sliced agreement."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import BinSketchConfig, make_mapping, packed as pk
from repro.data.synthetic import DATASETS, generate_corpus
from repro.engine import (
    BandIndex,
    BandPolicy,
    QueryPlanner,
    SegmentedStore,
    SketchEngine,
    get_backend,
)

SPEC = DATASETS["tiny"]


def _fixture(seed=0, rho=0.05):
    idx, lens = generate_corpus(SPEC, seed=seed)
    cfg = BinSketchConfig.from_sparsity(SPEC.d, int(lens.max()), rho)
    mapping = make_mapping(cfg, jax.random.PRNGKey(0))
    return cfg, mapping, idx


def _clustered(rng, n_docs, cluster, d, nnz):
    """Near-duplicate clusters: one base doc per cluster, one index
    re-rolled per member — the structure that makes bands collide."""
    base = rng.integers(0, d, size=(max(n_docs // cluster, 1), nnz),
                        dtype=np.int32)
    docs = base[np.arange(n_docs) % len(base)].copy()
    docs[np.arange(n_docs), rng.integers(0, nnz, n_docs)] = rng.integers(
        0, d, n_docs
    )
    return np.sort(docs, axis=1)


def _clustered_engine(backend="oracle", n_docs=240, segments=3, cluster=8,
                      policy=None, seed=0):
    rng = np.random.default_rng(seed)
    d, nnz = 2048, 32
    cfg = BinSketchConfig(d=d, n_bins=256)
    mapping = make_mapping(cfg, jax.random.PRNGKey(3))
    pol = policy or BandPolicy(n_bands=8, max_candidate_frac=0.5, min_rows=8)
    eng = SketchEngine.build(cfg, mapping, backend=backend, mutable=True,
                             band_policy=pol,
                             planner=QueryPlanner(min_batch=8, max_batch=16))
    docs = _clustered(rng, n_docs, cluster, d, nnz)
    per = -(-n_docs // segments)
    for s in range(0, n_docs, per):
        eng.add(jnp.asarray(docs[s : s + per]))
        eng.seal()
    # near-duplicate queries of known docs (one index re-rolled)
    pick = rng.choice(n_docs, 12, replace=False)
    q_np = docs[pick].copy()
    q_np[np.arange(len(pick)), rng.integers(0, nnz, len(pick))] = rng.integers(
        0, d, len(pick)
    )
    return eng, docs, np.sort(q_np, axis=1), pick


# ------------------------------------------------------------- band hash
def test_band_hash_three_way_parity():
    """numpy host twin == jnp oracle == Pallas kernel (interpret), over
    shapes that exercise word padding, band clamping, and single rows."""
    rng = np.random.default_rng(0)
    oracle, interp = get_backend("oracle"), get_backend("pallas-interpret")
    for (n, w, nb) in [(5, 14, 4), (3, 1, 8), (7, 32, 32), (9, 13, 5),
                       (1, 7, 3), (2, 64, 3)]:
        x = rng.integers(0, 2**32, (n, w), dtype=np.uint64).astype(np.uint32)
        host = pk.band_hash_host(x, nb)
        dev = np.asarray(oracle.band_hash(jnp.asarray(x), nb))
        pal = np.asarray(interp.band_hash(jnp.asarray(x), nb))
        np.testing.assert_array_equal(host, dev)
        np.testing.assert_array_equal(host, pal)
        assert host.dtype == np.uint32
        assert host.shape == (n, -(-w // -(-w // min(nb, w))))


def test_band_hash_collision_semantics():
    """Rows agreeing on every word of a band share that band's key; a
    single-bit difference in the band flips it (w.h.p.)."""
    rng = np.random.default_rng(1)
    w, nb = 16, 8  # wpb = 2
    a = rng.integers(0, 2**32, (1, w), dtype=np.uint64).astype(np.uint32)
    b = a.copy()
    b[0, 5] ^= np.uint32(1)  # band 2 (words 4-5) differs, others agree
    ka, kb = pk.band_hash_host(a, nb), pk.band_hash_host(b, nb)
    same = ka[0] == kb[0]
    assert not same[2] and same[[0, 1, 3, 4, 5, 6, 7]].all()


# ------------------------------------------------------------- BandIndex
def test_band_index_buckets_match_bruteforce():
    rng = np.random.default_rng(2)
    keys = rng.integers(0, 4, size=(50, 3), dtype=np.uint32)  # dense collisions
    bi = BandIndex.build(keys)
    qk = rng.integers(0, 5, size=(4, 3), dtype=np.uint32)  # incl. missing key 4
    want = np.unique(np.nonzero((keys[None, :, :] == qk[:, None, :]).any(0).any(-1))[0])
    got = bi.candidates(qk)
    np.testing.assert_array_equal(got, want.astype(np.int64))
    assert got.dtype == np.int64 and (np.diff(got) > 0).all()


def test_band_index_qkeys_shape_validated():
    bi = BandIndex.build(np.zeros((4, 3), np.uint32))
    with pytest.raises(ValueError, match="qkeys"):
        bi.candidates(np.zeros((2, 2), np.uint32))


def test_band_policy_validation_and_aux_roundtrip():
    with pytest.raises(ValueError):
        BandPolicy(n_bands=0)
    with pytest.raises(ValueError):
        BandPolicy(max_candidate_frac=0.0)
    pol = BandPolicy(n_bands=6, max_candidate_frac=0.3, min_rows=100)
    assert BandPolicy.from_aux(pol.to_aux()) == pol
    assert BandPolicy.from_aux(None) is None
    assert pol.wants_index(100) and not pol.wants_index(99)


def test_candidate_bucket_shapes():
    p = QueryPlanner()
    assert p.candidate_bucket(0, 0) == 0
    assert p.candidate_bucket(1, 10000) == 64  # floor
    assert p.candidate_bucket(65, 10000) == 128
    assert p.candidate_bucket(5000, 10000) == 8192
    assert p.candidate_bucket(9000, 10000) == 10000  # capped at segment rows
    assert p.candidate_bucket(3, 10) == 10  # floor > cap -> cap


# -------------------------------------------------- prefiltered queries
@pytest.mark.parametrize("backend", ["oracle", "pallas-interpret"])
def test_prefilter_subset_with_identical_scores(backend):
    """Prefiltered results are the exact top-k over a subset of the corpus:
    every returned id scores bit-identically to the exhaustive scan, and
    the planted near-duplicate (which collides on almost every band) is
    always found."""
    eng, docs, q_np, pick = _clustered_engine(backend=backend)
    q = jnp.asarray(q_np)
    s0, i0 = map(np.asarray, eng.query(q, 10, prefilter=False))
    s1, i1 = map(np.asarray, eng.query(q, 10, prefilter=True))
    stats = eng.last_prefilter_stats
    assert stats["banded_segments"] > 0
    assert stats["cand_rows"] < stats["seg_rows"]
    for r in range(len(q_np)):
        exhaustive = {int(i): float(s) for s, i in zip(s0[r], i0[r]) if i >= 0}
        for s, i in zip(s1[r], i1[r]):
            if int(i) in exhaustive:
                assert abs(exhaustive[int(i)] - float(s)) < 1e-6
        assert int(pick[r]) in set(i1[r].tolist())  # near-dup survives


def test_prefilter_escape_hatch_is_exhaustive_exact():
    """A candidate union above max_candidate_frac falls back to the full
    scan — results must be bit-identical to prefilter=False."""
    eng, _, q_np, _ = _clustered_engine(
        policy=BandPolicy(n_bands=8, max_candidate_frac=1e-9, min_rows=8)
    )
    q = jnp.asarray(q_np)
    s0, i0 = map(np.asarray, eng.query(q, 10, prefilter=False))
    s1, i1 = map(np.asarray, eng.query(q, 10, prefilter=True))
    np.testing.assert_array_equal(i0, i1)
    np.testing.assert_allclose(s0, s1, rtol=1e-6)
    assert eng.last_prefilter_stats["exhaustive_segments"] > 0


def test_prefilter_unindexed_below_min_rows_and_head():
    """Segments under min_rows carry no index and scan exhaustively; head
    rows are always scored — a head-resident near-duplicate is found even
    though the head is unbanded."""
    eng, docs, q_np, pick = _clustered_engine(
        policy=BandPolicy(n_bands=8, max_candidate_frac=0.5, min_rows=10_000)
    )
    eng.add(jnp.asarray(q_np[:1]))  # head doc identical to query 0's source
    head_id = eng.store.size - 1
    q = jnp.asarray(q_np)
    s0, i0 = map(np.asarray, eng.query(q, 10, prefilter=False))
    s1, i1 = map(np.asarray, eng.query(q, 10, prefilter=True))
    assert eng.last_prefilter_stats["unindexed_segments"] > 0
    assert eng.last_prefilter_stats["banded_segments"] == 0
    np.testing.assert_array_equal(i0, i1)  # everything exhaustive -> exact
    assert int(i1[0, 0]) == head_id  # the head self-match wins slot 0


def test_prefilter_auto_enable_and_opt_out():
    eng, _, q_np, _ = _clustered_engine()
    q = jnp.asarray(q_np)
    eng.query(q, 5)  # prefilter=None auto-enables with a policy armed
    assert eng.last_prefilter_stats is not None
    plain = SketchEngine.build(*_fixture()[:2], backend="oracle", mutable=True)
    with pytest.raises(ValueError, match="band_policy"):
        plain.query(jnp.asarray(_fixture()[2][:2]), 3, prefilter=True)


# ------------------------------------------------------------- lifecycle
def test_lifecycle_never_resurrects_tombstones():
    """seal -> delete -> compact -> distill: at every step the prefiltered
    query must never return a tombstoned id, and fresh indexes (compaction
    swap, distillation swap) must keep finding the live near-duplicates."""
    from repro.engine import DistillPolicy

    eng, docs, q_np, pick = _clustered_engine(n_docs=160, segments=2)
    q = jnp.asarray(q_np)
    dead = [int(pick[r]) for r in range(4)]
    eng.delete(dead)

    i1 = np.asarray(eng.query(q, 10, prefilter=True)[1])
    assert not np.isin(i1, dead).any()  # stale buckets filtered at query time

    eng.compact()  # new segment, fresh index built from survivors
    for seg in eng.store.sealed:
        if eng.store.band_policy.wants_index(seg.n_rows):
            assert seg.band_index is not None
    i2 = np.asarray(eng.query(q, 10, prefilter=True)[1])
    assert not np.isin(i2, dead).any()
    for r in range(4, len(pick)):  # undeleted near-dups still found
        assert int(pick[r]) in set(i2[r].tolist())

    eng.distill(DistillPolicy(widths=(128,)), background=False)
    assert any((s.n_bins or 256) == 128 for s in eng.store.sealed)
    i3 = np.asarray(eng.query(q, 10, prefilter=True)[1])
    assert not np.isin(i3, dead).any()
    stats = eng.last_prefilter_stats
    assert stats["banded_segments"] + stats["exhaustive_segments"] > 0


def test_background_compaction_rebuilds_index_off_thread():
    eng, docs, q_np, pick = _clustered_engine(n_docs=160, segments=2)
    dead = [int(pick[0]), int(pick[1])]
    eng.delete(dead)
    assert eng.compact(background=True) is None
    eng.wait_compaction()
    assert len(eng.store.sealed) == 1
    seg = eng.store.sealed[0]
    assert seg.band_index is not None and seg.band_index.n_rows == seg.n_rows
    i1 = np.asarray(eng.query(jnp.asarray(q_np), 10, prefilter=True)[1])
    assert not np.isin(i1, dead).any()
    for r in range(2, len(pick)):
        assert int(pick[r]) in set(i1[r].tolist())


def test_seal_sketches_bulk_ingest():
    """The bulk backfill path: pre-sketched rows seal directly into an
    indexed segment (no counting head), ids are contiguous, fills match
    the popcount, and queries treat the segment like any other."""
    cfg = BinSketchConfig(d=2048, n_bins=256)
    mapping = make_mapping(cfg, jax.random.PRNGKey(3))
    pol = BandPolicy(n_bands=8, min_rows=8)
    eng = SketchEngine.build(cfg, mapping, backend="oracle", mutable=True,
                             band_policy=pol)
    rng = np.random.default_rng(5)
    docs = _clustered(rng, 64, 8, 2048, 32)
    sk = eng.backend.sketch(cfg, mapping, jnp.asarray(docs))
    ids = eng.store.seal_sketches(sk, backend=eng.backend)
    assert list(ids) == list(range(64))
    seg = eng.store.sealed[-1]
    assert seg.band_index is not None
    np.testing.assert_array_equal(
        np.asarray(seg.fills), np.asarray(pk.row_popcount(sk))
    )
    twin = SketchEngine.build(cfg, mapping, jnp.asarray(docs),
                              backend="oracle", mutable=True)
    q = jnp.asarray(docs[:6])
    np.testing.assert_array_equal(
        np.asarray(eng.query(q, 5, prefilter=False)[1]),
        np.asarray(twin.query(q, 5)[1]),
    )
    with pytest.raises(ValueError, match="width"):
        eng.store.seal_sketches(jnp.zeros((4, cfg.n_words + 1), jnp.uint32))


def test_checkpoint_restore_rebuilds_band_index(tmp_path):
    """The index is never serialized: restore re-derives it from the slab +
    the aux-carried policy, and prefiltered answers survive the roundtrip."""
    from repro.checkpoint.manager import CheckpointManager

    eng, docs, q_np, pick = _clustered_engine(n_docs=160, segments=2)
    q = jnp.asarray(q_np)
    want = np.asarray(eng.query(q, 10, prefilter=True)[1])

    mgr = CheckpointManager(str(tmp_path))
    eng.store.save(mgr, step=1)
    back = SegmentedStore.restore(mgr)
    assert back.band_policy == eng.store.band_policy
    for seg, orig in zip(back.sealed, eng.store.sealed):
        assert (seg.band_index is None) == (orig.band_index is None)
        if seg.band_index is not None:
            np.testing.assert_array_equal(seg.band_index.orders,
                                          orig.band_index.orders)
    eng2 = SketchEngine(back, get_backend("oracle"), "jaccard",
                        QueryPlanner(min_batch=8, max_batch=16))
    np.testing.assert_array_equal(
        np.asarray(eng2.query(q, 10, prefilter=True)[1]), want
    )


# -------------------------------------------------------- topk crossover
@pytest.mark.parametrize("backend", ["oracle", "pallas-interpret"])
def test_topk_crossover_equivalence(backend):
    """Auto routing (materialize below the crossover, streaming above)
    returns bit-identical scores/ids to the forced streaming path, masks
    included, on both sides of the threshold."""
    import copy

    rng = np.random.default_rng(9)
    be = get_backend(backend)
    be_stream = copy.copy(be)
    be_stream.topk_crossover = 0
    n_bins, w, k = 101, 4, 7
    q = jnp.asarray(rng.integers(0, 2**32, (5, w), dtype=np.uint64).astype(np.uint32))
    for c in (37, 9000):
        corpus = jnp.asarray(
            rng.integers(0, 2**32, (c, w), dtype=np.uint64).astype(np.uint32)
        )
        valid = jnp.asarray((rng.random(c) > 0.2).astype(np.int32))
        for cv in (None, valid):
            s_a, i_a = be.topk(q, corpus, n_bins, "jaccard", k, corpus_valid=cv)
            s_f, i_f = be_stream.topk(q, corpus, n_bins, "jaccard", k,
                                      corpus_valid=cv)
            np.testing.assert_array_equal(np.asarray(i_a), np.asarray(i_f))
            np.testing.assert_allclose(np.asarray(s_a), np.asarray(s_f),
                                       rtol=1e-6)


# ----------------------------------------------------------------- sharded
def test_prefilter_placed_sliced_single_agreement(multidevice):
    """Mixed-width store on an 8-device mesh: the prefiltered placed path,
    the prefiltered single-device path, and both exhaustive paths agree
    (prefilter == prefilter, exhaustive == exhaustive, scores identical
    for shared ids) over a batch of several planner chunks."""
    multidevice(
        """
import numpy as np, jax, jax.numpy as jnp
from repro.core import BinSketchConfig, make_mapping
from repro.engine import BandPolicy, DistillPolicy, QueryPlanner, SketchEngine
from repro.launch.mesh import make_mesh

rng = np.random.default_rng(0)
d, nnz = 2048, 32
base = rng.integers(0, d, size=(30, nnz), dtype=np.int32)
docs = base[np.arange(240) % 30].copy()
docs[np.arange(240), rng.integers(0, nnz, 240)] = rng.integers(0, d, 240)
docs = np.sort(docs, axis=1)
cfg = BinSketchConfig(d=d, n_bins=256)
mapping = make_mapping(cfg, jax.random.PRNGKey(3))
eng = SketchEngine.build(cfg, mapping, backend="oracle", mutable=True,
                         band_policy=BandPolicy(n_bands=8, max_candidate_frac=0.5, min_rows=8),
                         planner=QueryPlanner(min_batch=8, max_batch=16))
for s in range(0, 240, 80):
    eng.add(jnp.asarray(docs[s : s + 80]))
    eng.seal()
eng.delete(list(range(0, 240, 13)))
eng.distill(DistillPolicy(widths=(128,)), background=False)  # mixed width
eng.add(jnp.asarray(docs[:5]))  # replicated head rows on top

# 40 queries: three planner chunks, each with its own candidate union
pick = rng.choice(240, 40, replace=False)
q_np = docs[pick].copy()
q_np[np.arange(40), rng.integers(0, nnz, 40)] = rng.integers(0, d, 40)
q = jnp.asarray(np.sort(q_np, axis=1))

mesh = make_mesh((8,), ("data",))
s_sp, i_sp = map(np.asarray, eng.query(q, 10, prefilter=True))
s_se, i_se = map(np.asarray, eng.query(q, 10, prefilter=False))
s_pp, i_pp = map(np.asarray, eng.query_sharded(mesh, "data", q, 10, prefilter=True))
s_pe, i_pe = map(np.asarray, eng.query_sharded(mesh, "data", q, 10, prefilter=False))
s_le, i_le = map(np.asarray, eng.query_sharded(mesh, "data", q, 10,
                                               use_placement=False))
np.testing.assert_array_equal(i_pp, i_sp)
np.testing.assert_allclose(s_pp, s_sp, rtol=1e-6)
np.testing.assert_array_equal(i_pe, i_se)
np.testing.assert_allclose(s_pe, s_se, rtol=1e-6)
np.testing.assert_array_equal(i_le, i_se)
print("placed/sliced/single agreement ok")
"""
    )
