"""The plain reference against the program's ``oracle`` backend at a tiny
size, and the device truth against the copied host ``exact_topk``."""

import numpy as np
import pytest

from bench_testlib import TINY
from bench import corpus, reference as ref, truth

K = 10


@pytest.fixture(scope="module")
def tiny():
    import jax.numpy as jnp

    from repro.core.binsketch import BinSketchConfig
    from repro.engine import SketchEngine

    cfg = dict(TINY)
    cfg.update(zipf_a=1.3, length_sigma=0.5,
               n_bins=corpus.theorem1_n_bins(TINY["psi"], TINY["rho"]))
    idx, _ = corpus.corpus(cfg, 3)
    pi = corpus.mapping(cfg, 3)
    bcfg = BinSketchConfig(d=cfg["vocab"], n_bins=cfg["n_bins"])
    eng = SketchEngine.build(bcfg, jnp.asarray(pi), jnp.asarray(idx), backend="oracle")
    return cfg, idx, pi, eng


def test_reference_sketches_equal_the_oracles(tiny):
    cfg, idx, pi, eng = tiny
    got = ref.pack_rows(ref.bin_rows(idx, pi), cfg["n_bins"])
    np.testing.assert_array_equal(got, np.asarray(eng.store.sketches))


def test_reference_estimate_agrees_with_the_oracle_scores(tiny):
    cfg, idx, pi, eng = tiny
    q = idx[:16]
    want = np.asarray(eng.score_all(q))
    qb, cb = ref.bin_rows(q, pi), ref.bin_rows(idx, pi)
    nab = ref.pair_counts(qb, np.broadcast_to(cb, (16,) + cb.shape), cfg["n_bins"])
    got = ref.binsketch_jaccard64(ref.set_sizes(qb)[:, None], ref.set_sizes(cb)[None, :],
                                  nab, cfg["n_bins"])
    np.testing.assert_allclose(got, want, atol=2e-6)


def test_reference_top_k_is_the_oracle_engines(tiny):
    cfg, idx, pi, eng = tiny
    q = idx[100:164]
    chunks = ref.device_chunks(lambda lo, hi: idx[lo:hi], len(idx), chunk=128)
    _, ids, inter, cs, qs = ref.scan_topk(
        q, np.full(len(q), len(idx)), chunks, kind="binsketch", universe=cfg["n_bins"],
        pi=pi, n_bins=cfg["n_bins"], k=K)
    est = np.where(ids >= 0, ref.binsketch_jaccard64(qs[:, None], cs, inter, cfg["n_bins"]),
                   -np.inf)
    top_i, top_s = ref.rank(ids, est, K)
    want_s, want_i = (np.asarray(x) for x in eng.query(q, K))
    np.testing.assert_allclose(top_s, want_s, atol=2e-6)
    # ids agree except where the two scores tie within float32 rounding
    differ = top_i != want_i
    assert np.all(np.abs(top_s - want_s)[differ] < 2e-6)
    assert differ.mean() < 0.05


def test_id_limits_admit_only_docs_acknowledged_before_the_query(tiny):
    cfg, idx, pi, _ = tiny
    q = idx[:8]
    limits = np.array([50, 100, 200, 300, 400, 500, 512, 10])
    chunks = ref.device_chunks(lambda lo, hi: idx[lo:hi], len(idx), chunk=96)
    _, ids, *_ = ref.scan_topk(q, limits, chunks, kind="binsketch", universe=cfg["n_bins"],
                               pi=pi, n_bins=cfg["n_bins"], k=K)
    valid = ids >= 0
    assert (ids < limits[:, None])[valid].all()


def test_device_truth_equals_the_copied_exact_topk(tiny):
    cfg, idx, _, _ = tiny
    q = idx[200:240]
    want = truth.exact_topk(idx, q, K)
    chunks = ref.device_chunks(lambda lo, hi: idx[lo:hi], len(idx), chunk=200)
    got = truth.device_exact_topk(q, np.full(len(q), len(idx)), chunks, K, vocab=cfg["vocab"])
    np.testing.assert_array_equal(got, want)
    assert truth.recall(got, want) == 1.0
    assert truth.recall(np.full_like(got, -1), want) == 0.0


def test_versions_admit_the_contents_each_query_saw(tiny):
    """A scan over every version of every doc (``History.chunks``) finds for
    each query what a scan over the corpus as it stood then finds."""
    from bench import check

    cfg, idx, pi, _ = tiny
    pool = corpus.corpus(dict(cfg, n_docs=64), 4)[0]
    hist = check.History(idx, pool)
    rng = np.random.default_rng(1)
    hot = rng.choice(len(idx), 40, replace=False)
    for lo in range(0, 64, 16):  # updates of 16 docs, hot docs written again
        hist.update(rng.choice(hot, 16, replace=False), lo)
    q = np.concatenate([idx[hot[:8]], pool[:8]])
    vers = np.arange(len(q)) % (hist.version + 1)
    chunks = hist.chunks()
    for kind, universe in (("binsketch", cfg["n_bins"]), ("jaccard", cfg["vocab"])):
        _, ids, inter, *_ = ref.scan_topk(q, np.full(len(q), len(idx)), chunks, kind=kind,
                                          universe=universe, pi=pi, n_bins=cfg["n_bins"],
                                          k=K, vers=vers)
        for v in range(hist.version + 1):
            at_v = hist.at(np.arange(len(idx)), np.full(len(idx), v))
            plain = ref.device_chunks(lambda lo, hi: at_v[lo:hi], len(idx), chunk=200)
            sel = vers == v
            _, want_ids, want_inter, *_ = ref.scan_topk(
                q[sel], np.full(sel.sum(), len(idx)), plain, kind=kind, universe=universe,
                pi=pi, n_bins=cfg["n_bins"], k=K)
            np.testing.assert_array_equal(inter[sel][:, :K], want_inter[:, :K])
            np.testing.assert_array_equal(ids[sel][:, :K], want_ids[:, :K])
    # an id's newest contents are its last update's
    last = hist.updates[-1]
    np.testing.assert_array_equal(hist.newest(last[0]), pool[last[1]])
