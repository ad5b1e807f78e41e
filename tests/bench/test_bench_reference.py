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
