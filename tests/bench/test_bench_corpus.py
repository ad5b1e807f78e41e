"""The benchmark's corpus: a faithful copy of the program's generator, the
device copy's statistics, determinism by seed, and the NYTimes sizes."""

import json

import numpy as np
import pytest

from bench_testlib import REPO, TINY
from bench import corpus

CFG = json.loads((REPO / "bench" / "configs" / "nytimes-readonly.json").read_text())


def test_host_copy_matches_the_programs_generator():
    from repro.data.synthetic import DatasetSpec, generate_corpus

    spec = DatasetSpec("t", 3000, 102660, 230, 870, distinct=True)
    want_idx, want_len = generate_corpus(spec, seed=5)
    got_idx, got_len = corpus.distinct_rows(np.random.default_rng(5), 3000, 102660, 230, 870,
                                            1.3)
    np.testing.assert_array_equal(got_idx, want_idx)
    np.testing.assert_array_equal(got_len, want_len)


@pytest.mark.parametrize("make", ["host", "device"])
def test_same_seed_same_corpus_other_seed_other_corpus(make):
    cfg = dict(CFG, **TINY)
    gen = corpus.corpus if make == "host" else corpus.device_corpus
    a, b, c = gen(cfg, 2**31 + 3)[0], gen(cfg, 2**31 + 3)[0], gen(cfg, 2**31 + 4)[0]
    np.testing.assert_array_equal(a, b)
    assert (a != c).any()
    np.testing.assert_array_equal(corpus.mapping(cfg, 9), corpus.mapping(cfg, 9))


def _stats(idx):
    words = idx[idx >= 0]
    freq = np.sort(np.bincount(words))[::-1]
    return {"mean_len": (idx >= 0).sum(1).mean(), "top100": freq[:100].sum() / len(words),
            "top1000": freq[:1000].sum() / len(words), "max": words.max()}


def test_device_copy_draws_the_same_distribution():
    cfg = dict(CFG, n_docs=8000)
    host, dev = corpus.corpus(cfg, 1)[0], corpus.device_corpus(cfg, 1)[0]
    h, d = _stats(host), _stats(dev)
    assert abs(h["mean_len"] - d["mean_len"]) < 3.0
    assert abs(h["top100"] - d["top100"]) < 0.01
    assert abs(h["top1000"] - d["top1000"]) < 0.01
    assert d["max"] < cfg["vocab"]
    for row in dev[:300]:  # distinct, ascending, padded with -1 at the end
        n = (row >= 0).sum()
        assert (row[n:] == -1).all() and (np.diff(row[:n]) > 0).all()


def test_nytimes_sizes_follow_the_published_statistics():
    assert CFG["nnz"] / CFG["n_docs"] == pytest.approx(CFG["mean_distinct"], abs=0.01)
    n_bins = corpus.theorem1_n_bins(CFG["psi"], CFG["rho"])
    assert n_bins == CFG["n_bins"] == 34851
    assert corpus.n_words(n_bins) == CFG["n_words"] == 1090
    assert 4 * corpus.n_words(n_bins) * CFG["n_docs"] == pytest.approx(1.31e9, rel=0.01)
    sample = corpus.device_corpus(dict(CFG, n_docs=4096), 0)[0]
    assert _stats(sample)["mean_len"] == pytest.approx(CFG["mean_distinct"], rel=0.03)
    assert (sample >= 0).sum(1).max() <= CFG["psi"]


def test_the_insert_pool_is_drawn_from_the_seed():
    cfg = dict(CFG, **TINY)
    a, b = corpus.device_pool(cfg, 2**31 + 5, 256), corpus.device_pool(cfg, 2**31 + 5, 256)
    np.testing.assert_array_equal(a, b)
    assert (a != corpus.device_pool(cfg, 2**31 + 6, 256)).any()
    assert (a[:256] != corpus.device_corpus(cfg, 2**31 + 5)[0][:256]).any()
