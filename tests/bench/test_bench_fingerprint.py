"""Nothing that the cells already there read has moved: the first 200
requests of each of their mixes and the check's draws, for two seeds each,
hash to what the harness drew before a mix could hold more than one op."""

import hashlib
import json

import numpy as np
import pytest

from bench_testlib import REPO
from bench import check, run, traffic

#: sha256 of the parent harness's draws (int64 bytes, in order)
RECORDED = {
    ("mlt32", 5): (
        "e6ed06bd95ad8c8582b647cc0261bab8f9e0a00e45bc1a025b6a3877d02986ca",
        "119e1b594a7908d9f4722966cc12731ae8a4ba5c18d79c2dd919c3b7f44ba183"),
    ("mlt32", 2**31 + 17): (
        "4b0acbf2fd2c2fa46ae89f43c352b3e2cc7a686c002ddd5f6fc3559454c7880b",
        "62d7922849ecacfbea6d48d76a02bb6593e2d1f73cdc6d1358246868c205aab6"),
    ("ingest1024", 5): (
        "eeec33ec59335e747d5a38afc5037e75557f6817d0972fd246004b45b74d82c2",
        "90b7b9635283395222fbb4324b6687ba4cff82b58a786b28a50799f1893cd215"),
    ("ingest1024", 2**31 + 17): (
        "737429d0003f96fc296b3129aa4ac58fb1db434e02849e2df416126e52cdc403",
        "2d2028f6c88f3a21e470b62562b5fe0de77b6afbbff80ffcd450b98ac41c7303"),
}

#: the pool's distribution, cut small (the draws do not depend on it)
CFG = {"n_docs": 300000, "vocab": 2048, "mean_distinct": 40.0, "psi": 96, "zipf_a": 1.3,
       "length_sigma": 0.5}
N_DOCS = 300000


def _hash(*arrays):
    m = hashlib.sha256()
    for a in arrays:
        m.update(np.ascontiguousarray(np.asarray(a, np.int64)).tobytes())
    return m.hexdigest()


@pytest.mark.parametrize("traffic_name,seed", sorted(RECORDED))
def test_the_cells_draw_what_they_drew_before(traffic_name, seed):
    mix = json.loads((REPO / "bench" / "traffic" / f"{traffic_name}.json").read_text())
    standing = np.arange(N_DOCS, dtype=np.int32)[:, None]  # a row names its doc
    gen = traffic.Generator(mix, CFG, seed, standing)
    reqs = [gen.next() for _ in range(200)]
    got_requests = _hash(np.concatenate([r.idx.ravel() for r in reqs]),
                         [r.pool_lo for r in reqs], [len(r.idx) for r in reqs])

    # the check's draws after a window of a length such runs reach
    records, hist = [], check.History(standing, np.zeros((mix.get("pool_docs", 1), 1)))
    if mix["op"] == "query":
        for j in range(1100):
            rows = np.arange(j * 32, (j + 1) * 32, dtype=np.int32)[:, None]
            records.append({"op": "query", "docs": 32, "live": N_DOCS + j, "ver": 0,
                            "req": traffic.Request("query", rows, k=10),
                            "out": {"scores": np.zeros((32, 10)),
                                    "ids": np.zeros((32, 10), np.int64)}})
    else:
        for j in range(989):
            hist.insert(N_DOCS + j * 1024, N_DOCS + (j + 1) * 1024, 0)
    queries, sample, f_ids = run._check_draws(seed, mix, records, hist, 1)
    got_check = (_hash(queries[0][:, 0], queries[1]) if mix["op"] == "query"
                 else _hash(sample, f_ids))
    assert (got_requests, got_check) == RECORDED[(traffic_name, seed)]
