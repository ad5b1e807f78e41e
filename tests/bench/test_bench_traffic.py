"""The generator's mixed ops and skewed keys: op shares, the Zipf law of the
keys, distinct update keys, queries that read the acknowledged writes, and
the same sequence from the same seed."""

import math

import numpy as np
import pytest

from bench import traffic

CFG = {"n_docs": 1000, "vocab": 2048, "mean_distinct": 40.0, "psi": 96, "zipf_a": 1.3,
       "length_sigma": 0.5}
N = 1000
STANDING = np.arange(N * 96, dtype=np.int32).reshape(N, 96)  # row j names doc j
MIX = {"loop": "closed", "clients": 1, "ops": {"query": 0.95, "update": 0.05},
       "keys": "zipf", "zipf_s": 0.99, "docs": 8, "k": 10, "pool_docs": 64,
       "check_sample": 16}


def _gen(seed=2**31 + 21, **over):
    return traffic.Generator(dict(MIX, **over), CFG, seed, STANDING)


def _within(count, n, p, sds=4.0):
    return abs(count - n * p) <= sds * math.sqrt(n * p * (1 - p))


def test_op_shares_match_their_weights():
    gen = _gen()
    ops = [gen.next().op for _ in range(4000)]
    assert set(ops) == {"query", "update"}
    assert _within(ops.count("update"), 4000, 0.05)
    gen = _gen(ops={"query": 1, "update": 1, "insert": 2})
    ops = [gen.next().op for _ in range(4000)]
    assert _within(ops.count("insert"), 4000, 0.5)
    assert _within(ops.count("update"), 4000, 0.25)


def test_the_top_zipf_rank_takes_one_over_h_of_the_keys():
    gen = _gen(ops={"query": 1.0}, docs=50)
    keys = np.concatenate([gen.next().keys for _ in range(4000)])
    h = sum(r ** -0.99 for r in range(1, N + 1))
    top, second = gen.zipf.ids[0], gen.zipf.ids[1]
    assert _within(int((keys == top).sum()), len(keys), 1 / h)
    assert _within(int((keys == second).sum()), len(keys), 2 ** -0.99 / h)
    # the ranks are scrambled: the hot docs are not the low ids
    assert sorted(gen.zipf.ids[:10].tolist()) != list(range(10))


def test_update_keys_are_distinct_within_a_request():
    gen = _gen(ops={"update": 1.0}, docs=64)
    for _ in range(300):
        req = gen.next()
        assert req.op == "update" and len(np.unique(req.keys)) == 64
        np.testing.assert_array_equal(req.idx, gen.pool[np.arange(req.pool_lo,
                                                                  req.pool_lo + 64) % 64])


def test_a_query_sends_the_newest_acknowledged_contents():
    gen = _gen(ops={"query": 1.0, "update": 1.0}, keys="uniform")
    first, second = gen.next("update"), gen.next("update")
    ids = np.concatenate([first.keys, second.keys])
    assert len(np.unique(ids)) == 16  # uniform keys: a permutation
    np.testing.assert_array_equal(gen.contents(ids), STANDING[ids])  # nothing acknowledged
    gen.ack(first)
    np.testing.assert_array_equal(gen.contents(first.keys), first.idx)
    np.testing.assert_array_equal(gen.contents(second.keys), STANDING[second.keys])
    again = gen.next("update")
    again.keys = first.keys[::-1].copy()
    gen.ack(again)
    np.testing.assert_array_equal(gen.contents(first.keys[::-1]), again.idx)
    # the rows a query request sends are its keys' contents
    gen = _gen()
    upd = gen.next("update")
    gen.ack(upd)
    for _ in range(200):
        req = gen.next("query")
        np.testing.assert_array_equal(req.idx, gen.contents(req.keys))
        hit = np.isin(req.keys, upd.keys)
        np.testing.assert_array_equal(req.idx[~hit], STANDING[req.keys[~hit]])
        assert all((row == upd.idx[list(upd.keys).index(key)]).all()
                   for row, key in zip(req.idx[hit], req.keys[hit]))


def test_the_same_seed_gives_the_same_sequence():
    def seq(seed):
        gen = _gen(seed, ops={"query": 0.5, "update": 0.3, "insert": 0.2})
        out = []
        for j in range(300):
            req = gen.next(gen.warmup_op(j) if j < 3 else None)
            if req.op == "update" and j % 2:
                gen.ack(req)
            out.append((req.op, req.pool_lo, None if req.keys is None else req.keys.tolist(),
                        req.idx.tolist()))
        return out

    a = seq(2**31 + 3)
    assert a == seq(2**31 + 3)
    assert a != seq(2**31 + 4)
    assert [op for op, *_ in a[:3]] == ["query", "update", "insert"]  # warm-up: each op


@pytest.mark.parametrize("bad", [
    {"op": "query"},  # both op and ops
    {"ops": {"query": 1.0, "delete": 1.0}},
    {"ops": {"query": 0.0, "update": 1.0}},
    {"ops": {}},
    {"keys": "hotspot"},
    {"zipf_s": 0},
    {"pool_docs": 4},  # fewer than one request's docs
])
def test_a_malformed_mix_is_refused(bad):
    with pytest.raises(ValueError):
        traffic.validate(dict(MIX, **bad))
