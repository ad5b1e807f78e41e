"""The one traffic generator: reads a mix's parameters and yields requests.

A mix is a JSON file under ``bench/traffic/``::

    {"loop": "closed", "clients": 1, "op": "query", "docs": 32, "k": 10,
     "warmup_requests": 3, "check_queries": 4096, "trace_seconds": 5}

    {"loop": "closed", "clients": 1, "ops": {"query": 0.95, "update": 0.05},
     "keys": "zipf", "zipf_s": 0.99, "docs": 32, "k": 10, "pool_docs": 16384,
     "warmup_requests": 4, "check_queries": 4096, "findability_queries": 64,
     "check_sample": 4096, "trace_seconds": 5}

* ``op``: every request of the mix is one ``query`` (the top-``k`` of
  ``docs`` query documents), one ``insert`` (``docs`` new documents) or one
  ``update`` (new contents for ``docs`` distinct standing documents).
* ``ops`` in place of ``op``: each request's op is drawn from the seed with
  these weights (YCSB's read/update proportions).
* ``keys``: how the standing documents a query asks about or an update
  replaces are drawn. ``uniform`` (the default): a permutation from the
  seed, with no repeat until every standing doc has been drawn once.
  ``zipf``: YCSB's scrambled Zipfian, rank ``r`` drawn with probability
  proportional to ``r^-zipf_s`` over the ``n`` standing docs and ranks
  mapped to docs by a permutation from the seed, so that the hot docs lie
  in every segment. Query keys repeat across and within requests; the keys
  of one update are distinct (the store refuses a repeated id in a batch).
* A query sends each key's newest contents: those of the last update
  acknowledged (``Generator.ack``) before the query is made.
* ``pool_docs`` (insert, update): the contents of inserts and updates cycle
  through this many fresh documents made on the device at set-up from the
  seed, so the write path's work does not depend on the window's length.
* ``warmup_requests``: requests sent in set-up, before the window, to
  compile and settle every shape the window uses; with ``ops``, they take
  each op in turn.
* ``check_queries``: at most this many window queries are compared with the
  reference (a sample drawn from the seed when there are more).
* ``findability_queries``: after a window with inserts or updates, this
  many inserted and this many updated documents are sent as queries with
  their newest contents and compared with the reference, so that an
  acknowledged write is shown findable.
* ``check_sample``: after a window with inserts or updates, this many
  inserted and this many updated documents (with every one of the last
  request of each) have their stored sketches compared with the
  reference's sketch of their newest contents.
* ``trace_seconds``: the traced window of a ``--trace 1`` run, at most the
  run's ``--seconds``.
"""

from __future__ import annotations

import dataclasses
import numbers

import numpy as np

from . import corpus as corpus_mod

OPS = ("query", "insert", "update")
KEYS = ("uniform", "zipf")


@dataclasses.dataclass
class Request:
    op: str
    idx: np.ndarray  # (docs, P) padded word rows sent
    pool_lo: int = -1  # first pool row of an insert's or update's contents
    k: int = 0
    keys: np.ndarray | None = None  # standing ids a query asks about or an update replaces


def op_weights(mix: dict) -> dict:
    """``{op: weight}`` of the mix's requests."""
    return {mix["op"]: 1.0} if "op" in mix else dict(mix["ops"])


def validate(mix: dict) -> None:
    if mix.get("loop") != "closed" or mix.get("clients") != 1:
        raise ValueError("only closed-loop traffic with one client is generated")
    if ("op" in mix) == ("ops" in mix):
        raise ValueError("a mix gives exactly one of 'op' and 'ops'")
    if "ops" in mix and (not isinstance(mix["ops"], dict) or not mix["ops"]):
        raise ValueError(f"ops must map ops to weights: {mix['ops']!r}")
    ops = op_weights(mix)
    for op, w in ops.items():
        if op not in OPS:
            raise ValueError(f"op must be one of {OPS}: {op!r}")
        if not isinstance(w, numbers.Real) or isinstance(w, bool) or not w > 0:
            raise ValueError(f"the weight of {op!r} must be a number above 0: {w!r}")
    keys = mix.get("keys", "uniform")
    if keys not in KEYS:
        raise ValueError(f"keys must be one of {KEYS}: {keys!r}")
    if keys == "zipf" and not (isinstance(mix.get("zipf_s"), numbers.Real)
                               and mix["zipf_s"] > 0):
        raise ValueError(f"zipf keys need zipf_s above 0: {mix.get('zipf_s')!r}")
    if {"insert", "update"} & set(ops) and not (
            isinstance(mix.get("pool_docs"), int) and mix["pool_docs"] >= mix.get("docs", 0)):
        raise ValueError("inserts and updates need pool_docs of at least docs")


class Zipf:
    """YCSB's scrambled Zipfian over ``n`` ids: rank ``r`` (from 1) drawn
    with probability ``r^-s / H(n, s)`` by inverse CDF, ranks mapped to ids
    by a permutation."""

    def __init__(self, n: int, s: float, rng: np.random.Generator):
        self.rng = rng
        self.ids = rng.permutation(n)
        cdf = np.cumsum(np.arange(1, n + 1, dtype=np.float64) ** -float(s))
        self.cdf = cdf / cdf[-1]

    def draw(self, m: int, distinct: bool) -> np.ndarray:
        keys = self.ids[np.searchsorted(self.cdf, self.rng.random(m), side="right")]
        while distinct:  # draw again each key that repeats an earlier one
            first = np.unique(keys, return_index=True)[1]
            again = np.setdiff1d(np.arange(m), first)
            if not len(again):
                break
            keys[again] = self.ids[np.searchsorted(self.cdf, self.rng.random(len(again)),
                                                   side="right")]
        return keys


class Generator:
    """Requests of one mix for one seed, over a standing corpus."""

    def __init__(self, mix: dict, cfg: dict, seed: int, standing: np.ndarray):
        validate(mix)
        self.mix, self.standing = mix, standing
        self.rng = corpus_mod.rng_for(seed, corpus_mod.STREAM_TRAFFIC)
        self.order = self.rng.permutation(len(standing))
        self.next_key = 0
        ops = op_weights(mix)
        self.ops = list(ops)
        self.op_cdf = np.cumsum(list(ops.values())) / sum(ops.values())
        self.op_cdf[-1] = 1.0  # every draw in [0, 1) falls below the last
        self.op_rng = corpus_mod.rng_for(seed, corpus_mod.STREAM_OPS) if "ops" in mix else None
        if "update" in self.ops and mix["docs"] > len(standing):
            raise ValueError("an update replaces at most every standing doc once")
        self.zipf = None
        if mix.get("keys", "uniform") == "zipf":
            self.zipf = Zipf(len(standing), mix["zipf_s"],
                             corpus_mod.rng_for(seed, corpus_mod.STREAM_KEYS))
        self.pool = None
        if {"insert", "update"} & set(self.ops):
            self.pool = corpus_mod.device_pool(cfg, seed, mix["pool_docs"])
            self.next_pool = 0
        self.newest = None  # (n,) pool row of each standing doc's newest contents, or -1

    def warmup_op(self, j: int):
        """The op of the ``j``-th warm-up request: each op of ``ops`` in
        turn, or the mix's one ``op`` (None: as the window draws it)."""
        return self.ops[j % len(self.ops)] if self.op_rng is not None else None

    def next(self, op: str | None = None) -> Request:
        if op is None:
            op = self.ops[0] if self.op_rng is None else self.ops[
                int(np.searchsorted(self.op_cdf, self.op_rng.random(), side="right"))]
        m = self.mix["docs"]
        if op == "insert":
            return self._pool_request("insert", m)
        keys = self._keys(m, distinct=op == "update")
        if op == "query":
            return Request("query", self.contents(keys), k=self.mix["k"], keys=keys)
        req = self._pool_request("update", m)
        req.keys = keys
        return req

    def ack(self, req: Request) -> None:
        """``req``, an update, is acknowledged: later queries send its contents."""
        if self.newest is None:
            self.newest = np.full(len(self.standing), -1, np.int64)
        self.newest[req.keys] = (req.pool_lo + np.arange(len(req.keys))) % len(self.pool)

    def contents(self, keys: np.ndarray) -> np.ndarray:
        """The newest acknowledged contents of standing docs ``keys``."""
        rows = self.standing[keys]
        if self.newest is not None:
            new = self.newest[keys]
            rows[new >= 0] = self.pool[new[new >= 0]]
        return rows

    def _keys(self, m: int, distinct: bool) -> np.ndarray:
        if self.zipf is not None:
            return self.zipf.draw(m, distinct)
        if self.next_key + m > len(self.standing):  # all drawn: a fresh order
            self.order, self.next_key = self.rng.permutation(len(self.standing)), 0
        keys = self.order[self.next_key : self.next_key + m]
        self.next_key += m
        return keys

    def _pool_request(self, op: str, m: int) -> Request:
        p, lo = len(self.pool), self.next_pool
        self.next_pool = (lo + m) % p
        if lo + m <= p:  # a view: the client sends its buffer, no copy
            return Request(op, self.pool[lo : lo + m], pool_lo=lo)
        return Request(op, self.pool[np.arange(lo, lo + m) % p], pool_lo=lo)
