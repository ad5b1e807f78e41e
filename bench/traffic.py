"""The one traffic generator: reads a mix's parameters and yields requests.

A mix is a JSON file under ``bench/traffic/``::

    {"loop": "closed", "clients": 1, "op": "query", "docs": 32, "k": 10,
     "warmup_requests": 3, "check_queries": 4096, "trace_seconds": 5}

* ``op``: every request of the mix is one ``query`` (the top-``k`` of
  ``docs`` query documents) or one ``insert`` (``docs`` new documents).
* query documents are standing documents drawn uniformly from the seed, with
  no repeat until every standing doc has been asked once.
* ``pool_docs`` (insert): the contents of inserts cycle through this many
  fresh documents made on the device at set-up from the seed, under fresh ids, so the
  write path's work does not depend on the window's length.
* ``warmup_requests``: requests sent in set-up, before the window, to
  compile and settle every shape the window uses.
* ``check_queries``: at most this many window queries are compared with the
  reference (a sample drawn from the seed when there are more).
* ``findability_queries``: after an insert window, this many inserted
  documents are sent as queries and compared with the reference, so that an
  acknowledged insert is shown findable.
* ``check_sample``: after an insert window, this many acknowledged
  documents (with every one of the last request) have their stored sketches
  compared with the reference's.
* ``trace_seconds``: the traced window of a ``--trace 1`` run, at most the
  run's ``--seconds``.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from . import corpus as corpus_mod

OPS = ("query", "insert")


@dataclasses.dataclass
class Request:
    op: str
    idx: np.ndarray  # (docs, P) padded word rows sent
    pool_lo: int = -1  # first pool row of an insert's contents
    k: int = 0


def validate(mix: dict) -> None:
    if mix.get("loop") != "closed" or mix.get("clients") != 1:
        raise ValueError("only closed-loop traffic with one client is generated")
    if mix.get("op") not in OPS:
        raise ValueError(f"op must be one of {OPS}: {mix.get('op')!r}")


class Generator:
    """Requests of one mix for one seed, over a standing corpus."""

    def __init__(self, mix: dict, cfg: dict, seed: int, standing: np.ndarray):
        validate(mix)
        self.mix, self.standing = mix, standing
        self.rng = corpus_mod.rng_for(seed, corpus_mod.STREAM_TRAFFIC)
        self.order = self.rng.permutation(len(standing))
        self.next_key = 0
        self.pool = None
        if mix["op"] == "insert":
            self.pool = corpus_mod.device_pool(cfg, seed, mix["pool_docs"])
            self.next_pool = 0

    def next(self) -> Request:
        m = self.mix["docs"]
        if self.mix["op"] == "query":
            if self.next_key + m > len(self.standing):  # all asked: a fresh order
                self.order, self.next_key = self.rng.permutation(len(self.standing)), 0
            keys = self.order[self.next_key : self.next_key + m]
            self.next_key += m
            return Request("query", self.standing[keys], k=self.mix["k"])
        p, lo = len(self.pool), self.next_pool
        self.next_pool = (lo + m) % p
        if lo + m <= p:  # a view: the client sends its buffer, no copy
            return Request("insert", self.pool[lo : lo + m], pool_lo=lo)
        return Request("insert", self.pool[np.arange(lo, lo + m) % p], pool_lo=lo)
