"""The system under test, reached only through its public API.

``build`` makes a ``SketchEngine`` over the configuration's store from the
benchmark's corpus and sketch map; ``query``, ``insert`` and ``update`` are
the calls a client makes. Each call into the engine sits in a ``bench.*``
profiler span, so that the trace can say what the host was doing between device
operations; ``PHASES`` holds the host seconds of each span of the last
request, so that a slow request can be told apart without a trace.
"""

from __future__ import annotations

import contextlib
import time

import numpy as np

#: span name -> host seconds it took in the last request
PHASES: dict = {}


@contextlib.contextmanager
def _span(name: str):
    import jax

    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(name):
        yield
    PHASES[name] = time.perf_counter() - t0


class Builder:
    """The configuration's engine, fed the standing corpus one device chunk
    at a time (``add``) and returned by ``finish``: a read-only store ingests
    each chunk (``SketchEngine.add``), a segmented store sketches the chunks
    and bulk-seals every ``seal_rows`` of them (``seal_sketches``)."""

    def __init__(self, cfg: dict, pi: np.ndarray, backend: str = "pallas"):
        import jax.numpy as jnp

        from repro.core.binsketch import BinSketchConfig
        from repro.engine import SketchEngine

        self.cfg, self.pending = cfg, []
        self.bcfg = BinSketchConfig(d=cfg["vocab"], n_bins=cfg["n_bins"])
        self.mapping = jnp.asarray(pi)
        if cfg["store"] == "readonly":
            self.eng = SketchEngine.build(self.bcfg, self.mapping, backend=backend,
                                          capacity=cfg["n_docs"])
        elif cfg["store"] == "segmented":
            seal = cfg["seal_rows"]
            if seal % cfg["build_batch"]:
                raise ValueError("seal_rows must be a multiple of build_batch")
            self.eng = SketchEngine.build(self.bcfg, self.mapping, backend=backend,
                                          mutable=True, seal_rows=seal, capacity=seal)
        else:
            raise ValueError(f"unknown store {cfg['store']!r}")

    def add(self, rows) -> None:
        eng = self.eng
        if self.cfg["store"] == "readonly":
            eng.add(rows, batch=self.cfg["build_batch"])
            return
        self.pending.append(eng.backend.sketch(self.bcfg, self.mapping, rows))
        if sum(p.shape[0] for p in self.pending) >= self.cfg["seal_rows"]:
            self._seal()

    def _seal(self) -> None:
        import jax.numpy as jnp

        if self.pending:
            self.eng.store.seal_sketches(jnp.concatenate(self.pending))
            self.pending = []

    def finish(self):
        import jax

        if self.cfg["store"] == "segmented":
            self._seal()
        jax.block_until_ready([v.sketches for v in self.eng.store.segment_views()])
        return self.eng


def query(eng, idx: np.ndarray, k: int):
    """One query request: the client's rows in, (scores, ids) on the host out."""
    import jax.numpy as jnp

    with _span("bench.upload"):
        q = jnp.asarray(idx)
    with _span("bench.query"):
        s, i = eng.query(q, k)
    with _span("bench.fetch"):
        return np.asarray(s), np.asarray(i)


def insert(eng, idx: np.ndarray):
    """One insert request; returns (first id, last id + 1, whether it sealed)
    once the documents are stored on the device."""
    import jax
    import jax.numpy as jnp

    store = eng.store
    n_sealed = len(getattr(store, "sealed", ()))
    with _span("bench.upload"):
        x = jnp.asarray(idx)
    with _span("bench.add"):
        ids = eng.add(x, batch=len(idx))
    with _span("bench.settle"):
        sealed = len(getattr(store, "sealed", ())) > n_sealed
        jax.block_until_ready(_written(store, sealed))
    return ids.start, ids.stop, sealed


def update(eng, ids: np.ndarray, idx: np.ndarray) -> bool:
    """One update request: the contents of docs ``ids`` replaced by the rows
    ``idx``; returns whether it sealed the head, once the new contents are
    stored on the device."""
    import jax
    import jax.numpy as jnp

    store = eng.store
    n_sealed = len(store.sealed)
    with _span("bench.upload"):
        x = jnp.asarray(idx)
    with _span("bench.update"):
        eng.update(ids, x)
    with _span("bench.settle"):
        sealed = len(store.sealed) > n_sealed
        jax.block_until_ready(_written(store, sealed))
    return sealed


def _written(store, sealed: bool):
    """The device arrays an insert or an update wrote: a segmented store's
    head (and the segment it sealed), or an append-only store's rows."""
    head = getattr(store, "head", None)
    if head is None:
        return [store.sketches]
    return [head.packed, head.fills] + ([store.sealed[-1].sketches] if sealed else [])


def views(eng):
    """The store as (sketches, ids or None, valid or None) per query view."""
    return [(v.sketches, v.ids, v.valid) for v in eng.store.segment_views()]
