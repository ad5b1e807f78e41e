#!/usr/bin/env python3
"""Bring-up smoke of the sketch-serving engine, compiled, on TPU chips.

    python chip_smoke.py            # one chip: phases 1-5 below
    python chip_smoke.py --chips 4  # the segment-placed four-chip path only

Runs the engine's main path through its user entry points
(``repro.launch.serve.main``) at the published scale of the UCI NYTimes
bag-of-words corpus (n = 300,000 docs, d = 102,660 words, 230 distinct
words per doc on average, psi = 870 -> N = 34,851 sketch bins, W = 1,090
words), generated from a seed. One process, no children; every phase is
timed, with its compile time (JAX's backend-compile events) reported as
set-up.

  1. device check — the first device is a TPU and the ``pallas`` backend
     runs its kernels compiled (not interpreted). Anything else exits 1
     with a one-line reason before any phase runs;
  2. read-only serve — ingest through ``SketchEngine.add`` (the
     ``sketch_build`` kernel), 64 top-10 Jaccard queries through the fused
     ``topk_stream`` kernel, recall@10 against exact Jaccard;
  3. mutable serve — 10 % of the corpus deleted / updated, the head sealed
     every 16,384 rows, the banded prefilter armed, sealed segments
     distilled to N/2: ``count_update``, ``band_hash``, ``rebucket`` and
     the ``popcount_sim`` materialize path;
  4. parity — on 256 fresh query docs, each kernel against the ``oracle``
     backend's jnp reference, and each engine's top-10 against an oracle
     engine over the same store (``assert_topk_equivalent``: ids equal
     except at provable score ties);
  5. health — no degraded component, no failed / abandoned / quarantined
     job (``health_faults``).

``--chips 4`` runs only the placed path and what it is compared with: the
phase-3 store placed over a 4-device mesh (``query_sharded``) against
single-device ``query`` on the same store, each device holding resident
rows, and no ``placement`` degradation.

The last line of standard output is the JSON verdict
``{"ok": true, "device": {"platform", "kind", "count"}}``; any failure
exits non-zero without it.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
DATASET = "nytimes-full"
PARITY_QUERIES = 256
TOPK = 10


def fail(reason: str) -> None:
    print(f"chip_smoke: FAILED: {reason}", file=sys.stderr)
    sys.exit(1)


class CompileClock:
    """Sums JAX's backend-compile durations (persistent-cache reads
    included) and counts persistent-cache hits, for the set-up column."""

    def __init__(self):
        import jax

        self.secs, self.compiles, self.hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += secs
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def phase(name, clock, fn):
    """Run one phase; print its wall time, compile set-up and peak HBM."""
    import jax

    c0, n0, h0 = clock.secs, clock.compiles, clock.hits
    t0 = time.perf_counter()
    out = fn()
    wall = time.perf_counter() - t0
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use", 0) / 2**30
    print(f"[{name}] {wall:.1f} s wall, compile set-up {clock.secs - c0:.1f} s "
          f"({clock.compiles - n0} compiles, {clock.hits - h0} cache hits); "
          f"peak HBM {peak:.2f} GiB", flush=True)
    return out


def device_check(n_chips: int) -> dict:
    """Phase 1: a TPU, enough chips, and compiled (not interpreted) kernels."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"no TPU: jax.devices()[0].platform is {devs[0].platform!r}")
    if len(devs) < n_chips:
        fail(f"{n_chips} chip(s) asked for, {len(devs)} present")
    sys.path.insert(0, os.path.join(REPO, "src"))
    try:
        from repro.engine import get_backend
    except ImportError as e:
        fail(f"the repro package is not next to this script ({e})")
    if get_backend("pallas").interpreted:
        fail("the pallas backend resolved to interpret mode on a TPU")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": n_chips}


def serve_args(dataset: str, *extra: str) -> list:
    return ["--dataset", dataset, "--backend", "pallas", "--queries", "64",
            "--topk", str(TOPK), *extra]


def mutable_args(dataset: str, n_bins: int, seal_rows: int) -> list:
    return serve_args(dataset, "--mutate-rate", "0.1", "--seal-rows",
                      str(seal_rows), "--ingest-batch",
                      str(min(1024, seal_rows)), "--prefilter", "--bands", "8",
                      "--distill", str(n_bins // 2))


def parity_queries(dataset: str):
    """Fresh docs from the corpus distribution (not corpus members)."""
    from repro.data.synthetic import DATASETS, generate_corpus

    spec = dataclasses.replace(DATASETS[dataset], n_points=PARITY_QUERIES)
    return generate_corpus(spec, seed=7)[0]


def assert_same_topk(got, want, engine, queries, what: str) -> None:
    """ids equal except where the two ids score-tie under ``engine``."""
    import numpy as np

    from repro.engine.testing import assert_topk_equivalent, score_ids

    truth = None
    bad = np.asarray(got[1]) != np.asarray(want[1])
    if bad.any():
        rows = np.nonzero(bad.any(axis=1))[0]
        among = np.full((len(queries), 2 * TOPK), -1, np.int64)
        among[rows] = np.concatenate(
            [np.asarray(got[1])[rows], np.asarray(want[1])[rows]], axis=1)
        truth = score_ids(engine, queries, among)
    assert_topk_equivalent(got, want, truth, err_msg=what)
    print(f"parity: {what}: {len(queries)} queries, "
          f"{int(bad.sum())} tied slot(s) resolved differently", flush=True)


def kernel_parity(engine, queries) -> None:
    """Every kernel of the main path against the oracle's jnp reference on
    the same inputs: bit-exact where the result is integer."""
    import jax.numpy as jnp
    import numpy as np

    from repro.engine import get_backend

    pallas, oracle = engine.backend, get_backend("oracle")
    cfg, mapping = engine.cfg, engine.store.mapping
    q = jnp.asarray(queries)
    sk = pallas.sketch(cfg, mapping, q)
    exact = {
        "sketch_build": (sk, oracle.sketch(cfg, mapping, q)),
        "count_update": (pallas.count(cfg, mapping, q),
                         oracle.count(cfg, mapping, q)),
        "band_hash": (pallas.band_hash(sk, 8), oracle.band_hash(sk, 8)),
        "rebucket": (pallas.rebucket(sk, cfg.n_bins, cfg.n_bins // 2),
                     oracle.rebucket(sk, cfg.n_bins, cfg.n_bins // 2)),
    }
    for name, (got, want) in exact.items():
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                      err_msg=name)
    # the oracle materializes (Q, C, W) words: keep that under 1 GiB
    qs, corpus = sk[:32], engine.store.sketches[:4096]
    np.testing.assert_allclose(
        np.asarray(pallas.score(qs, corpus, cfg.n_bins, engine.measure)),
        np.asarray(oracle.score(qs, corpus, cfg.n_bins, engine.measure)),
        rtol=1e-5, atol=1e-6, err_msg="popcount_sim")
    print(f"parity: kernels {sorted(exact)} bit-exact on {len(queries)} docs, "
          f"popcount_sim allclose on {qs.shape[0]} x {corpus.shape[0]}",
          flush=True)


def engine_parity(engine, queries, what: str) -> None:
    """The compiled engine's top-10 against an oracle engine over the same
    store (same planner, same prefilter policy)."""
    import jax.numpy as jnp

    from repro.engine import SketchEngine, get_backend

    oracle = SketchEngine(engine.store, get_backend("oracle"), engine.measure,
                          engine.planner)
    q = jnp.asarray(queries)
    assert_same_topk(engine.query(q, TOPK), oracle.query(q, TOPK), oracle,
                     queries, f"{what} pallas vs oracle")


def health_check(engine, what: str) -> None:
    from repro.engine import health_faults

    found = health_faults(engine.health())
    if found:
        fail(f"{what} health: " + "; ".join(found))
    print(f"health: {what}: no degraded component, no failed job", flush=True)


def one_chip(dataset: str, clock) -> None:
    from repro.launch import serve

    run = phase("read-only serve", clock, lambda: serve.main(serve_args(dataset)))
    cfg = run.engine.cfg
    print(f"read-only: n={run.engine.store.size}, N={cfg.n_bins}, "
          f"W={cfg.n_words}, recall@{TOPK}={run.recall:.4f}", flush=True)
    queries = parity_queries(dataset)
    phase("parity (read-only store)", clock, lambda: (
        kernel_parity(run.engine, queries),
        engine_parity(run.engine, queries, "read-only")))
    health_check(run.engine, "read-only")
    n_bins = cfg.n_bins
    del run  # free the read-only store before the mutable one is built

    seal_rows = 16384 if dataset == DATASET else 64
    run = phase("mutable serve", clock,
                lambda: serve.main(mutable_args(dataset, n_bins, seal_rows)))
    widths = sorted({s.n_bins or n_bins for s in run.engine.store.sealed})
    print(f"mutable: live={run.engine.store.size}, sealed segment widths "
          f"{widths}, recall@{TOPK}={run.recall:.4f}", flush=True)
    phase("parity (mutable store)", clock,
          lambda: engine_parity(run.engine, queries, "mutable"))
    health_check(run.engine, "mutable")


def four_chips(dataset: str, n_chips: int, clock) -> None:
    import jax.numpy as jnp
    import numpy as np

    from repro.core import BinSketchConfig
    from repro.data.synthetic import DATASETS
    from repro.launch import serve
    from repro.launch.mesh import make_mesh

    spec = DATASETS[dataset]
    n_bins = BinSketchConfig.from_sparsity(spec.d, spec.max_nnz, 0.05).n_bins
    seal_rows = 16384 if dataset == DATASET else 64
    run = phase("placed mutable serve", clock, lambda: serve.main(
        mutable_args(dataset, n_bins, seal_rows) + ["--sharded"]))
    engine = run.engine
    mesh = make_mesh((n_chips,), ("data",))
    queries = parity_queries(dataset)
    q = jnp.asarray(queries)
    phase("placed vs single-device", clock, lambda: assert_same_topk(
        engine.query_sharded(mesh, "data", q, TOPK), engine.query(q, TOPK),
        engine, queries, f"query_sharded on {n_chips} chips vs query"))
    placement = engine.place(mesh, "data")
    rows = np.zeros(n_chips, np.int64)
    for slab in placement.slabs:
        if slab.sketches.sharding.is_fully_replicated:
            fail(f"width-{slab.n_bins} slab is replicated, not placed")
        for shard in slab.ids.addressable_shards:
            d = mesh.devices.tolist().index(shard.device)
            rows[d] += int((np.asarray(shard.data) >= 0).sum())
    print(f"residency: live rows per device {rows.tolist()} over "
          f"{len(placement.slabs)} width slab(s), segments per device "
          f"{[len(g) for g in placement.assign]}; head "
          f"({engine.store.head.size} rows) scored replicated", flush=True)
    if (rows == 0).any():
        fail(f"a device holds no resident rows: {rows.tolist()}")
    health_check(engine, "placed")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the segment-placed four-chip path")
    args = ap.parse_args(argv)
    device = device_check(args.chips)

    from repro.launch.cache import enable_compile_cache

    print(f"chip_smoke: {device}, compile cache {enable_compile_cache()}",
          flush=True)
    clock = CompileClock()
    t0 = time.perf_counter()
    if args.chips == 1:
        one_chip(DATASET, clock)
    else:
        four_chips(DATASET, args.chips, clock)
    print(f"chip_smoke: all phases passed in {time.perf_counter() - t0:.1f} s, "
          f"compile set-up {clock.secs:.1f} s over {clock.compiles} compiles "
          f"({clock.hits} persistent-cache hits)", flush=True)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
