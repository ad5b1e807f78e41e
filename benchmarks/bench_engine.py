"""Serving-engine throughput: ingest docs/s (batch vs streaming), query q/s
with the ingest-time fill cache on vs off, the fused streaming top-k
vs the materialize-(Q,C)-then-``lax.top_k`` baseline across corpus sizes,
the mutable-corpus lifecycle (ingest -> delete -> compact -> query)
against a fresh batch rebuild — including what serving pays during a
background compaction — the segment-placed sharded path against the
slice-every-segment baseline (per-query cross-device payload + QPS), and
segment distillation (bytes/doc + recall@k before/after each width tier,
background-fold launch + swap stalls), and the banded LSH prefilter at
serving scale (QPS + recall@k vs the exhaustive scan over >= 1M clustered
synthetic docs, DESIGN.md §12).

    PYTHONPATH=src python -m benchmarks.bench_engine [--dataset tiny]
    PYTHONPATH=src python -m benchmarks.bench_engine --smoke   # CI parity gate

Emits ``BENCH_engine.json`` (repo root by default) so the perf trajectory
of the serving subsystem is recorded PR-over-PR. Uses the oracle backend on
CPU (the Pallas interpret path measures Python, not the system); on TPU run
with ``--backend pallas``.

Timing discipline: every timed section is jit-warmed (two untimed calls,
each ``block_until_ready``) and reports the *minimum* over ``repeats``
timed calls — the standard microbenchmark estimator; mean-of-noisy-runs is
what made the fill cache look like a regression in PR 1's numbers. Paired
comparisons (fill cache on/off, fused vs materialize, post-compaction vs
fresh) additionally *interleave* their two arms per repeat, so load drift
between separately-timed blocks cannot masquerade as a speedup of the arm
that ran in the quieter window.

The top-k sweep scores synthetic random packed sketches (content does not
affect the arithmetic) so 64k+ docs don't pay the host-side corpus
generator. Alongside QPS it reports the scoring output footprint per query
batch: the fused path writes O(Q·k), the materialize path O(Q·C) — the
memory wall the streaming kernel removes (DESIGN.md §7).
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np


def _timeit(fn, repeats: int, warmup: int = 2) -> float:
    for _ in range(warmup):  # trace + compile + first-touch, untimed
        jax.block_until_ready(fn())
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def _timeit_pair(fa, fb, repeats: int, warmup: int = 2):
    """Min-of-repeats for two competing arms, *interleaved*.

    Timing the arms in separate blocks lets background-load drift between
    the blocks masquerade as a speedup (or regression) of whichever arm ran
    in the quieter window — the cross-arm cousin of the mean-vs-min problem
    the per-arm estimator already fixes. Alternating A/B per repeat puts
    both arms under the same load profile."""
    for _ in range(warmup):
        jax.block_until_ready(fa())
        jax.block_until_ready(fb())
    best_a = best_b = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fa())
        best_a = min(best_a, time.perf_counter() - t0)
        t0 = time.perf_counter()
        jax.block_until_ready(fb())
        best_b = min(best_b, time.perf_counter() - t0)
    return best_a, best_b


def _rand_packed(rng, n: int, n_words: int) -> jnp.ndarray:
    x = rng.integers(0, 2**32, (n, n_words), dtype=np.uint64).astype(np.uint32)
    return jnp.asarray(x)


def run_topk_sweep(sizes, backend="oracle", queries=32, topk=10, n_bins=512,
                   repeats=3, seed=0, auto_tolerance=1.25):
    """Fused streaming top-k vs materialize+``lax.top_k`` per corpus size,
    plus the **auto** arm: ``Backend.topk`` as shipped, which routes to the
    materialize path below ``topk_crossover`` and the streaming path above
    (the 0.93x-at-4096 dip in PR 2's sweep was the streaming overhead on a
    corpus too small to amortize it). Each row asserts the auto arm lands
    within ``auto_tolerance`` of the faster hand-picked arm — the crossover
    must never route a size to its slower path."""
    import copy

    from repro.core.packed import num_words, row_popcount
    from repro.engine import get_backend

    be = get_backend(backend)
    be_stream = copy.copy(be)
    be_stream.topk_crossover = 0  # force the streaming/fused path
    w = num_words(n_bins)
    rng = np.random.default_rng(seed)
    qs = _rand_packed(rng, queries, w)
    rows = []
    for c in sizes:
        corpus = _rand_packed(rng, c, w)
        fills = row_popcount(corpus)  # = the store's ingest-time cache

        def fused():
            return be_stream.topk(qs, corpus, n_bins, "jaccard", topk,
                                  corpus_fills=fills)[1]

        def materialize():
            s = be.score(qs, corpus, n_bins, "jaccard", corpus_fills=fills)
            return jax.lax.top_k(s, topk)[1]

        def auto():
            return be.topk(qs, corpus, n_bins, "jaccard", topk,
                           corpus_fills=fills)[1]

        t_fused, t_mat = _timeit_pair(fused, materialize, repeats)
        t_auto = _timeit(auto, repeats)
        auto_path = ("materialize" if c < getattr(be, "topk_crossover", 0)
                     else "fused")
        auto_vs_best = t_auto / min(t_fused, t_mat)
        assert t_auto <= auto_tolerance * min(t_fused, t_mat), (
            f"auto topk routed {c} rows to its slower arm "
            f"({auto_path}: {t_auto:.4f}s vs best {min(t_fused, t_mat):.4f}s)"
        )
        rows.append({
            "corpus_docs": int(c),
            "qps_fused_topk": queries / t_fused,
            "qps_materialize_topk": queries / t_mat,
            "qps_auto_topk": queries / t_auto,
            "fused_topk_speedup": t_mat / t_fused,
            "auto_path": auto_path,
            "auto_vs_best": auto_vs_best,
            # scoring-output HBM footprint per query batch: the O(Q·C) wall
            # the fused path removes (scores f32 + ids i32 for fused)
            "out_bytes_fused": int(queries * topk * 8),
            "out_bytes_materialized": int(queries * c * 4),
        })
    return rows


def run_fill_cache(dataset="tiny", backend="oracle", queries=16, topk=10,
                   repeats=10, seed=0, min_rows=16384):
    """Query QPS with the ingest-time fill cache on vs off.

    Measured on the dataset's corpus **tiled to >= min_rows docs** and a
    **small query batch**: the cache replaces one popcount reduction over
    every scored corpus row — O(C·W) against the scorer's O(Q·C·W) — so
    the structural saving is ~1/Q and disappears into dispatch jitter at
    large Q or small C (the PR-5 BENCH file's 0.85 was 256 rows x 64
    queries: a ~1% effect measured with ~5% noise, sign flipped). At
    16k+ rows and Q<=16 the ratio is reliably >= 1.04 (measured
    1.04-1.09) and the smoke gate asserts it stays >= 1.0."""
    from repro.core import BinSketchConfig, make_mapping
    from repro.data.synthetic import DATASETS, generate_corpus
    from repro.engine import QueryPlanner, SketchEngine

    spec = DATASETS[dataset]
    idx, lens = generate_corpus(spec, seed=seed)
    n = idx.shape[0]
    target = max(n, min_rows)
    idx = np.tile(idx, (-(-target // n), 1))[:target]
    cfg = BinSketchConfig.from_sparsity(spec.d, int(lens.max()), 0.05)
    mapping = make_mapping(cfg, jax.random.PRNGKey(0))
    planner = QueryPlanner(min_batch=8, max_batch=max(queries, 8))
    engine = SketchEngine.build(cfg, mapping, jnp.asarray(idx),
                                backend=backend, planner=planner)
    rng = np.random.default_rng(seed + 1)
    q = jnp.asarray(idx[rng.choice(len(idx), queries, replace=False)])
    t_cached, t_uncached = _timeit_pair(
        lambda: engine.query(q, topk)[1],
        lambda: engine.query(q, topk, use_fill_cache=False)[1],
        repeats,
    )
    return {
        "corpus_docs": int(len(idx)),
        "query_qps_fill_cache": queries / t_cached,
        "query_qps_no_cache": queries / t_uncached,
        "fill_cache_speedup": t_uncached / t_cached,
    }


def _clustered_corpus(rng, n_docs, n_clusters, d, nnz):
    """(n_docs, nnz) sparse docs in near-duplicate clusters: each cluster is
    one base doc with ``swap`` indices re-rolled per member — the planted
    neighborhood structure every real retrieval corpus has and uniform
    random docs lack (under uniform data *nothing* collides on a whole
    band, so a prefilter benchmark would measure an empty index)."""
    base = rng.integers(0, d, size=(n_clusters, nnz), dtype=np.int32)
    docs = base[np.arange(n_docs) % n_clusters].copy()
    swap = rng.integers(0, nnz, size=n_docs)
    docs[np.arange(n_docs), swap] = rng.integers(0, d, size=n_docs)
    return np.sort(docs, axis=1)


def run_prefilter(n_docs=1_000_000, backend="oracle", queries=64, topk=10,
                  n_bins=512, d=4096, nnz=48, cluster=12, segments=4,
                  repeats=3, seed=0, band_policy=None):
    """Banded LSH prefilter vs exhaustive scan at serving scale (§12).

    Builds a mutable engine over ``n_docs`` clustered synthetic docs —
    sketched in bulk and sealed via ``SegmentedStore.seal_sketches``, the
    ingest path for exactly this kind of backfill (a 1M-row counting head
    would cost n_docs x n_bins u16 counters for nothing) — then times
    ``query(prefilter=True)`` against ``query(prefilter=False)`` on the
    same engine and reports recall@k of the prefiltered results against
    the exhaustive ones plus the realized candidate fraction. Queries are
    fresh near-duplicates of random corpus docs, so the exhaustive top-k
    is dominated by the query's own cluster and the banding math (§12) is
    actually exercised: cluster members collide on most bands, unrelated
    docs on none."""
    from repro.core import BinSketchConfig, make_mapping
    from repro.engine import BandPolicy, QueryPlanner, SketchEngine

    rng = np.random.default_rng(seed)
    cfg = BinSketchConfig(d=d, n_bins=n_bins)
    mapping = make_mapping(cfg, jax.random.PRNGKey(0))
    policy = band_policy or BandPolicy()
    planner = QueryPlanner(min_batch=8, max_batch=max(queries, 8))
    engine = SketchEngine.build(cfg, mapping, backend=backend,
                                planner=planner, mutable=True,
                                band_policy=policy)

    n_clusters = max(n_docs // cluster, 1)
    docs = _clustered_corpus(rng, n_docs, n_clusters, d, nnz)
    seg_rows = -(-n_docs // segments)
    sketch_batch = 131072
    for s in range(0, n_docs, seg_rows):
        part = docs[s : s + seg_rows]
        sk = jnp.concatenate([
            engine.backend.sketch(cfg, mapping, jnp.asarray(part[b : b + sketch_batch]))
            for b in range(0, len(part), sketch_batch)
        ], axis=0)
        engine.store.seal_sketches(sk, backend=engine.backend)

    # queries: near-duplicates of random docs (one index re-rolled)
    pick = rng.choice(n_docs, queries, replace=False)
    q_np = docs[pick].copy()
    q_np[np.arange(queries), rng.integers(0, nnz, queries)] = rng.integers(
        0, d, queries
    )
    q = jnp.asarray(np.sort(q_np, axis=1))

    ids_ex = np.asarray(engine.query(q, topk, prefilter=False)[1])
    ids_pf = np.asarray(engine.query(q, topk, prefilter=True)[1])
    stats = dict(engine.last_prefilter_stats)
    hits = sum(
        len(set(ids_pf[i].tolist()) & set(t for t in ids_ex[i].tolist() if t >= 0))
        for i in range(queries)
    )
    denom = int((ids_ex >= 0).sum())
    recall = hits / max(denom, 1)
    cand_frac = stats["cand_rows"] / max(stats["seg_rows"], 1)

    t_pf, t_ex = _timeit_pair(
        lambda: engine.query(q, topk, prefilter=True)[1],
        lambda: engine.query(q, topk, prefilter=False)[1],
        repeats,
    )
    return {
        "corpus_docs": int(n_docs),
        "n_bins": int(n_bins),
        "queries": int(queries),
        "topk": int(topk),
        "n_bands": int(policy.n_bands),
        "max_candidate_frac": float(policy.max_candidate_frac),
        "segments": int(len(engine.store.sealed)),
        "qps_exhaustive": queries / t_ex,
        "qps_prefilter": queries / t_pf,
        "prefilter_speedup": t_ex / t_pf,
        "recall_at_k": recall,
        "candidate_fraction": cand_frac,
        "banded_segments": int(stats["banded_segments"]),
        "exhaustive_segments": int(stats["exhaustive_segments"]),
        "unindexed_segments": int(stats["unindexed_segments"]),
    }


def run_placement(dataset="tiny", backend="oracle", queries=32, topk=10,
                  repeats=3, seed=0, seal_rows=None):
    """Segment-placed vs slice-every-segment sharded query (DESIGN.md §10).

    Builds a mutable engine whose corpus spans several sealed segments
    (seal_rows defaults to n//8) plus a head, mutates it, then times
    ``query_sharded`` with segment placement (whole segments resident on
    devices; one O(k)-row all-gather per device) against the legacy path
    (every segment padded, re-sliced across the mesh and merged with its
    own collective, every query). Alongside QPS it reports the per-query
    cross-device payload both ways: the legacy path re-ships O(C) corpus
    rows + one O(Q·k·D) gather *per segment*; the placed path ships the
    replicated queries in and one O(Q·k) partial per device out — the
    resident slabs never move. Results of the two paths are asserted
    identical before timing."""
    from repro.core import BinSketchConfig, make_mapping
    from repro.data.synthetic import DATASETS, generate_corpus
    from repro.engine import QueryPlanner, SketchEngine

    spec = DATASETS[dataset]
    idx, lens = generate_corpus(spec, seed=seed)
    n = idx.shape[0]
    cfg = BinSketchConfig.from_sparsity(spec.d, int(lens.max()), 0.05)
    mapping = make_mapping(cfg, jax.random.PRNGKey(0))
    planner = QueryPlanner(min_batch=8, max_batch=max(queries, 8))
    seal_rows = seal_rows or max(n // 8, 8)

    engine = SketchEngine.build(cfg, mapping, backend=backend, planner=planner,
                                capacity=n, mutable=True, seal_rows=seal_rows)
    for s in range(0, n, seal_rows):
        engine.add(jnp.asarray(idx[s : s + seal_rows]))
    rng = np.random.default_rng(seed + 2)
    engine.delete(np.sort(rng.choice(n, n // 16, replace=False)).tolist())

    from repro.launch.mesh import make_mesh

    mesh = make_mesh((len(jax.devices()),), ("data",))
    d = len(jax.devices())
    q = jnp.asarray(idx[rng.choice(n, queries, replace=False)])

    from repro.engine.testing import assert_topk_equivalent, topk_truth

    sc_p, id_p = engine.query_sharded(mesh, "data", q, topk)
    sc_s, id_s = engine.query_sharded(mesh, "data", q, topk,
                                      use_placement=False)
    assert_topk_equivalent((sc_p, id_p), (sc_s, id_s),
                           truth=topk_truth(engine, q))

    t_placed, t_sliced = _timeit_pair(
        lambda: engine.query_sharded(mesh, "data", q, topk)[1],
        lambda: engine.query_sharded(mesh, "data", q, topk,
                                     use_placement=False)[1],
        repeats,
    )
    placement = engine._placement
    n_seg = len(engine.store.sealed)
    c_rows = sum(s.n_rows for s in engine.store.sealed)
    # cross-device bytes per query batch (analytic): the legacy path
    # re-shards every segment's rows (4·W B each + fills/ids/valid) and
    # runs one (Q, k·D) score+id gather per segment; the placed path moves
    # the replicated query sketches plus one (Q, k) partial per device
    bytes_sliced = (c_rows * (cfg.n_words * 4 + 12)
                    + n_seg * queries * topk * d * 8)
    bytes_placed = d * queries * cfg.n_words * 4 + d * queries * topk * 8
    return {
        "devices": int(d),
        "segments": int(n_seg),
        "segments_per_device": int(placement.segments_per_device),
        "corpus_docs": int(n),
        "qps_placed": queries / t_placed,
        "qps_sliced_per_segment": queries / t_sliced,
        "placed_speedup": t_sliced / t_placed,
        "payload_bytes_sliced": int(bytes_sliced),
        "payload_bytes_placed": int(bytes_placed),
        "payload_shrink": bytes_sliced / bytes_placed,
    }


def run_mutate_cycle(dataset="tiny", backend="oracle", queries=32, topk=10,
                     repeats=3, seed=0, delete_frac=0.25):
    """Mutable lifecycle: ingest -> delete -> seal+compact -> query, with the
    post-compaction query latency compared against a fresh batch build over
    the surviving docs (acceptance: within noise — ratio ~ 1.0). The
    delete phase is tombstone flips only; compaction is the pass that
    rewrites sealed bytes, so its docs/s is reported separately."""
    from repro.core import BinSketchConfig, make_mapping
    from repro.data.synthetic import DATASETS, generate_corpus
    from repro.engine import QueryPlanner, SketchEngine

    spec = DATASETS[dataset]
    idx, lens = generate_corpus(spec, seed=seed)
    n = idx.shape[0]
    cfg = BinSketchConfig.from_sparsity(spec.d, int(lens.max()), 0.05)
    mapping = make_mapping(cfg, jax.random.PRNGKey(0))
    idx_dev = jnp.asarray(idx)
    planner = QueryPlanner(min_batch=8, max_batch=max(queries, 8))
    rng = np.random.default_rng(seed + 1)
    dele = np.sort(rng.choice(n, int(round(delete_frac * n)), replace=False))
    surv = np.setdiff1d(np.arange(n), dele)

    # ---- ingest (streaming, counting head)
    def ingest():
        eng = SketchEngine.build(cfg, mapping, backend=backend, planner=planner,
                                 capacity=n, mutable=True)
        for s in range(0, n, 256):
            eng.add(idx_dev[s : s + 256])
        # realize the head buffers, not store.sketches — that property runs
        # the full live() gather and would bill materialization to ingest
        return eng.store.head.packed

    t_ingest = _timeit(ingest, repeats)

    # ---- the measured lifecycle instance
    engine = SketchEngine.build(cfg, mapping, backend=backend, planner=planner,
                                capacity=n, mutable=True)
    for s in range(0, n, 256):
        engine.add(idx_dev[s : s + 256])
    engine.seal()

    t0 = time.perf_counter()
    engine.delete(dele.tolist())  # tombstone flips, no data movement
    t_delete = time.perf_counter() - t0

    t0 = time.perf_counter()
    stats = engine.compact()
    if engine.store.sealed:  # realize the compacted segment itself —
        # store.sketches would run a second full live() gather in the window
        jax.block_until_ready(engine.store.sealed[0].sketches)
    t_compact = time.perf_counter() - t0

    # ---- post-compaction query vs fresh rebuild over survivors
    fresh = SketchEngine.build(cfg, mapping, jnp.asarray(idx[surv]),
                               backend=backend, planner=planner)
    q = jnp.asarray(idx[surv[rng.choice(len(surv), queries, replace=False)]])
    t_q_mut, t_q_fresh = _timeit_pair(
        lambda: engine.query(q, topk)[1],
        lambda: fresh.query(q, topk)[1],
        repeats,
    )

    # parity: the compacted store answers exactly like the fresh rebuild
    sc_m, id_m = engine.query(q, topk)
    sc_f, id_f = fresh.query(q, topk)
    id_f_global = np.where(np.asarray(id_f) >= 0,
                           surv[np.maximum(np.asarray(id_f), 0)], -1)
    np.testing.assert_array_equal(np.asarray(id_m), id_f_global)
    np.testing.assert_allclose(np.asarray(sc_m), np.asarray(sc_f),
                               rtol=1e-5, atol=1e-6)

    # ---- background compaction: what does serving pay while it runs?
    # same lifecycle on a twin engine, but the merge happens off-thread;
    # the query fires the moment compact() returns (the sync path would
    # still be merging) and its result must match the old segments exactly
    engine_bg = SketchEngine.build(cfg, mapping, backend=backend,
                                   planner=planner, capacity=n, mutable=True)
    for s in range(0, n, 256):
        engine_bg.add(idx_dev[s : s + 256])
    engine_bg.seal()
    engine_bg.delete(dele.tolist())
    jax.block_until_ready(engine_bg.query(q, topk)[1])  # warm the query path
    t0 = time.perf_counter()
    engine_bg.compact(background=True)
    t_launch = time.perf_counter() - t0  # snapshot-to-host: the only stall
    t0 = time.perf_counter()
    sc_bg, id_bg = engine_bg.query(q, topk)
    jax.block_until_ready(id_bg)
    t_first_query = time.perf_counter() - t0
    engine_bg.wait_compaction()
    from repro.engine.testing import assert_topk_equivalent, topk_truth
    assert_topk_equivalent((sc_bg, id_bg), (sc_m, id_m),
                           truth=topk_truth(engine, q))

    return {
        "corpus_docs": int(n),
        "deleted_docs": int(len(dele)),
        "ingest_docs_per_s": n / t_ingest,
        "delete_tombstones_per_s": len(dele) / max(t_delete, 1e-9),
        "compact_rows_in": int(stats["rows_in"]),
        "compact_rows_out": int(stats["rows_out"]),
        "compact_rows_per_s": stats["rows_in"] / max(t_compact, 1e-9),
        "query_qps_post_compaction": queries / t_q_mut,
        "query_qps_fresh_rebuild": queries / t_q_fresh,
        "post_compaction_latency_ratio": t_q_mut / t_q_fresh,
        "bg_compact_launch_s": t_launch,
        "bg_compact_sync_s": t_compact,  # what the sync path stalls for
        "bg_query_during_compaction_s": t_first_query,
    }


def run_distill(dataset="tiny", backend="oracle", queries=32, topk=10,
                seed=0, tiers=(2, 4)):
    """Segment distillation (DESIGN.md §11): bytes/doc and recall@k before
    and after each width tier, plus what serving pays for the background
    fold (launch stall = snapshot-to-host, swap stall = the poll that
    adopts the result).

    ``tiers`` are divisors of the base width: tier ``t`` re-sketches every
    sealed segment to ``N // t``. Recall is against exact Jaccard over the
    survivors (the serve driver's ground truth), so the recorded delta per
    tier is the real accuracy price of the memory saved."""
    from repro.core import BinSketchConfig, make_mapping
    from repro.data.synthetic import DATASETS, generate_corpus
    from repro.engine import QueryPlanner, SketchEngine
    from repro.launch.serve import exact_topk_jaccard

    spec = DATASETS[dataset]
    idx, lens = generate_corpus(spec, seed=seed)
    n = idx.shape[0]
    cfg = BinSketchConfig.from_sparsity(spec.d, int(lens.max()), 0.05)
    mapping = make_mapping(cfg, jax.random.PRNGKey(0))
    planner = QueryPlanner(min_batch=8, max_batch=max(queries, 8))
    seal_rows = max(n // 4, 8)

    engine = SketchEngine.build(cfg, mapping, backend=backend, planner=planner,
                                capacity=n, mutable=True, seal_rows=seal_rows)
    for s in range(0, n, seal_rows):
        engine.add(jnp.asarray(idx[s : s + seal_rows]))
    engine.seal()
    rng = np.random.default_rng(seed + 3)
    dele = np.sort(rng.choice(n, n // 16, replace=False))
    engine.delete(dele.tolist())
    surv = np.setdiff1d(np.arange(n), dele)

    q_rows = idx[surv[rng.choice(len(surv), queries, replace=False)]]
    q = jnp.asarray(q_rows)
    truth_ids = surv[exact_topk_jaccard(idx[surv], q_rows, topk)]

    def recall():
        ids = np.asarray(engine.query(q, topk)[1])
        hits = sum(len(set(ids[i].tolist()) & set(truth_ids[i].tolist()))
                   for i in range(queries))
        return hits / (queries * topk)

    def bytes_per_doc():
        store = engine.store
        sealed = sum(
            s.n_live * (((s.n_bins or cfg.n_bins) + 31) // 32) * 4
            for s in store.sealed
        )
        return sealed / max(sum(s.n_live for s in store.sealed), 1)

    out = {
        "corpus_docs": int(n),
        "n_bins_base": int(cfg.n_bins),
        "bytes_per_doc_base": bytes_per_doc(),
        "recall_base": recall(),
        "tiers": [],
    }
    for t in tiers:
        n_new = max(cfg.n_bins // int(t), 32)
        t0 = time.perf_counter()
        started = engine.distill(widths=(n_new,))  # background launch
        t_launch = time.perf_counter() - t0
        assert started
        # join the off-thread fold without adopting it (the supervisor wait
        # leaves the finished job for poll_compaction, whose swap is the
        # stall being measured)
        engine.store.supervisor.wait(engine.store._compaction.job)
        t0 = time.perf_counter()
        engine.poll_compaction()  # the swap: the only serving stall
        t_swap = time.perf_counter() - t0
        bpd, rec = bytes_per_doc(), recall()
        out["tiers"].append({
            "n_bins": int(n_new),
            "bytes_per_doc": bpd,
            "bytes_per_doc_reduction": out["bytes_per_doc_base"] / bpd,
            "recall": rec,
            "recall_delta_vs_base": rec - out["recall_base"],
            "distill_launch_ms": t_launch * 1e3,
            "swap_stall_ms": t_swap * 1e3,
        })
    return out


def run_supervision(dataset="tiny", backend="oracle", queries=32, topk=10,
                    repeats=5, seed=0):
    """Supervision/fault-injection overhead on the query hot path.

    The robustness layer (DESIGN.md §13) instruments the serving code
    permanently: every injection point is one module-global ``None`` check
    when disarmed, and the degraded-mode fallbacks add a try/except frame
    around the prefilter lookup. The claim is that this costs nothing
    measurable. Two arms, interleaved: the shipped path with no plan
    installed vs an *armed-but-quiet* :class:`~repro.faults.FaultPlan`
    (installed, zero specs — every point takes the dict-miss branch), on a
    banded mutable store so the instrumented lookup path is the one that
    runs."""
    from repro import faults
    from repro.core import BinSketchConfig, make_mapping
    from repro.data.synthetic import DATASETS, generate_corpus
    from repro.engine import BandPolicy, QueryPlanner, SketchEngine

    spec = DATASETS[dataset]
    idx, lens = generate_corpus(spec, seed=seed)
    n = idx.shape[0]
    cfg = BinSketchConfig.from_sparsity(spec.d, int(lens.max()), 0.05)
    mapping = make_mapping(cfg, jax.random.PRNGKey(0))
    planner = QueryPlanner(min_batch=8, max_batch=max(queries, 8))
    engine = SketchEngine.build(
        cfg, mapping, jnp.asarray(idx), backend=backend, planner=planner,
        mutable=True, band_policy=BandPolicy(n_bands=4, min_rows=32),
    )
    engine.seal()
    engine.compact()
    rng = np.random.default_rng(seed + 2)
    q = jnp.asarray(idx[rng.choice(n, queries, replace=False)])
    plan = faults.FaultPlan({}, seed=seed)  # armed, fires nothing

    def disarmed():
        return engine.query(q, topk)[1]

    def armed_quiet():
        faults.install(plan)
        try:
            return engine.query(q, topk)[1]
        finally:
            faults.clear()

    faults.clear()  # whatever state the caller left behind
    t_off, t_on = _timeit_pair(disarmed, armed_quiet, repeats)
    return {
        "corpus_docs": int(n),
        "query_qps_disarmed": queries / t_off,
        "query_qps_armed_quiet": queries / t_on,
        "supervision_overhead": t_on / t_off,
    }


def run_metrics_overhead(dataset="tiny", backend="oracle", queries=32,
                         topk=10, repeats=5, seed=0):
    """Telemetry-plane overhead on the banded prefilter query path.

    The observability layer (DESIGN.md §14) instruments every query
    permanently: each site is one module-global ``None`` check while
    disarmed, and an armed registry + per-query trace adds histogram
    observes and stage clocks. Two paired comparisons on the same engine,
    both interleaved: (1) disarmed vs armed-with-tracing — the full cost
    of running telemetry; (2) disarmed vs disarmed re-timed — the noise
    floor the disarmed gate must sit inside (the instrumented-but-off
    claim the CI smoke enforces at <= 1.05x)."""
    from repro import obs
    from repro.core import BinSketchConfig, make_mapping
    from repro.data.synthetic import DATASETS, generate_corpus
    from repro.engine import BandPolicy, QueryPlanner, SketchEngine

    spec = DATASETS[dataset]
    idx, lens = generate_corpus(spec, seed=seed)
    n = idx.shape[0]
    cfg = BinSketchConfig.from_sparsity(spec.d, int(lens.max()), 0.05)
    mapping = make_mapping(cfg, jax.random.PRNGKey(0))
    planner = QueryPlanner(min_batch=8, max_batch=max(queries, 8))
    engine = SketchEngine.build(
        cfg, mapping, jnp.asarray(idx), backend=backend, planner=planner,
        mutable=True, band_policy=BandPolicy(n_bands=4, min_rows=32),
    )
    engine.seal()
    engine.compact()
    rng = np.random.default_rng(seed + 2)
    q = jnp.asarray(idx[rng.choice(n, queries, replace=False)])
    inner = 8  # query calls per timed closure: amortizes dispatch jitter,
    # which at smoke shapes is larger than the per-call gate being measured

    def disarmed():
        for _ in range(inner):
            out = engine.query(q, topk)[1]
        return out

    def armed_full():
        engine.enable_metrics(sample=1)  # registry + every-query tracing
        try:
            for _ in range(inner):
                out = engine.query(q, topk)[1]
            return out
        finally:
            obs.disable()

    obs.disable()  # whatever state the caller left behind
    t_off, t_on = _timeit_pair(disarmed, armed_full, repeats)
    # the disarmed arm timed against itself (interleaved): the disarmed
    # instrumentation gate must be indistinguishable from this noise floor
    t_off_a, t_off_b = _timeit_pair(disarmed, disarmed, repeats)
    return {
        "corpus_docs": int(n),
        "query_qps_disarmed": queries * inner / t_off,
        "query_qps_armed": queries * inner / t_on,
        "metrics_overhead_armed": t_on / t_off,
        "metrics_overhead_disarmed": t_off_b / t_off_a,
    }


def run_autopilot(dataset="tiny", backend="oracle", queries=32, topk=10,
                  repeats=5, seed=0, churn_docs=16, churn_deletes=8):
    """Hands-off serving cost under sustained churn (DESIGN.md §16).

    Two identical mutable engines run the same seeded churn schedule —
    ingest a batch, delete random live docs, answer a query batch — one
    with a :class:`~repro.engine.lifecycle.LifecycleController` ticking
    every round (merges launch in the background as tiers fill), the
    other with the pre-controller operator idiom: a blocking
    ``compact()`` every 4th round. The claim is that closing the loop
    costs nothing on serving throughput: the tick itself is a host-side
    poll over lifecycle gauges, and the merges it launches run on the
    background slot serving already tolerates. Interleaved
    min-of-repeats; ``autopilot_qps_ratio`` is controller-arm QPS over
    explicit-arm QPS (>= 0.9 gated in smoke)."""
    from repro.core import BinSketchConfig, make_mapping
    from repro.data.synthetic import DATASETS, generate_corpus
    from repro.engine import (
        ControllerPolicy,
        LifecycleController,
        QueryPlanner,
        SketchEngine,
    )
    from repro.obs.clock import ManualClock

    spec = DATASETS[dataset]
    idx, lens = generate_corpus(spec, seed=seed)
    n = idx.shape[0]
    cfg = BinSketchConfig.from_sparsity(spec.d, int(lens.max()), 0.05)
    mapping = make_mapping(cfg, jax.random.PRNGKey(0))
    planner = QueryPlanner(min_batch=8, max_batch=max(queries, 8))
    seal = 24
    rng = np.random.default_rng(seed + 2)
    q = jnp.asarray(idx[rng.choice(n, queries, replace=False)])

    def build():
        clk = ManualClock()
        eng = SketchEngine.build(cfg, mapping, jnp.asarray(idx),
                                 backend=backend, planner=planner,
                                 mutable=True, seal_rows=seal, clock=clk)
        eng.seal()
        return eng, clk

    eng_on, clk_on = build()
    ctl = LifecycleController(
        eng_on,
        ControllerPolicy(tier_min_rows=seal, tier_fanout=4,
                         tombstone_density=0.5),
        clock=clk_on)
    eng_off, clk_off = build()

    window = 4  # rounds per timed closure == the explicit compact cadence,
    # so min-of-repeats amortizes each arm's maintenance identically — a
    # per-round closure would let the explicit arm's min be a
    # maintenance-free round while every controller round pays its tick

    def mk_window(eng, clk, maintain):
        # per-arm rng with one shared seed: both arms replay the same
        # mutation schedule, so the paired timing compares like for like
        arm_rng = np.random.default_rng(seed + 5)
        state = {"cursor": 0, "round": 0}

        def one_window():
            for _ in range(window):
                s = state["cursor"] % (n - churn_docs)
                state["cursor"] += churn_docs
                eng.add(jnp.asarray(idx[s : s + churn_docs]), now=clk())
                live = np.asarray(eng.store.live_ids)
                kill = min(churn_deletes, max(len(live) - queries, 0))
                if kill:
                    victims = arm_rng.choice(live, size=kill, replace=False)
                    eng.delete([int(g) for g in victims])
                out = eng.query(q, topk)[1]
                clk.advance(1.0)
                maintain(state["round"])
                state["round"] += 1
            return out

        return one_window

    on = mk_window(eng_on, clk_on, lambda r: ctl.tick(now=clk_on()))
    off = mk_window(eng_off, clk_off,
                    lambda r: eng_off.compact() if r % window == window - 1
                    else None)
    t_on, t_off = _timeit_pair(on, off, repeats)
    eng_on.store.wait_compaction()
    return {
        "corpus_docs": int(n),
        "churn_docs_per_round": int(churn_docs),
        "churn_deletes_per_round": int(churn_deletes),
        "rounds_per_window": int(window),
        "query_qps_controller": queries * window / t_on,
        "query_qps_explicit": queries * window / t_off,
        "autopilot_qps_ratio": t_off / t_on,
        "segments_controller": len(eng_on.store.sealed),
        "segments_explicit": len(eng_off.store.sealed),
        "controller_merges": int(ctl.merges),
        "controller_ticks": int(ctl.ticks),
    }


def run_analysis_time(paths=("src",), repeats=1):
    """Wall time of a full `repro.analysis` pass (all three analyzer
    families, trace checks included) over ``paths`` — the DESIGN §15 CI
    job's cost, tracked PR-over-PR so the zero-new-findings gate stays
    cheap as the repo grows. Min over ``repeats`` (the first pass pays
    jax import + engine build; repeats>1 would amortize that away and
    hide the cost CI actually pays, so the default times one cold-ish
    run)."""
    import os

    from repro.analysis import runner as analysis_runner

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    best, report = float("inf"), None
    for _ in range(max(1, repeats)):
        t0 = time.perf_counter()
        report = analysis_runner.run(root, paths=list(paths))
        best = min(best, time.perf_counter() - t0)
    return {
        "paths": list(paths),
        "files_scanned": report.files_scanned,
        "new_findings": len(report.new),
        "suppressed": len(report.suppressed),
        "errors": len(report.errors),
        "analysis_wall_s": best,
    }


def run(dataset="tiny", backend="oracle", queries=64, topk=10, repeats=5,
        seed=0, sweep_sizes=(4096, 16384, 65536), prefilter_docs=1_000_000):
    from repro.core import BinSketchConfig, make_mapping
    from repro.data.synthetic import DATASETS, generate_corpus
    from repro.engine import QueryPlanner, SketchEngine

    spec = DATASETS[dataset]
    idx, lens = generate_corpus(spec, seed=seed)
    n = idx.shape[0]
    cfg = BinSketchConfig.from_sparsity(spec.d, int(lens.max()), 0.05)
    mapping = make_mapping(cfg, jax.random.PRNGKey(0))
    idx_dev = jnp.asarray(idx)
    planner = QueryPlanner(min_batch=8, max_batch=max(queries, 8))

    # ---- ingest: one-shot batch build
    def batch_build():
        eng = SketchEngine.build(cfg, mapping, idx_dev, backend=backend, planner=planner)
        return eng.store.sketches

    t_batch = _timeit(batch_build, repeats)

    # ---- ingest: streaming adds (256-doc chunks into doubling capacity)
    def stream_build():
        eng = SketchEngine.build(cfg, mapping, backend=backend, planner=planner, capacity=64)
        for s in range(0, n, 256):
            eng.add(idx_dev[s : s + 256])
        return eng.store.sketches

    t_stream = _timeit(stream_build, repeats)

    # ---- query: fill cache on vs off, measured at a real corpus size
    # (the pair is dispatch-jitter-bound below ~4k rows; see run_fill_cache)
    fc = run_fill_cache(dataset, backend=backend, queries=min(queries, 16),
                        topk=topk, repeats=max(repeats, 10), seed=seed)

    result = {
        "dataset": dataset,
        "backend": backend,
        "corpus_docs": int(n),
        "n_bins": int(cfg.n_bins),
        "n_words": int(cfg.n_words),
        "queries": int(queries),
        "topk": int(topk),
        "ingest_batch_docs_per_s": n / t_batch,
        "ingest_stream_docs_per_s": n / t_stream,
        "fill_cache_corpus_docs": fc["corpus_docs"],
        "query_qps_fill_cache": fc["query_qps_fill_cache"],
        "query_qps_no_cache": fc["query_qps_no_cache"],
        "fill_cache_speedup": fc["fill_cache_speedup"],
    }
    if sweep_sizes:
        result["topk_sweep"] = run_topk_sweep(
            sweep_sizes, backend=backend, topk=topk, repeats=max(2, repeats - 2),
            seed=seed,
        )
        biggest = result["topk_sweep"][-1]
        result["topk_fused_speedup_largest"] = biggest["fused_topk_speedup"]
        result["topk_out_bytes_ratio_largest"] = (
            biggest["out_bytes_materialized"] / biggest["out_bytes_fused"]
        )
    result["mutate_cycle"] = run_mutate_cycle(
        dataset, backend=backend, queries=queries, topk=topk,
        repeats=max(2, repeats - 2), seed=seed,
    )
    result["placement"] = run_placement(
        dataset, backend=backend, queries=queries, topk=topk,
        repeats=max(2, repeats - 2), seed=seed,
    )
    result["distill"] = run_distill(
        dataset, backend=backend, queries=min(queries, 32), topk=topk,
        seed=seed,
    )
    result["supervision"] = run_supervision(
        dataset, backend=backend, queries=min(queries, 32), topk=topk,
        repeats=max(repeats, 5), seed=seed,
    )
    result["metrics_overhead"] = run_metrics_overhead(
        dataset, backend=backend, queries=min(queries, 32), topk=topk,
        repeats=max(repeats, 5), seed=seed,
    )
    result["autopilot"] = run_autopilot(
        dataset, backend=backend, queries=min(queries, 32), topk=topk,
        repeats=max(repeats, 5), seed=seed,
    )
    result["analysis"] = run_analysis_time()
    if prefilter_docs:
        result["prefilter"] = run_prefilter(
            n_docs=prefilter_docs, backend=backend, queries=queries,
            topk=topk, repeats=max(2, repeats - 2), seed=seed,
        )
    return result


def smoke() -> dict:
    """CI gate: tiny shapes, asserts fused-topk parity against the
    materialized score matrix on both the oracle and interpret backends."""
    from repro.engine import get_backend

    rng = np.random.default_rng(7)
    n_bins, q, c, k = 101, 8, 37, 5
    w = (n_bins + 31) // 32
    a = _rand_packed(rng, q, w)
    b = _rand_packed(rng, c, w)
    for name in ("oracle", "pallas-interpret"):
        be = get_backend(name)
        for measure in ("jaccard", "ip", "cosine", "hamming"):
            s = np.asarray(be.score(a, b, n_bins, measure))
            want_sc, want_ix = jax.lax.top_k(s, k)
            got_sc, got_ix = be.topk(a, b, n_bins, measure, k)
            got_sc, got_ix = np.asarray(got_sc), np.asarray(got_ix)
            np.testing.assert_allclose(got_sc, np.asarray(want_sc),
                                       rtol=1e-5, atol=1e-6)
            gathered = np.take_along_axis(s, got_ix, axis=1)
            np.testing.assert_allclose(gathered, got_sc, rtol=1e-5, atol=1e-6)
        # k > C padding contract
        sc, ix = be.topk(a, b, n_bins, "jaccard", c + 4)
        assert (np.asarray(sc)[:, c:] == -np.inf).all(), name
        assert (np.asarray(ix)[:, c:] == -1).all(), name
        # crossover routing parity: forced-streaming == shipped auto ==
        # materialize, on a corpus below the crossover (the routing the
        # topk_sweep asserts is never slower must also never change results)
        import copy
        be_stream = copy.copy(be)
        be_stream.topk_crossover = 0
        s_a, i_a = be.topk(a, b, n_bins, "jaccard", k)
        s_f, i_f = be_stream.topk(a, b, n_bins, "jaccard", k)
        np.testing.assert_array_equal(np.asarray(i_a), np.asarray(i_f))
        np.testing.assert_allclose(np.asarray(s_a), np.asarray(s_f),
                                   rtol=1e-5, atol=1e-6)
        print(f"smoke ok: {name}")
    _smoke_mutate_cycle()
    _smoke_fill_cache()
    _smoke_prefilter()
    _smoke_supervision()
    _smoke_metrics_overhead()
    _smoke_autopilot()
    _smoke_analysis()
    return {"smoke": "ok"}


def _smoke_analysis():
    """CI gate for the static-analysis pass itself (DESIGN.md §15): a
    full run over src/ — AST rules, ownership checker, and the
    trace-level jax checks — must come back clean and finish within 10s,
    so the `analysis` CI job stays a cheap always-on gate as the repo
    grows. (Today's full run is ~7s; most of it is the recompile guard
    building its probe engine, which is size-independent — the part that
    scales with the repo, the AST pass, is ~1s over ~100 files.) The
    gate takes min-of-2 so a transient load spike (e.g. a parallel test
    run on a dev box) can't fail it — the tracked PR-over-PR number in
    ``run()`` stays a single cold pass, the cost CI actually pays."""
    az = run_analysis_time(repeats=2)
    assert az["errors"] == 0, "analyzer reported internal errors"
    assert az["new_findings"] == 0, (
        f"analyzer found {az['new_findings']} new finding(s) — run "
        f"`python -m repro.analysis` for the list"
    )
    assert az["analysis_wall_s"] <= 10.0, (
        f"full analysis pass took {az['analysis_wall_s']:.1f}s over "
        f"{az['files_scanned']} files — budget is 10s; profile the rules "
        f"or shrink the trace-check shapes"
    )
    print(f"smoke ok: analysis clean in {az['analysis_wall_s']:.2f}s over "
          f"{az['files_scanned']} files ({az['suppressed']} baselined)")


def _smoke_fill_cache():
    """CI gate for the fill cache: at a shape where the saving is
    structural (16k rows, 8 queries, min-of-repeats), the cache must not
    lose."""
    fc = run_fill_cache(queries=8, repeats=10)
    assert fc["fill_cache_speedup"] >= 1.0, (
        f"fill cache slower than recompute at {fc['corpus_docs']} rows: "
        f"{fc['fill_cache_speedup']:.3f}"
    )
    print(f"smoke ok: fill-cache speedup {fc['fill_cache_speedup']:.2f} "
          f"@ {fc['corpus_docs']} rows")


def _smoke_prefilter():
    """CI gate for the banded prefilter (§12): on a clustered corpus at the
    default BandPolicy, prefiltered recall@k against the exhaustive scan
    must hold the floor and the candidate union must stay a small fraction
    of the scanned segments — the sublinearity claim, asserted cheaply."""
    pf = run_prefilter(n_docs=8192, queries=32, segments=2, repeats=2)
    assert pf["recall_at_k"] >= 0.95, f"prefilter recall {pf['recall_at_k']:.3f}"
    assert pf["candidate_fraction"] <= 0.25, (
        f"candidate fraction {pf['candidate_fraction']:.3f} above ceiling"
    )
    assert pf["banded_segments"] > 0, "prefilter never engaged"
    print(f"smoke ok: prefilter recall {pf['recall_at_k']:.3f}, "
          f"candidate fraction {pf['candidate_fraction']:.4f}, "
          f"speedup {pf['prefilter_speedup']:.1f}x @ {pf['corpus_docs']} docs")


def _smoke_supervision():
    """CI gate for the robustness layer's overhead claim: an installed but
    quiet FaultPlan (the most instrumentation a fault-free process ever
    pays for) must keep query latency within noise of the shipped
    disarmed path. Min-of-repeats over interleaved arms; the margin
    absorbs dispatch jitter at smoke shapes, not a real regression — the
    per-point cost is one module-global check."""
    sv = run_supervision(queries=16, repeats=10)
    assert sv["supervision_overhead"] <= 1.25, (
        f"armed-but-quiet fault plan cost {sv['supervision_overhead']:.3f}x "
        f"on the query path @ {sv['corpus_docs']} docs"
    )
    print(f"smoke ok: supervision overhead {sv['supervision_overhead']:.3f}x "
          f"@ {sv['corpus_docs']} docs")


def _smoke_metrics_overhead():
    """CI gate for the telemetry plane's overhead budget (DESIGN.md §14):
    disarmed, the instrumented query path must be indistinguishable from
    noise (<= 1.05x against itself, min-of-repeats interleaved); armed
    with every-query tracing it must stay within 1.25x on the banded
    prefilter path at smoke shapes. The margins absorb dispatch jitter —
    per-site cost while disarmed is one module-global None check."""
    mo = run_metrics_overhead(queries=16, repeats=10)
    assert mo["metrics_overhead_disarmed"] <= 1.05, (
        f"disarmed telemetry gate cost "
        f"{mo['metrics_overhead_disarmed']:.3f}x on the query path "
        f"@ {mo['corpus_docs']} docs"
    )
    assert mo["metrics_overhead_armed"] <= 1.25, (
        f"armed telemetry (registry + tracing) cost "
        f"{mo['metrics_overhead_armed']:.3f}x on the query path "
        f"@ {mo['corpus_docs']} docs"
    )
    print(f"smoke ok: metrics overhead disarmed "
          f"{mo['metrics_overhead_disarmed']:.3f}x / armed "
          f"{mo['metrics_overhead_armed']:.3f}x @ {mo['corpus_docs']} docs")


def _smoke_autopilot():
    """CI gate for hands-off serving (DESIGN.md §16): under the paired
    churn schedule, the controller-driven arm must hold >= 0.9x the QPS
    of the explicit-maintenance baseline (the tick is a host-side poll;
    its merges ride the background slot), and its ticks must actually
    have engaged — a controller that never merges isn't exercising the
    claim. Min-of-repeats over interleaved arms; the margin absorbs
    dispatch jitter at smoke shapes."""
    ap = run_autopilot(queries=16, repeats=5)
    assert ap["autopilot_qps_ratio"] >= 0.9, (
        f"controller-on serving at {ap['autopilot_qps_ratio']:.3f}x the "
        f"explicit-maintenance baseline @ {ap['corpus_docs']} docs"
    )
    assert ap["controller_merges"] >= 1, "controller never merged under churn"
    print(f"smoke ok: autopilot qps ratio {ap['autopilot_qps_ratio']:.3f} "
          f"({ap['controller_merges']} merge(s) over "
          f"{ap['controller_ticks']} ticks, "
          f"{ap['segments_controller']} segments vs "
          f"{ap['segments_explicit']} explicit)")


def _smoke_mutate_cycle():
    """CI gate for the mutable lifecycle: an ingest -> delete -> update ->
    seal -> compact sequence on the segmented store must answer queries
    exactly like a fresh batch build over the surviving docs, on both the
    oracle and interpret backends."""
    from repro.core import BinSketchConfig, make_mapping
    from repro.data.synthetic import DATASETS, generate_corpus
    from repro.engine import SketchEngine

    spec = DATASETS["tiny"]
    idx, lens = generate_corpus(spec, seed=3)
    n = 64
    cfg = BinSketchConfig.from_sparsity(spec.d, int(lens.max()), 0.05)
    mapping = make_mapping(cfg, jax.random.PRNGKey(0))
    for name in ("oracle", "pallas-interpret"):
        eng = SketchEngine.build(cfg, mapping, jnp.asarray(idx[:n]),
                                 backend=name, mutable=True)
        eng.seal()
        eng.delete([1, 17, 40])
        eng.update([5, 23], jnp.asarray(idx[n : n + 2]))
        eng.add(jnp.asarray(idx[n + 2 : n + 6]))
        eng.seal()
        eng.compact()

        contents = {i: idx[i] for i in range(n)}
        for g in (1, 17, 40):
            contents.pop(g)
        contents[5], contents[23] = idx[n], idx[n + 1]
        for j in range(4):
            contents[n + j] = idx[n + 2 + j]
        surv = np.asarray(sorted(contents))
        fresh = SketchEngine.build(
            cfg, mapping, jnp.asarray(np.stack([contents[int(g)] for g in surv])),
            backend=name,
        )
        q = jnp.asarray(idx[:8])
        sc_m, id_m = eng.query(q, 5)
        sc_f, id_f = fresh.query(q, 5)
        id_f = np.where(np.asarray(id_f) >= 0,
                        surv[np.maximum(np.asarray(id_f), 0)], -1)
        np.testing.assert_array_equal(np.asarray(id_m), id_f)
        np.testing.assert_allclose(np.asarray(sc_m), np.asarray(sc_f),
                                   rtol=1e-5, atol=1e-6)
        # segment-placed sharded path answers identically (mesh of whatever
        # devices the CI box has — usually 1; the 8-device runs live in the
        # multidevice test suite); ids exact up to provable score ties
        from repro.engine.testing import assert_topk_equivalent, topk_truth

        from repro.launch.mesh import make_mesh

        mesh = make_mesh((len(jax.devices()),), ("data",))
        sc_p, id_p = eng.query_sharded(mesh, "data", q, 5)
        assert_topk_equivalent((sc_p, id_p), (sc_m, id_m),
                               truth=topk_truth(eng, q))
        print(f"smoke ok: mutate-cycle {name}")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="tiny")
    ap.add_argument("--backend", default="oracle")
    ap.add_argument("--queries", type=int, default=64)
    ap.add_argument("--topk", type=int, default=10)
    ap.add_argument("--repeats", type=int, default=10)
    ap.add_argument("--sweep-sizes", default="4096,16384,65536",
                    help="comma-separated corpus sizes for the fused-topk "
                         "sweep; empty string disables it")
    ap.add_argument("--prefilter-docs", type=int, default=1_000_000,
                    help="synthetic corpus size for the banded-prefilter "
                         "arm; 0 disables it")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny-shape fused-topk parity assert (CI); no json")
    ap.add_argument("--out", default="BENCH_engine.json")
    args = ap.parse_args(argv)

    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    if args.smoke:
        return smoke()

    sizes = tuple(int(s) for s in args.sweep_sizes.split(",") if s)
    t0 = time.perf_counter()
    result = run(args.dataset, args.backend, args.queries, args.topk,
                 args.repeats, sweep_sizes=sizes,
                 prefilter_docs=args.prefilter_docs)
    result["wall_s"] = time.perf_counter() - t0
    with open(args.out, "w") as f:
        json.dump(result, f, indent=2)
    print("metric,value")
    for k in ("ingest_batch_docs_per_s", "ingest_stream_docs_per_s",
              "query_qps_fill_cache", "query_qps_no_cache", "fill_cache_speedup"):
        print(f"{k},{result[k]:.1f}")
    for row in result.get("topk_sweep", ()):
        print(f"topk_fused_speedup@{row['corpus_docs']},"
              f"{row['fused_topk_speedup']:.2f}")
        print(f"topk_auto_path@{row['corpus_docs']},"
              f"{row['auto_path']}:{row['auto_vs_best']:.2f}")
    mut = result.get("mutate_cycle", {})
    for k in ("ingest_docs_per_s", "delete_tombstones_per_s",
              "compact_rows_per_s", "query_qps_post_compaction",
              "post_compaction_latency_ratio", "bg_compact_launch_s",
              "bg_compact_sync_s", "bg_query_during_compaction_s"):
        if k in mut:
            print(f"mutate_{k},{mut[k]:.4f}")
    plc = result.get("placement", {})
    for k in ("qps_placed", "qps_sliced_per_segment", "placed_speedup",
              "payload_shrink"):
        if k in plc:
            print(f"placement_{k},{plc[k]:.2f}")
    az = result.get("analysis", {})
    if az:
        print(f"analysis_wall_s,{az['analysis_wall_s']:.2f}")
        print(f"analysis_new_findings,{az['new_findings']}")
    pf = result.get("prefilter", {})
    for key in ("qps_exhaustive", "qps_prefilter", "prefilter_speedup",
                "recall_at_k", "candidate_fraction"):
        if key in pf:
            print(f"prefilter_{key},{pf[key]:.4f}")
    sv = result.get("supervision", {})
    for key in ("query_qps_disarmed", "query_qps_armed_quiet",
                "supervision_overhead"):
        if key in sv:
            print(f"supervision_{key},{sv[key]:.4f}")
    ap = result.get("autopilot", {})
    for key in ("query_qps_controller", "query_qps_explicit",
                "autopilot_qps_ratio", "segments_controller",
                "segments_explicit", "controller_merges"):
        if key in ap:
            print(f"autopilot_{key},{ap[key]:.4f}")
    dst = result.get("distill", {})
    for tier in dst.get("tiers", ()):
        print(f"distill_bytes_reduction@N={tier['n_bins']},"
              f"{tier['bytes_per_doc_reduction']:.2f}")
        print(f"distill_recall_delta@N={tier['n_bins']},"
              f"{tier['recall_delta_vs_base']:+.3f}")
        print(f"distill_swap_stall_ms@N={tier['n_bins']},"
              f"{tier['swap_stall_ms']:.1f}")
    print(f"# bench_engine done in {result['wall_s']:.1f}s -> {args.out}")
    return result


if __name__ == "__main__":
    main()
