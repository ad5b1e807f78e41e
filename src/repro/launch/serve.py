"""Sketch-serving driver — the paper's native workload as a service.

    PYTHONPATH=src python -m repro.launch.serve --dataset tiny --queries 64
    PYTHONPATH=src python -m repro.launch.serve --mutate-rate 0.3   # live catalog
    PYTHONPATH=src python -m repro.launch.serve --chaos 0.3         # fault demo

Runs on :class:`repro.engine.SketchEngine`. Build phase: the corpus streams
into the store in ``--ingest-batch`` chunks (incremental ingest; fill
counts enter the cache here, once). With ``--mutate-rate r`` the engine is
built over a :class:`~repro.engine.segments.SegmentedStore` (counting head
+ sealed segments, DESIGN.md §9) and a **mutation phase** runs before
serving: half of ``r·n`` docs are deleted (tombstones), half updated in
place with fresh content (counter overwrite / LSM relocation), then the
head is sealed and the sealed segments compacted — no rebuild at any
point. Serve phase: ragged query batches are bucketed by the engine's
planner onto a bounded set of jit shapes, sketched, and streamed through
the fused top-k per segment. Reports build/mutate/serve throughput and
recall@k against exact Jaccard over the *surviving* documents — the
paper's ranking experiment (§IV-B) as a live, mutable service.

Exit status: besides the probe and autopilot gates, a run that was not
asked to inject faults (no ``--chaos``) exits non-zero when the engine's
health shows a fault that a fallback served through (a degraded
component, a failed / abandoned / quarantined job — ``health_faults``):
the fallbacks keep answers correct, so without this gate a kernel the
device refused would still exit 0.
"""

from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np


# Ground truth lives with the telemetry plane now (repro.obs.probe) so the
# online recall probe and this driver's final report share one
# implementation; the old name stays as a re-export for callers
# (bench_engine imports it).
from repro.obs.probe import exact_topk as exact_topk_jaccard  # noqa: E402


@dataclasses.dataclass
class ServeRun:
    """What a serve run leaves behind for a caller that drives it in-process
    (``chip_smoke.py``): the engine and recall@k against exact Jaccard over
    the surviving catalog."""

    engine: Any  # repro.engine.SketchEngine
    recall: Optional[float]


def main(argv=None) -> ServeRun:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="tiny")
    ap.add_argument("--queries", type=int, default=64)
    ap.add_argument("--topk", type=int, default=10)
    ap.add_argument("--rho", type=float, default=0.05)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--ingest-batch", type=int, default=1024,
                    help="streaming ingest chunk size (docs per add)")
    ap.add_argument("--backend", default="auto",
                    help="engine backend: auto | oracle | pallas | pallas-tpu | pallas-interpret")
    ap.add_argument("--mutate-rate", type=float, default=0.0,
                    help="fraction of the corpus mutated before serving "
                         "(half deleted, half updated); > 0 builds the "
                         "mutable segmented store")
    ap.add_argument("--seal-rows", type=int, default=None,
                    help="auto-seal the counting head at this many rows "
                         "(mutable store only)")
    ap.add_argument("--sharded", action="store_true",
                    help="serve via query_sharded over a mesh of all local "
                         "devices: segment-placed on a mutable store "
                         "(segment = shard unit, resident slabs), "
                         "row-sliced on an append-only one")
    ap.add_argument("--background-compact", action="store_true",
                    help="mutable store: run the post-mutation compaction "
                         "as a background job and serve the first query "
                         "batches while it is still merging")
    ap.add_argument("--ttl", type=float, default=None,
                    help="mutable store: lazy TTL (in ingest-batch ticks) — "
                         "docs older than this at serve time drop out of "
                         "results via the query-time mask, no sweep")
    ap.add_argument("--distill", default=None, metavar="N1,N2,...",
                    help="mutable store: after the mutation phase, distill "
                         "sealed segments down the given width tiers "
                         "(DESIGN.md §11) and serve mixed-width; recall is "
                         "then the distilled corpus's recall")
    ap.add_argument("--distill-age", type=float, default=None,
                    help="only distill segments whose youngest live doc is "
                         "at least this many ticks old (default: all sealed "
                         "segments are eligible)")
    ap.add_argument("--prefilter", action="store_true",
                    help="mutable store: arm the banded LSH prefilter "
                         "(DESIGN.md §12) — sealed segments grow bucket "
                         "indexes and queries scan only colliding buckets; "
                         "recall is then the prefiltered recall")
    ap.add_argument("--bands", type=int, default=8,
                    help="bands per sketch for --prefilter (more bands = "
                         "higher recall, larger candidate unions)")
    ap.add_argument("--chaos", type=float, default=None, metavar="RATE",
                    help="fault-injection demo (DESIGN.md §13): arm a seeded "
                         "FaultPlan firing at this per-hit probability on "
                         "the maintenance and query-path injection points, "
                         "run supervised background compaction and "
                         "checkpoint saves during the serve loop, then "
                         "report injected / recovered / quarantined counts, "
                         "the restore walk-back, and recall under faults. "
                         "Implies --mutate-rate 0.3 and --prefilter unless "
                         "given explicitly")
    ap.add_argument("--chaos-seed", type=int, default=1234,
                    help="FaultPlan seed for --chaos (CI pins this so a "
                         "failure reproduces locally from the seed alone)")
    ap.add_argument("--autopilot", action="store_true",
                    help="hands-off mode (DESIGN.md §16): attach a "
                         "LifecycleController and tick it once per query "
                         "batch — size-tiered merges, the distill ladder "
                         "and the recall guardrail run from observed "
                         "telemetry, no explicit compact/distill calls. "
                         "Implies a mutable store; per-batch mutation churn "
                         "(--churn-docs) exercises the loop")
    ap.add_argument("--churn-docs", type=int, default=8, metavar="K",
                    help="--autopilot: per query batch, delete K/2 live "
                         "docs and ingest K fresh ones (sustained churn "
                         "the controller must absorb; 0 = no churn)")
    ap.add_argument("--autopilot-fanout", type=int, default=4,
                    help="--autopilot: segments per size tier before that "
                         "tier merges (ControllerPolicy.tier_fanout)")
    ap.add_argument("--autopilot-distill", default=None, metavar="N1,N2,...",
                    help="--autopilot: width ladder for controller-driven "
                         "distillation (default: distillation off)")
    ap.add_argument("--autopilot-budget", type=int, default=None,
                    metavar="BYTES",
                    help="--autopilot: sealed-slab memory budget gating the "
                         "distill ladder (default: pressure unconditional "
                         "once a ladder is given)")
    ap.add_argument("--autopilot-max-segments", type=int, default=None,
                    help="gate: nonzero exit if the sealed segment count "
                         "ends above this (the bounded-segment-count claim, "
                         "CI-checked)")
    ap.add_argument("--check-recall", action="store_true", default=True)
    ap.add_argument("--metrics-json", default=None, metavar="PATH",
                    help="write the final SketchEngine.metrics() snapshot "
                         "(DESIGN.md §14) to this file as JSON")
    ap.add_argument("--stats-every", type=int, default=0, metavar="N",
                    help="print a one-line telemetry summary every N query "
                         "batches during the serve loop (0 = off)")
    ap.add_argument("--probe", type=int, default=0, metavar="Q",
                    help="after serving, run the online recall probe "
                         "(repro.obs.probe) over up to Q of the serve "
                         "queries on a supervised background job and report "
                         "the probe.recall gauge (0 = off); a probe that "
                         "gets no reading exits nonzero")
    ap.add_argument("--probe-baseline", type=float, default=None,
                    help="expected probe recall; with --probe-tol this "
                         "turns the probe into a gate (nonzero exit on "
                         "violation) — CI pins the fault-free baseline here")
    ap.add_argument("--probe-tol", type=float, default=0.02,
                    help="allowed |probe recall - baseline| for "
                         "--probe-baseline")
    args = ap.parse_args(argv)

    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    chaos = args.chaos is not None and args.chaos > 0.0
    if chaos:
        if args.mutate_rate == 0.0:
            args.mutate_rate = 0.3  # chaos needs a mutable lifecycle to fault
        args.prefilter = True  # exercise band.build / band.lookup degradation

    from repro.core import BinSketchConfig, make_mapping
    from repro.data.synthetic import DATASETS, generate_corpus
    from repro.engine import BandPolicy, QueryPlanner, SketchEngine

    spec = DATASETS[args.dataset]
    idx, lens = generate_corpus(spec, seed=0)
    n = idx.shape[0]
    if args.autopilot and args.seal_rows is None:
        # hands-off mode needs segments to manage; a never-sealing head
        # would give the controller nothing to do
        args.seal_rows = max(n // 16, 64)
    mutable = (args.mutate_rate > 0.0 or args.ttl is not None
               or args.distill is not None or args.prefilter
               or args.autopilot)
    print(f"corpus: {n} docs, d={spec.d}, psi={spec.max_nnz}"
          + (f", mutate-rate={args.mutate_rate}" if mutable else ""))

    cfg = BinSketchConfig.from_sparsity(spec.d, int(lens.max()), args.rho)
    print(f"sketch: N={cfg.n_bins} bins ({cfg.n_words} words, "
          f"{cfg.n_words * 4} B/doc vs {int(lens.mean()) * 4} B raw avg)")
    mapping = make_mapping(cfg, jax.random.PRNGKey(0))

    supervisor = None
    if chaos:
        from repro.engine import JobSupervisor, SupervisionPolicy

        supervisor = JobSupervisor(SupervisionPolicy(
            max_retries=3, backoff_base=0.02, backoff_cap=0.2,
            deadline=60.0, quarantine_after=3, probation=5.0,
        ))
    engine = SketchEngine.build(
        cfg, mapping,
        backend=args.backend,
        planner=QueryPlanner(min_batch=8, max_batch=max(args.batch, 8)),
        capacity=n,
        mutable=mutable,
        seal_rows=args.seal_rows,
        ttl=args.ttl,
        # chaos lowers min_rows so the demo corpus's small segments get
        # band indexes at all — otherwise band.build/band.lookup faults
        # would never be reached on the tiny dataset
        band_policy=(BandPolicy(n_bands=args.bands,
                                min_rows=64 if chaos else 256)
                     if args.prefilter else None),
        supervisor=supervisor,
    )
    # arm the telemetry plane (module-global registry + sampled traces,
    # DESIGN.md §14): every query below lands in the stage histograms and
    # the final report / --metrics-json read from one snapshot
    from repro import obs

    engine.enable_metrics()
    if args.prefilter:
        pol = engine.store.band_policy
        print(f"prefilter: {pol.n_bands} bands, escape hatch at "
              f"{pol.max_candidate_frac:.0%} candidates, segments under "
              f"{pol.min_rows} rows stay unindexed")
    t0 = time.perf_counter()
    idx_dev = jnp.asarray(idx)
    # the lifecycle clock ticks once per ingest batch: born stamps, the
    # mutation phase, and lazy TTL expiry all measure age in these ticks
    tick = 0
    born = {}
    for s in range(0, n, args.ingest_batch):  # streaming ingest
        ids = engine.add(idx_dev[s : s + args.ingest_batch], now=float(tick))
        if mutable:
            born.update({int(g): tick for g in ids})
        tick += 1
    # realize the ingest buffers themselves; store.sketches on a mutable
    # store would run a full live() gather and bill it to the build time
    jax.block_until_ready(engine.store.head.packed if mutable
                          else engine.store.sketches)
    t_build = time.perf_counter() - t0
    print(f"build: {t_build:.2f}s ({n / t_build:.0f} docs/s, "
          f"backend={engine.backend.name}, fill cache primed at ingest)")

    mesh = axis = None
    if args.sharded:
        from repro.launch.mesh import make_mesh

        mesh = make_mesh((len(jax.devices()),), ("data",))
        axis = "data"
        print(f"sharded serve: {len(jax.devices())} device(s)"
              + (", segment-placed (resident slabs, head replicated)"
                 if mutable else ", row-sliced single slab"))

    serve_now = None
    if mutable:
        # content per live doc id — mutations keep this in sync so the
        # exact-recall ground truth is computed over the surviving catalog
        contents = {i: idx[i] for i in range(n)}
        rng = np.random.default_rng(7)
        n_mut = int(round(args.mutate_rate * n))
        victims = rng.choice(n, n_mut, replace=False) if n_mut else np.array([], int)
        dele, upd = victims[: n_mut // 2], victims[n_mut // 2 :]
        fresh_idx, _ = generate_corpus(spec, seed=1)

        t0 = time.perf_counter()
        engine.seal()  # freeze the build; deletions hit tombstone bitmaps
        if len(dele):
            engine.delete(dele.tolist())
        if len(upd):
            engine.update(upd.tolist(), jnp.asarray(fresh_idx[upd]), now=float(tick))
        engine.seal()
        if chaos:
            # compaction is deferred into the chaos serve loop below: the
            # merge must launch *after* the FaultPlan is armed so the
            # injected failures hit it deterministically (launching first
            # and arming second would race the worker past the fault point)
            stats = None
        elif args.background_compact:
            # snapshot-to-host happens here; the merge runs on the worker
            # thread while the serve phase below answers queries against
            # the old segments — the swap lands at whichever query batch
            # finds the job done
            engine.compact(background=True)
            stats = None
        elif mesh is not None:
            # sharded serving: place the sealed segments first, so the
            # merge runs device-locally — one output segment per device
            # (DESIGN.md §10) instead of one global slab on one device
            engine.place(mesh, axis)
            engine.compact(background=True)
            stats = engine.wait_compaction()
        else:
            stats = engine.compact()
            if engine.store.sealed:
                jax.block_until_ready(engine.store.sealed[0].sketches)
        t_mut = time.perf_counter() - t0
        for g in dele:
            contents.pop(int(g))
            born.pop(int(g))
        for g in upd:
            contents[int(g)] = fresh_idx[g]
            born[int(g)] = tick
        compacted = (f"compacted {stats['rows_in']}->{stats['rows_out']} rows"
                     if stats else "compaction deferred to chaos loop"
                     if chaos else "compaction running in background"
                     if args.background_compact else "nothing to compact")
        print(f"mutate: {len(dele)} deleted, {len(upd)} updated, sealed + "
              f"{compacted} in {t_mut:.2f}s "
              f"({n_mut / max(t_mut, 1e-9):.0f} mutations/s); "
              f"live={engine.store.size}")

        if args.distill:
            from repro.engine import DistillPolicy

            widths = tuple(int(w) for w in args.distill.split(",") if w)
            policy = DistillPolicy(widths=widths, min_age=args.distill_age)
            t0 = time.perf_counter()
            n_tiers = 0  # one pass per tier: segments walk down the ladder;
            # distill() returns swap stats (truthy) per pass, False once
            # nothing is eligible anymore
            while engine.distill(policy, now=float(tick), background=False):
                n_tiers += 1
            t_dist = time.perf_counter() - t0
            store = engine.store
            by_w = {}
            live_bytes = sealed_live = 0
            for seg in store.sealed:
                w = seg.n_bins or cfg.n_bins
                by_w[w] = by_w.get(w, 0) + 1
                live_bytes += seg.n_live * ((w + 31) // 32) * 4
                sealed_live += seg.n_live
            print(f"distill: {n_tiers} tier pass(es) in {t_dist:.2f}s -> "
                  f"segments by width {sorted(by_w.items(), reverse=True)}, "
                  f"{live_bytes / max(sealed_live, 1):.1f} B/doc over "
                  f"{sealed_live} sealed docs (base width: "
                  f"{cfg.n_words * 4} B/doc); serving is mixed-width from here")

        serve_now = float(tick + 1)
        if args.ttl is not None:  # lazily expired docs leave the catalog too
            dead = [g for g, b in born.items() if b + args.ttl <= serve_now]
            for g in dead:
                contents.pop(g)
                born.pop(g)
            print(f"ttl: {len(dead)} docs older than {args.ttl} ticks at "
                  f"serve time (now={serve_now}) masked lazily — no sweep ran")
        surv_ids = np.asarray(sorted(contents))
        surv_rows = np.stack([contents[int(g)] for g in surv_ids])
    else:  # no mutation phase: the catalog is the corpus, verbatim
        surv_ids, surv_rows = np.arange(n), idx

    controller = None
    churn_rng = churn_pool = None
    churn_cursor = 0
    if args.autopilot:
        from repro.engine import ControllerPolicy, LifecycleController
        from repro.obs.probe import RecallProbe

        ap_widths = (tuple(int(w) for w in args.autopilot_distill.split(",") if w)
                     if args.autopilot_distill else ())
        cpolicy = ControllerPolicy(
            tier_min_rows=max(args.seal_rows, 1),
            tier_fanout=args.autopilot_fanout,
            distill_widths=ap_widths,
            memory_budget=args.autopilot_budget,
            # ages are measured in ingest/batch ticks here, like TTL
            cold_age=4.0,
            probe_baseline=args.probe_baseline,
            probe_tol=args.probe_tol,
            probe_interval=4.0 if args.probe else None,
        )
        probe = (RecallProbe(engine, k=args.topk, sample=args.probe, seed=0)
                 if args.probe else None)

        def _catalog():
            ids_ = np.asarray(sorted(contents))
            return ids_, np.stack([contents[int(g)] for g in ids_])

        controller = LifecycleController(engine, cpolicy, probe=probe,
                                         probe_feed=_catalog)
        churn_rng = np.random.default_rng(5)
        churn_pool, _ = generate_corpus(spec, seed=2)
        print(f"autopilot: controller armed (tier_min_rows="
              f"{cpolicy.tier_min_rows}, fanout={cpolicy.tier_fanout}, "
              f"distill={list(ap_widths) or 'off'}, "
              f"churn={args.churn_docs} docs/batch, "
              f"probe={'on' if probe else 'off'})")

    rng = np.random.default_rng(1)
    n_queries = min(args.queries, len(surv_ids))
    if n_queries < args.queries:
        print(f"(clamping --queries {args.queries} -> {n_queries}: "
              f"only {len(surv_ids)} docs survive the mutation phase)")
    args.queries = n_queries
    q_pick = rng.choice(len(surv_ids), args.queries, replace=False)
    queries = surv_rows[q_pick]


    chaos_mgr = chaos_dir = chaos_plan = None
    chaos_saves = 0
    if chaos:
        import shutil
        import tempfile

        from repro import faults
        from repro.checkpoint.manager import CheckpointManager

        chaos_dir = tempfile.mkdtemp(prefix="repro-chaos-ckpt-")
        chaos_mgr = CheckpointManager(chaos_dir, keep=8, supervisor=supervisor)
        # one clean generation before the plan arms: the restore walk-back
        # below is then guaranteed a verifying floor to land on, however
        # many of the under-fire saves get torn
        engine.store.save(chaos_mgr, step=1, blocking=True)
        chaos_saves = 1
        rate = min(args.chaos, 1.0)
        chaos_plan = faults.install(faults.FaultPlan({
            "compact.work": faults.FaultSpec("raise", p=rate),
            "distill.work": faults.FaultSpec("raise", p=rate),
            "band.build": faults.FaultSpec("raise", p=rate),
            "band.lookup": faults.FaultSpec("raise", p=rate),
            "placement.build": faults.FaultSpec("raise", p=rate),
            "placement.refresh": faults.FaultSpec("raise", p=rate),
            "checkpoint.write": faults.FaultSpec("raise", p=rate),
            "checkpoint.leaf": faults.FaultSpec("torn-write", p=rate),
        }, seed=args.chaos_seed))
        engine.compact(background=True)  # merges under fire, supervised
        print(f"chaos: plan armed at rate={rate} seed={args.chaos_seed}; "
              f"deferred compaction launched under faults; checkpoints in "
              f"{chaos_dir}")

    t0 = time.perf_counter()
    all_ids = []
    for bi, s in enumerate(range(0, args.queries, args.batch)):
        if chaos:
            # the maintenance heartbeat a real server would run: drive the
            # supervised compaction (retries/backoff land here; never
            # raises into serving) and overlap async checkpoint saves
            engine.poll_compaction()
            if s // args.batch in (1, 3, 5):
                chaos_saves += 1
                engine.store.save(chaos_mgr, step=chaos_saves,
                                  blocking=False)
        if controller is not None:
            now_bi = float(serve_now + bi)
            if args.churn_docs:
                # sustained churn: the mutation stream the controller must
                # absorb without segment count growing unboundedly
                live = sorted(contents)
                k_del = min(args.churn_docs // 2,
                            max(len(live) - args.topk, 0))
                if k_del > 0:
                    dead = churn_rng.choice(live, k_del, replace=False)
                    engine.delete([int(g) for g in dead])
                    for g in dead:
                        contents.pop(int(g))
                        born.pop(int(g), None)
                take = churn_pool[churn_cursor : churn_cursor + args.churn_docs]
                if len(take):
                    new_ids = engine.add(jnp.asarray(take), now=now_bi)
                    for j, g in enumerate(new_ids):
                        contents[int(g)] = take[j]
                        born[int(g)] = now_bi
                    churn_cursor += len(take)
            controller.tick(now=now_bi)
        qb = jnp.asarray(queries[s : s + args.batch])
        if mesh is not None:
            scores, ids = engine.query_sharded(mesh, axis, qb, args.topk,
                                               now=serve_now)
        else:
            scores, ids = engine.query(qb, args.topk, now=serve_now)
        all_ids.append(np.asarray(ids))
        if args.stats_every and (bi + 1) % args.stats_every == 0:
            snap = obs.metrics.active().snapshot()
            qh = snap["histograms"].get(
                "query.query_sharded_s" if mesh is not None
                else "query.query_s", {})
            deg = sum(v for k_, v in snap["counters"].items()
                      if k_.startswith("degraded."))
            cf = snap["histograms"].get("query.candidate_frac", {})
            print(f"stats: batch {bi + 1}: "
                  f"calls={snap['counters'].get('query.calls', 0)} "
                  f"rows={snap['counters'].get('query.rows', 0)} "
                  f"p50={qh.get('p50', 0.0) * 1e3:.1f}ms "
                  f"p99={qh.get('p99', 0.0) * 1e3:.1f}ms "
                  f"cand_frac={cf.get('mean', float('nan')):.3f} "
                  f"degraded={deg}")
    ids = np.concatenate(all_ids)
    t_serve = time.perf_counter() - t0
    print(f"serve: {args.queries} queries in {t_serve:.2f}s "
          f"({args.queries / t_serve:.0f} q/s, batch={args.batch})")
    autopilot_ok = True
    if controller is not None:
        # settle: drain the action cascade (a merge can unblock the next
        # tier) so the segment-count gate measures steady state, then
        # refresh the catalog — churn moved it under the probe/recall
        settle_now = float(serve_now + args.queries / max(args.batch, 1) + 1)
        for i in range(4):
            engine.store.wait_compaction()  # supervised: never raises
            r = controller.tick(now=settle_now + i)
            if r is None or r["action"] is None:
                break
        engine.store.wait_compaction()
        surv_ids = np.asarray(sorted(contents))
        surv_rows = np.stack([contents[int(g)] for g in surv_ids])
        cs = controller.controller_state()
        nseg = len(engine.store.sealed)
        print(f"autopilot: {cs['ticks']} tick(s): {cs['merges']} merge(s), "
              f"{cs['distills']} distill(s), {cs['probes']} probe "
              f"launch(es), {cs['guardrail_trips']} guardrail trip(s), "
              f"state={cs['state']}; {nseg} sealed segment(s), "
              f"live={engine.store.size}")
        if args.autopilot_max_segments is not None:
            autopilot_ok = nseg <= args.autopilot_max_segments
            print(f"autopilot: segment count {nseg} "
                  f"{'<=' if autopilot_ok else '>'} gate "
                  f"{args.autopilot_max_segments}"
                  + ("" if autopilot_ok else " — GATE FAILED"))
    metrics_snap = engine.metrics(now=serve_now)  # one §14 snapshot feeds
    if args.prefilter and metrics_snap.get("prefilter") is not None:
        st = metrics_snap["prefilter"]  # ... the whole report below
        frac = st["cand_rows"] / max(st["seg_rows"], 1)
        print(f"prefilter: {st['banded_segments']} banded / "
              f"{st['exhaustive_segments']} escape-hatch / "
              f"{st['unindexed_segments']} unindexed segment scan(s) on the "
              f"last batch; candidate fraction {frac:.4f}")
    if mutable and args.background_compact:
        stats = engine.wait_compaction()
        if stats:
            print(f"background compaction: {stats['groups']} group(s), "
                  f"{stats['rows_in']}->{stats['rows_out']} rows "
                  f"(served throughout)")

    if chaos:
        stats = engine.wait_compaction()  # supervised: never raises
        chaos_mgr.wait()  # drain the last async save (ditto)
        faults.clear()
        metrics_snap = engine.metrics(now=serve_now)  # refresh post-wait
        h = metrics_snap["health"]
        c = chaos_plan.counters()
        fired = {p: k for p, k in sorted(c["fired"].items()) if k}
        jobs = h["jobs"]
        recovered = sum(v.get("succeeded", 0) for v in jobs.values())
        failed = sum(v.get("failed", 0) for v in jobs.values())
        print(f"chaos: {chaos_plan.total_fired} fault(s) injected {fired}")
        print(f"chaos: jobs recovered={recovered} failed={failed} "
              f"retries={h['retries']} abandoned={h['abandoned']} "
              f"quarantined={[q['op'] for q in h['quarantined']]} "
              f"degraded={sorted(d['component'] for d in h['degraded'])}")
        if stats:
            print(f"chaos: compaction landed under faults — "
                  f"{stats['rows_in']}->{stats['rows_out']} rows "
                  f"(retried through injected failures)")
        elif jobs.get("compact", {}).get("succeeded", 0):
            # a query-batch poll already swapped the result in mid-loop
            print("chaos: compaction landed under faults mid-serve "
                  "(swapped in by a query-path poll)")
        else:
            print("chaos: compaction never landed (retries exhausted or "
                  "quarantined) — serving degraded to the pre-compaction "
                  "segments throughout, no query saw an error")
        from repro.engine import SegmentedStore

        good = chaos_mgr.resolve_step(None)
        torn = [st for st in range(1, chaos_saves + 1)
                if not chaos_mgr.verify_step(st)]
        restored = SegmentedStore.restore(chaos_mgr)
        print(f"chaos: {chaos_saves} checkpoint generation(s) written, "
              f"torn/failed: {torn if torn else 'none'}; restore walked "
              f"back to step {good} ({restored.size} live docs)")
        shutil.rmtree(chaos_dir, ignore_errors=True)

    probe_ok = True
    if args.probe:
        from repro.obs.probe import RecallProbe

        # reuse the controller's probe when autopilot armed one — the gate
        # then reads the same gauge the guardrail watched all run
        pr = (controller.probe
              if controller is not None and controller.probe is not None
              else RecallProbe(engine, k=args.topk, sample=args.probe, seed=0))
        if pr.running or pr.launch(surv_ids, surv_rows, queries=queries):
            got = pr.wait(now=serve_now)
            if got is None:
                print("probe: ground-truth job failed — no reading")
                probe_ok = False
            else:
                print(f"probe: recall@{pr.k} = {got:.3f} over "
                      f"{min(args.probe, len(queries))} queries "
                      f"(ground truth on a supervised background job; "
                      f"gauge probe.recall)")
                if args.probe_baseline is not None:
                    delta = abs(got - args.probe_baseline)
                    probe_ok = delta <= args.probe_tol
                    print(f"probe: |reading - baseline "
                          f"{args.probe_baseline:.3f}| = {delta:.3f} "
                          f"{'<=' if probe_ok else '>'} tol {args.probe_tol}"
                          + ("" if probe_ok else " — GATE FAILED"))
        else:
            print("probe: launch refused (op quarantined) — no reading")
            probe_ok = False

    recall = None
    if args.check_recall:
        truth = exact_topk_jaccard(surv_rows, queries, args.topk)
        truth_ids = surv_ids[truth]  # positions -> global doc ids
        hits = sum(
            len(set(ids[i].tolist()) & set(truth_ids[i].tolist()))
            for i in range(args.queries)
        )
        recall = hits / (args.queries * args.topk)
        print(f"recall@{args.topk} vs exact Jaccard over survivors: {recall:.3f}")

    if args.metrics_json:
        import json

        snap = engine.metrics(now=serve_now)  # includes the probe gauges
        with open(args.metrics_json, "w") as f:
            json.dump(snap, f, indent=2, sort_keys=True)
        print(f"metrics: snapshot written to {args.metrics_json} "
              f"({len(snap['counters'])} counters, "
              f"{len(snap['histograms'])} histograms, "
              f"{len(snap['lifecycle']['segments'])} segment(s))")
    if not probe_ok:
        raise SystemExit("probe recall gate failed (see 'probe:' lines above)")
    if not autopilot_ok:
        raise SystemExit("autopilot segment-count gate failed "
                         "(see 'autopilot:' lines above)")
    if not chaos:
        from repro.engine import health_faults

        found = health_faults(engine.health())
        if found:
            raise SystemExit("serve: faults recorded with no fault plan "
                             "armed: " + "; ".join(found))
    return ServeRun(engine, recall)


if __name__ == "__main__":
    main()
