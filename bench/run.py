#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip(s) of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to a cell is found by name from ``BENCHMARK.json``
at the root of the checkout: its configuration (``bench/configs/``), its
traffic mix (``bench/traffic/<traffic>.json``, read by the one generator in
``bench/traffic.py``) and, with ``--trace 1``, one reader per per-layer
metric (``bench/metrics/<metric>.py``). A run:

1. refuses to run without the TPU chips the cell asks for, or on a device
   missing from ``bench/peaks.json`` (exit 3 and 2, no result);
2. makes the corpus on the device and the sketch map from ``--seed``,
   builds the engine through its public API and warms every shape the mix
   uses by sending its first requests (set-up, with JAX's persistent
   compilation cache in ``<checkout>/.bench_cache/jax``);
3. sends the mix's requests in a closed loop for ``--seconds`` (with
   ``--trace 1``: for the mix's ``trace_seconds``, under the profiler);
4. reads the device's peak memory, frees the engine, and compares the
   window's answers and the store with the plain reference, each answer
   against the corpus as it stood when its query was sent
   (``bench/check.py``);
5. prints, as the last line of standard output, one JSON object:
   ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
   end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
   ``device``, with ``--trace 1`` ``breakdown``, and last ``checks``: each
   number compared beside its limit, which also end standard error.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import resource  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402
from contextlib import nullcontext  # noqa: E402

import numpy as np  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]

if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import check, corpus, reference, tracefile  # noqa: E402
from bench import traffic as traffic_mod  # noqa: E402


class Refused(Exception):
    """The run cannot be made here; ``code`` is the exit status."""

    def __init__(self, code: int, reason: str):
        super().__init__(reason)
        self.code = code


# ------------------------------------------------------------ the spec
def load_spec(root: pathlib.Path, workload: str) -> types.SimpleNamespace:
    """The cell, its configuration, its mix and its metrics, by name."""
    try:
        bench = json.loads((root / "BENCHMARK.json").read_text())
        cell = next((w for w in bench["workloads"] if w["name"] == workload), None)
        if cell is None:
            raise Refused(2, f"no cell named {workload!r} in BENCHMARK.json")
        entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
        cfg = json.loads((root / entry["file"]).read_text())
        mix = json.loads((root / "bench" / "traffic" / f"{cell['traffic']}.json").read_text())
    except (OSError, KeyError, StopIteration, json.JSONDecodeError) as e:
        raise Refused(2, f"cannot read the cell {workload!r}: {e!r}") from e
    want = corpus.theorem1_n_bins(cfg["psi"], cfg["rho"])
    if cfg.get("n_bins", want) != want or cfg.get("n_words", corpus.n_words(want)) != \
            corpus.n_words(want):
        raise Refused(2, f"{entry['file']}: n_bins/n_words disagree with Theorem 1 ({want})")
    cfg = dict(cfg, n_bins=want, n_words=corpus.n_words(want))
    try:
        traffic_mod.validate(mix)
    except ValueError as e:
        raise Refused(2, f"mix {cell['traffic']!r}: {e}") from e
    if "update" in traffic_mod.op_weights(mix) and cfg["store"] != "segmented":
        raise Refused(2, f"mix {cell['traffic']!r} updates docs, which a "
                         f"{cfg['store']!r} store cannot")

    def mine(m):
        return "workloads" not in m or workload in m["workloads"]

    e2e = [m for m in bench["end_to_end"] if mine(m)]
    for m in e2e:
        if m["name"] not in END_TO_END:
            raise Refused(2, f"no computation for end-to-end metric {m['name']!r}")
    layer = [m for m in bench["per_layer"] if mine(m)]
    return types.SimpleNamespace(root=root, cell=cell, cfg=cfg, mix=mix, e2e=e2e,
                                 per_layer=layer)


def load_reader(root: pathlib.Path, name: str):
    """``bench/metrics/<name>.py``: ``UNIT`` and ``read(ctx)``."""
    path = root / "bench" / "metrics" / f"{name}.py"
    if not path.is_file():
        raise Refused(2, f"no reader for per-layer metric {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_peaks(root: pathlib.Path, kind: str) -> dict:
    table = json.loads((root / "bench" / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise Refused(2, f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


# --------------------------------------------------------- the device
def device_check(chips: int) -> dict:
    """The chips the cell asks for, TPUs, with the kernels compiled."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Refused(3, f"no TPU: JAX found {devs[0].platform!r} devices")
    if len(devs) < chips:
        raise Refused(3, f"the cell asks for {chips} chips, JAX found {len(devs)}")
    from repro.engine import get_backend

    if get_backend("pallas").interpreted:
        raise Refused(3, "the pallas backend resolved to interpret mode on a TPU")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": chips}


def memory_peak(chips: int) -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))


class CompileClock:
    """Counts JAX's backend compiles (persistent-cache reads included) and
    persistent-cache hits, to tell set-up from window."""

    def __init__(self):
        import jax

        self.compiles, self.secs, self.hits = 0, 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += secs
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def enable_cache(root: pathlib.Path) -> None:
    import jax

    jax.config.update("jax_compilation_cache_dir", str(root / ".bench_cache" / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


# ------------------------------------------------------- end to end
def _p90(xs):
    return float(np.percentile(xs, 90)) if xs else None


END_TO_END = {
    "query_qps": lambda w: w.query_docs / w.window_s if w.query_docs else None,
    "query_p90_ms": lambda w: _p90([1e3 * (r["t1"] - r["t0"]) for r in w.records
                                    if r["op"] == "query"]),
    "recall_at_10": lambda w: w.recall,
    "ingest_docs_s": lambda w: w.insert_docs / w.window_s if w.insert_docs else None,
    "setup_s": lambda w: w.setup_s,
}


# ------------------------------------------------------------- a run
def _send(system, eng, req):
    if req.op == "query":
        s, i = system.query(eng, req.idx, req.k)
        return {"scores": s, "ids": i}
    if req.op == "update":
        return {"sealed": system.update(eng, req.keys, req.idx)}
    lo, hi, sealed = system.insert(eng, req.idx)
    return {"lo": lo, "hi": hi, "sealed": sealed}


def _acknowledge(gen, hist, seals, req, out) -> None:
    """A write the system acknowledged: later queries send its contents
    and the check holds the store to it; ``seals`` gets whether each
    update sealed the head."""
    if req.op == "insert":
        hist.insert(out["lo"], out["hi"], req.pool_lo)
    elif req.op == "update":
        gen.ack(req)
        hist.update(req.keys, req.pool_lo)
        seals.append(out["sealed"])


def run_cell(spec, seed: int, seconds: float, trace: bool, device: dict, peaks: dict, *,
             system=None, t_start: float = T_START, log=None) -> dict:
    """One run of ``spec``'s cell: set-up, window, check; the result line."""
    import jax

    if system is None:
        from bench import system
    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    cfg, mix, root = spec.cfg, spec.mix, spec.root
    chips = spec.cell["chips"]
    clock = CompileClock()
    t_begin = time.perf_counter()

    # ---- set-up: data, engine, warm-up
    pi = corpus.mapping(cfg, seed)
    builder = system.Builder(cfg, pi)
    standing, _ = corpus.device_corpus(cfg, seed, on_chunk=lambda lo, rows: builder.add(rows),
                                       chunk=cfg["build_batch"])
    eng = builder.finish()
    del builder
    t_built = time.perf_counter()
    n_docs = len(standing)
    gen = traffic_mod.Generator(mix, cfg, seed, standing)
    hist, seals = check.History(standing, gen.pool), []
    for j in range(mix["warmup_requests"]):
        req = gen.next(gen.warmup_op(j))
        _acknowledge(gen, hist, seals, req, _send(system, eng, req))
    n_compiles = clock.compiles

    # ---- the window
    trace_dir = str(root / ".bench_cache" / "trace")
    length = min(seconds, mix["trace_seconds"]) if trace else seconds
    records, failed = [], 0
    phases = getattr(system, "PHASES", {})  # host seconds of each span of a request
    gc_clock = _GcClock()
    t_w0 = time.perf_counter()
    setup_s = t_w0 - t_start
    with tracefile.capture(trace_dir) if trace else nullcontext(), gc_clock:
        with jax.profiler.TraceAnnotation("bench.window"):
            deadline = t_w0 + length
            while time.perf_counter() < deadline:
                req = gen.next()
                live, ver = hist.hi, hist.version
                phases.clear()  # spans of this request's op only
                before = _host_state(gc_clock)
                t0 = time.perf_counter()
                with jax.profiler.TraceAnnotation("bench.request", kind=req.op):
                    try:
                        out = _send(system, eng, req)
                    except Exception as e:  # a failed request counts, the run goes on
                        failed += 1
                        log(f"request {len(records)} ({req.op}) failed: {e!r}")
                        out = None
                t1 = time.perf_counter()
                host = _host_state(gc_clock) - before
                rec = {"op": req.op, "t0": t0, "t1": t1, "docs": len(req.idx),
                       "live": live, "ver": ver, "sealed": False, "req": req, "out": out,
                       "host": host, "phases": dict(phases)}
                if out is not None and req.op != "query":
                    rec["sealed"] = out["sealed"]
                    _acknowledge(gen, hist, seals, req, out)
                records.append(rec)
    t_w1 = records[-1]["t1"] if records else time.perf_counter()
    in_window = clock.compiles - n_compiles
    log(f"set-up {setup_s:.3f} s: to the harness {t_begin - t_start:.3f} s, corpus and "
        f"engine {t_built - t_begin:.3f} s, warm-up {t_w0 - t_built:.3f} s")
    log(f"set-up {setup_s:.3f} s ({n_compiles} compiles, {clock.hits} persistent-cache "
        f"hits, {clock.secs:.1f} s compiling); window {t_w1 - t_w0:.3f} s, "
        f"{len(records)} requests, {in_window} compiles inside the window")
    lat = np.asarray([1e3 * (r["t1"] - r["t0"]) for r in records])
    if len(lat):
        p50 = float(np.median(lat))
        log(f"request ms: p50 {p50:.3f}, p90 {np.percentile(lat, 90):.3f}, max "
            f"{lat.max():.3f}, {int((lat > 2 * p50).sum())} over twice the p50; between "
            f"requests {1e3 * (t_w1 - t_w0) / len(lat) - lat.mean():.3f} ms on average")
        log(f"host in the window: {gc_clock.secs:.3f} s in {gc_clock.runs} garbage "
            f"collections ({gc_clock.full} full)")
        for j in np.argsort(-lat)[:5]:
            if lat[j] > 3 * p50:
                r = records[j]
                cpu, gc_s, majflt, minflt, nvcsw, nivcsw = r["host"]
                phases = ", ".join(f"{k} {1e3 * v:.1f}" for k, v in r["phases"].items())
                log(f"slow request {j}: {lat[j]:.1f} ms ({phases} ms); process CPU "
                    f"{1e3 * cpu:.1f} ms, GC {1e3 * gc_s:.1f} ms, page faults {majflt:.0f} "
                    f"major {minflt:.0f} minor, context switches {nvcsw:.0f} voluntary "
                    f"{nivcsw:.0f} involuntary")
    if hist.updates:
        docs, rewrites = _rewrites(hist.updates, seals)
        window = sum(r["op"] == "update" and r["out"] is not None for r in records)
        log(f"updates: {len(hist.updates)} requests ({window} in the window), {docs} docs, "
            f"{rewrites} of them rewrites of a doc already in the head; {sum(seals)} seals")
    mem = memory_peak(chips) if device["platform"] == "tpu" else 0

    # ---- the program's part of the check, then free it
    ok = [r for r in records if r["out"] is not None]
    queries, sample, f_ids = _check_draws(seed, mix, ok, hist, cfg["psi"])
    numbers = {"failed_requests": failed}
    find = None
    if hist.inserts or hist.updates:
        numbers.update(check.store_numbers(
            system.views(eng), np.concatenate([np.arange(n_docs), hist.inserted_ids()]),
            sample, hist.newest, pi=pi, n_bins=cfg["n_bins"]))
        if len(f_ids):
            f_idx = hist.newest(f_ids)
            s, i = system.query(eng, f_idx, 10)
            find = (f_idx, np.full(len(f_ids), hist.hi), np.full(len(f_ids), hist.version),
                    s, i)
    del eng
    gc.collect()

    # ---- the reference
    q_idx, limits, vers, got_s, got_i, k = queries
    if find is not None:
        q_idx = np.concatenate([q_idx, find[0]])
        limits = np.concatenate([limits, find[1]])
        vers = np.concatenate([vers, find[2]])
        got_s = np.concatenate([got_s.reshape(-1, 10), find[3]])
        got_i = np.concatenate([got_i.reshape(-1, 10), find[4]])
        k = 10
    t_ref = time.perf_counter()
    chunks = hist.chunks()
    if len(q_idx):
        numbers.update(check.query_numbers(q_idx, limits, vers, got_s, got_i, hist.at, chunks,
                                           pi=pi, n_bins=cfg["n_bins"], k=k))
    w = types.SimpleNamespace(
        records=ok, setup_s=setup_s, window_s=t_w1 - t_w0,
        query_docs=sum(r["docs"] for r in ok if r["op"] == "query"),
        insert_docs=sum(r["docs"] for r in ok if r["op"] == "insert"), recall=None)
    nq = len(queries[0])
    if nq and any(m["name"] == "recall_at_10" for m in spec.e2e) and not trace:
        from bench import truth

        t = truth.device_exact_topk(q_idx[:nq], limits[:nq], chunks, k, vocab=cfg["vocab"],
                                    vers=vers[:nq])
        w.recall = truth.recall(got_i[:nq], t)
    del chunks
    log(f"check: {len(q_idx)} answers, {len(hist.inserts)} inserts and {len(hist.updates)} "
        f"updates compared with the reference; the program's part {t_ref - t_w1:.3f} s, "
        f"the reference's {time.perf_counter() - t_ref:.3f} s")

    # ---- metrics
    dev = dict(device, memory_peak_bytes=mem)
    result = {"correct": check.verdict(numbers), "attempted": len(records), "failed": failed}
    if not trace:
        metrics = {}
        for m in spec.e2e:
            v = END_TO_END[m["name"]](w)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        result.update(metrics=metrics, device=dev)
    else:
        tr = tracefile.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        tr.save(str(root / ".bench_cache" / "last_trace.json.gz"))
        ctx = types.SimpleNamespace(trace=tr, traced=ok, cfg=cfg, peaks=peaks,
                                    cell=spec.cell, note=log)
        metrics = {}
        for m in spec.per_layer:
            v = load_reader(root, m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        dev.update(busy_s=tracefile.mean_busy_s(tr), window_s=tracefile.window_s(tr))
        result.update(metrics=metrics, device=dev,
                      breakdown={"device_ops": tracefile.top_ops(tr),
                                 "idle_gaps": tracefile.idle_gaps(tr)})
    result["checks"] = check.report(numbers)
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    return result


class _GcClock:
    """Seconds and runs of Python's garbage collector while entered."""

    def __init__(self):
        self.secs, self.runs, self.full, self._t0 = 0.0, 0, 0, 0.0

    def _callback(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
            return
        self.secs += time.perf_counter() - self._t0
        self.runs += 1
        self.full += info["generation"] == 2

    def __enter__(self):
        gc.callbacks.append(self._callback)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._callback)


def _host_state(gc_clock) -> np.ndarray:
    """Process CPU seconds, GC seconds, page faults and context switches so
    far: the difference over a request says where a slow one waited."""
    ru = resource.getrusage(resource.RUSAGE_SELF)
    return np.array([time.process_time(), gc_clock.secs, ru.ru_majflt, ru.ru_minflt,
                     ru.ru_nvcsw, ru.ru_nivcsw], np.float64)


def _check_draws(seed, mix, records, hist, psi):
    """What the check compares, drawn from the seed: the window's queries
    (``_query_sample``), the written docs whose stored sketches are compared,
    and the written docs sent as queries after the window. Inserted docs
    are drawn from the check's stream, after the queries; updated docs from
    a stream of their own."""
    rng = corpus.rng_for(seed, corpus.STREAM_CHECK)
    rng_u = corpus.rng_for(seed, corpus.STREAM_UPDATE_CHECK)
    queries = _query_sample(records, mix, rng, psi)
    ins_ids, upd_ids = hist.inserted_ids(), hist.updated_ids()
    sample = np.zeros(0, np.int64)
    if len(ins_ids):
        sample = _drawn(rng, ins_ids, mix["check_sample"], np.arange(*hist.inserts[-1][:2]))
    if len(upd_ids):
        sample = np.union1d(sample, _drawn(rng_u, upd_ids, mix["check_sample"],
                                           hist.updates[-1][0]))
    nf = mix.get("findability_queries", 0)
    f_ids = np.concatenate([np.zeros(0, np.int64)] + [
        _drawn(r, ids, nf) for r, ids in ((rng, ins_ids), (rng_u, upd_ids)) if len(ids)])
    return queries, sample, f_ids


def _query_sample(records, mix, rng, psi):
    """(rows, limits, versions, scores, ids, k) of at most ``check_queries``
    window queries, drawn from the seed."""
    qs = [r for r in records if r["op"] == "query"]
    if not qs:
        return (np.zeros((0, psi), np.int32), np.zeros(0, np.int64), np.zeros(0, np.int64),
                np.zeros((0, 10)), np.zeros((0, 10), np.int64), 10)
    k = qs[0]["req"].k
    rows = np.concatenate([r["req"].idx for r in qs])
    limits = np.concatenate([np.full(r["docs"], r["live"]) for r in qs])
    vers = np.concatenate([np.full(r["docs"], r["ver"]) for r in qs])
    s = np.concatenate([r["out"]["scores"] for r in qs])
    i = np.concatenate([r["out"]["ids"] for r in qs])
    cap = mix["check_queries"]
    if len(rows) > cap:
        pick = np.sort(rng.choice(len(rows), cap, replace=False))
        rows, limits, vers, s, i = rows[pick], limits[pick], vers[pick], s[pick], i[pick]
    return rows, limits, vers, s, i, k


def _drawn(rng, ids, n, last=()):
    """``n`` of ``ids`` (at most all) drawn from ``rng``, with every id of
    ``last``: the docs of the last write request."""
    got = rng.choice(ids, min(n, len(ids)), replace=False)
    return np.union1d(got, last) if len(last) else got


def _rewrites(updates, seals):
    """(docs updated, how many of them were in the head already): an update
    moves each doc it writes into the head, and a seal empties it."""
    in_head, docs, rewrites = set(), 0, 0
    for (ids, _), sealed in zip(updates, seals):
        docs += len(ids)
        rewrites += len(in_head.intersection(ids.tolist()))
        in_head = set() if sealed else in_head | set(ids.tolist())
    return docs, rewrites


# ------------------------------------------------------------------ CLI
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")
    try:
        spec = load_spec(ROOT, args.workload)
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        sys.path.insert(1, str(ROOT / "src"))
        t_harness = time.perf_counter()
        enable_cache(ROOT)  # before the program's first compile, which fixes the directory
        try:
            import repro.engine  # noqa: F401
        except ImportError as e:
            raise Refused(2, f"the system under test is not in this checkout: {e}") from e
        t_imported = time.perf_counter()
        device = device_check(spec.cell["chips"])
        print(f"bench: from process start {t_harness - T_START:.3f} s to the harness's "
              f"imports, {t_imported - t_harness:.3f} s importing the program and JAX, "
              f"{time.perf_counter() - t_imported:.3f} s starting the TPU runtime",
              file=sys.stderr, flush=True)
        peaks = load_peaks(ROOT, device["kind"])
        result = run_cell(spec, args.seed, args.seconds, bool(args.trace), device, peaks)
    except Refused as e:
        print(f"bench: refused: {e}", file=sys.stderr)
        return e.code
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
