"""Telemetry plane (repro.obs + engine wiring, DESIGN.md §14): histogram
quantile error bounds on adversarial distributions, registry snapshot
JSON round-trips, trace completeness over the banded multi-segment query
path, the online recall probe against exact ground truth, per-segment
access counters and lifecycle gauges, and the unified injectable clock
across supervision / TTL / metrics timestamps."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import BinSketchConfig, make_mapping
from repro.data.synthetic import DATASETS, generate_corpus
from repro.engine import BandPolicy, JobSupervisor, SketchEngine
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.obs.probe import RecallProbe, exact_topk

SPEC = DATASETS["tiny"]


@pytest.fixture(autouse=True)
def _disarm_obs():
    """No test can leak an armed registry/collector into the next."""
    yield
    obs.disable()


def _fixture(seed=0, rho=0.05):
    idx, lens = generate_corpus(SPEC, seed=seed)
    cfg = BinSketchConfig.from_sparsity(SPEC.d, int(lens.max()), rho)
    mapping = make_mapping(cfg, jax.random.PRNGKey(0))
    return cfg, mapping, idx


def _banded_engine(cfg, mapping, idx, n=96, seal_rows=24, clock=None,
                   max_candidate_frac=1.0, ttl=None):
    eng = SketchEngine.build(
        cfg, mapping, backend="oracle", mutable=True, seal_rows=seal_rows,
        band_policy=BandPolicy(n_bands=8, min_rows=8,
                               max_candidate_frac=max_candidate_frac),
        clock=clock, ttl=ttl,
    )
    for s in range(0, n, seal_rows):
        eng.add(jnp.asarray(idx[s : s + seal_rows]))
    return eng


# ------------------------------------------------------------- histogram
@pytest.mark.parametrize("name,values", [
    ("lognormal", np.random.default_rng(0).lognormal(0.0, 2.0, 20000)),
    ("heavy_tail", np.random.default_rng(1).pareto(1.1, 20000) + 1e-6),
    ("bimodal", np.concatenate([
        np.random.default_rng(2).normal(1e-4, 1e-5, 10000),
        np.random.default_rng(3).normal(10.0, 1.0, 10000),
    ]).clip(min=1e-7)),
    ("constant", np.full(5000, 0.125)),
])
def test_histogram_quantiles_bounded_relative_error(name, values):
    """The DDSketch bound: every reported quantile is within alpha (5%)
    relative error of the exact order statistic, whatever the shape of
    the distribution — the property a mean (PR 7's latency summary)
    or a fixed-width histogram cannot give."""
    h = obs_metrics.Histogram(alpha=0.05)
    for v in values:
        h.observe(float(v))
    s = np.sort(values)
    for q in (0.50, 0.90, 0.99):
        exact = float(s[min(len(s) - 1, int(q * len(s)))])
        got = h.quantile(q)
        assert abs(got - exact) <= 0.05 * exact + 1e-12, (
            f"{name} p{int(q * 100)}: got {got}, exact {exact}"
        )


def test_histogram_zero_and_tiny_values_hit_zero_bucket():
    h = obs_metrics.Histogram()
    for v in (0.0, 1e-12, 1e-10):
        h.observe(v)
    assert h.count == 3
    assert h.quantile(0.5) == 0.0
    snap = h.snapshot()
    assert snap["p99"] == 0.0 and snap["count"] == 3


# -------------------------------------------------------------- registry
def test_registry_snapshot_json_round_trip_and_prometheus():
    reg = obs_metrics.MetricsRegistry(clock=obs.ManualClock(42.0))
    reg.inc("query.calls", 3)
    reg.set_gauge("probe.recall", 0.625)
    for v in (0.001, 0.002, 0.5):
        reg.observe("query.stage.kernel_score_s", v)
    snap = json.loads(json.dumps(reg.snapshot()))
    assert snap["at"] == 42.0
    assert snap["counters"]["query.calls"] == 3
    assert snap["gauges"]["probe.recall"] == 0.625
    hist = snap["histograms"]["query.stage.kernel_score_s"]
    assert hist["count"] == 3 and hist["min"] == 0.001


def test_free_helpers_are_noops_disarmed_and_land_when_armed():
    obs_metrics.inc("x")  # disarmed: must not raise, must not record
    with obs_metrics.scoped(obs_metrics.MetricsRegistry()) as reg:
        obs_metrics.inc("x", 2)
        obs_metrics.set_gauge("g", 1.5)
        obs_metrics.observe("h", 0.25)
        assert reg.counter("x") == 2
        assert reg.gauge("g") == 1.5
        assert reg.histogram("h").count == 1
    assert obs_metrics.active() is None


# ----------------------------------------------------------------- trace
def test_trace_completeness_on_banded_multi_segment_query():
    """One sampled banded multi-segment query must record every pipeline
    stage exactly once (stages is a keyed accumulator — presence is the
    completeness claim), per-segment candidate fractions, and the width
    touched; counters stay exact alongside."""
    cfg, mapping, idx = _fixture()
    eng = _banded_engine(cfg, mapping, idx)
    eng.enable_metrics()
    # queries drawn across all four segments so several produce parts
    q = jnp.asarray(idx[[0, 10, 30, 50, 70, 90]])
    eng.query(q, 5)
    reg = obs_metrics.active()
    assert reg.counter("query.calls") == 1
    assert reg.counter("query.rows") == 6
    tr = obs_trace.active().last()
    assert tr is not None and tr["path"] == "query"
    assert set(tr["stages_s"]) == set(obs_trace.STAGES)
    assert all(dt >= 0.0 for dt in tr["stages_s"].values())
    assert len(tr["segments"]) >= 2  # all four sealed segments looked up
    for seg in tr["segments"]:
        assert 0.0 <= seg["candidate_frac"] <= 1.0
    assert tr["widths"] == [cfg.n_bins]
    assert tr["degraded"] == [] and tr["k_overflow"] is False
    assert tr["duration_s"] > 0.0


def test_trace_sampling_keeps_counters_exact():
    cfg, mapping, idx = _fixture()
    eng = _banded_engine(cfg, mapping, idx)
    obs.enable(sample=2)
    q = jnp.asarray(idx[:4])
    for _ in range(4):
        eng.query(q, 3)
    reg = obs_metrics.active()
    assert reg.counter("query.calls") == 4  # exact, engine-side
    assert reg.counter("query.rows") == 16
    col = obs_trace.active()
    assert len(col.traces()) == 2  # every other call traced


def test_trace_flags_degraded_band_lookup():
    from repro import faults

    cfg, mapping, idx = _fixture()
    eng = _banded_engine(cfg, mapping, idx)
    eng.enable_metrics()
    with faults.scoped(faults.FaultPlan(
        {"band.lookup": faults.FaultSpec("raise")}
    )):
        eng.query(jnp.asarray(idx[:4]), 5)  # degrades, must not raise
    faults.clear()
    tr = obs_trace.active().last()
    assert "band_lookup" in tr["degraded"]
    reg = obs_metrics.active()
    assert reg.counter("query.degraded.band_lookup") >= 1
    assert reg.counter("degraded.band_lookup") >= 1  # supervisor-side twin


def test_k_overflow_counted_and_flagged():
    cfg, mapping, idx = _fixture()
    eng = SketchEngine.build(cfg, mapping, jnp.asarray(idx[:16]),
                             backend="oracle")
    eng.enable_metrics()
    eng.query(jnp.asarray(idx[:2]), 32)  # k > live corpus
    assert obs_metrics.active().counter("query.k_overflow") == 1
    assert obs_trace.active().last()["k_overflow"] is True


# ------------------------------------------------- lifecycle + hit counters
def test_segment_hits_and_lifecycle_snapshot():
    clock = obs.ManualClock(0.0)
    cfg, mapping, idx = _fixture()
    eng = _banded_engine(cfg, mapping, idx, clock=clock)
    eng.add(jnp.asarray(idx[96:100]))  # live head rows
    clock.advance(7.0)
    q = jnp.asarray(idx[[0, 30, 60, 90]])
    eng.query(q, 5)
    eng.query(q, 5)
    m = eng.metrics()
    life = m["lifecycle"]
    assert life["live_docs"] == 100
    assert life["head"]["rows"] == 4 and life["head"]["hits"] == 2
    assert len(life["segments"]) == 4
    total_hits = sum(s["hits"] for s in life["segments"])
    assert total_hits >= 2  # every segment with candidates was scored
    for s in life["segments"]:
        assert s["width"] == cfg.n_bins
        assert s["age_min"] == 7.0  # ManualClock-derived, docs born at 0
    assert life["width_mix"] == {str(cfg.n_bins): 100}  # head counts too
    assert life["tombstone_density"] == 0.0
    eng.delete([0, 1, 2])
    life2 = eng.metrics()["lifecycle"]
    assert life2["tombstone_density"] > 0.0
    json.dumps(m)  # whole snapshot JSON-safe


def test_metrics_snapshot_acceptance_fields():
    """The ISSUE's acceptance surface: metrics() carries query-stage
    latency histograms, per-segment access counters, lifecycle gauges,
    and the probe reading slot — JSON-safe — with health unified in."""
    cfg, mapping, idx = _fixture()
    eng = _banded_engine(cfg, mapping, idx)
    eng.enable_metrics()
    eng.query(jnp.asarray(idx[:8]), 5)
    m = json.loads(json.dumps(eng.metrics()))
    assert m["armed"] is True
    assert any(k.startswith("query.stage.") for k in m["histograms"])
    assert {"p50", "p99", "count"} <= set(
        next(iter(m["histograms"].values()))
    )
    assert all("hits" in s and "tombstones" in s and "width" in s
               for s in m["lifecycle"]["segments"])
    assert "tombstone_density" in m["lifecycle"]
    assert "width_mix" in m["lifecycle"]
    assert set(m["probe"]) == {"recall", "at", "runs"}
    assert "jobs" in m["health"] and "degraded" in m["health"]
    assert m["last_trace"]["path"] == "query"


# ----------------------------------------------------------------- probe
def test_recall_probe_agrees_with_exact_ground_truth():
    """The probe's published gauge must equal the recall recomputed
    independently from exact_topk + the engine's own answers — the
    arithmetic, threading, and id-mapping all on the line."""
    cfg, mapping, idx = _fixture()
    n, k = 80, 5
    eng = SketchEngine.build(cfg, mapping, jnp.asarray(idx[:n]),
                             backend="oracle")
    reg = eng.enable_metrics()
    pr = RecallProbe(eng, k=k, sample=16, seed=3)
    ids = np.arange(n)
    assert pr.launch(ids, idx[:n])
    got = pr.wait()
    assert got is not None and 0.0 <= got <= 1.0
    assert reg.gauge("probe.recall") == got
    assert reg.counter("probe.runs") == 1
    # independent recomputation over the same seeded query sample
    rng = np.random.default_rng(3)
    pick = rng.choice(n, 16, replace=False)
    queries = idx[:n][pick]
    truth_ids = ids[exact_topk(idx[:n], queries, k)]
    _, got_ids = eng.query(jnp.asarray(queries), k)
    got_ids = np.asarray(got_ids)
    hits = sum(len(set(got_ids[i].tolist()) & set(truth_ids[i].tolist()))
               for i in range(len(queries)))
    assert got == pytest.approx(hits / (len(queries) * k))


def test_probe_runs_off_thread_and_is_single_flight():
    cfg, mapping, idx = _fixture()
    eng = SketchEngine.build(cfg, mapping, jnp.asarray(idx[:40]),
                             backend="oracle")
    eng.enable_metrics()
    pr = RecallProbe(eng, k=3, sample=8, seed=0)
    assert pr.launch(np.arange(40), idx[:40])
    assert pr.running
    assert not pr.launch(np.arange(40), idx[:40])  # single in-flight probe
    assert pr.wait() is not None
    assert not pr.running
    assert pr.snapshot()["runs"] == 1


# ----------------------------------------------------------------- clock
def test_one_manual_clock_drives_ttl_supervision_and_metrics():
    """Satellite (a): a single injected ManualClock is the time source
    for lazy TTL expiry (no explicit now at query time), the
    supervisor's latency stamps, and the registry snapshot timestamp."""
    clock = obs.ManualClock(0.0)
    cfg, mapping, idx = _fixture()
    eng = SketchEngine.build(cfg, mapping, backend="oracle", mutable=True,
                             ttl=5.0, clock=clock)
    eng.add(jnp.asarray(idx[:12]), now=0.0)
    reg = eng.enable_metrics()
    assert eng.supervisor._clock() == 0.0  # same clock object's time
    _, ids = eng.query(jnp.asarray(idx[:4]), 3)  # now from clock: t=0
    assert (np.asarray(ids) >= 0).any()
    clock.advance(10.0)  # everything born at 0 is now past ttl=5
    _, ids = eng.query(jnp.asarray(idx[:4]), 3)  # no explicit now
    assert (np.asarray(ids) == -1).all()
    assert reg.snapshot()["at"] == 10.0


def test_supervision_health_reports_latency_quantiles():
    sup = JobSupervisor(clock=obs.ManualClock(0.0))
    job = sup.submit("probe", ("x", 0), lambda: 1)
    assert job is not None
    import time as _t

    deadline = _t.monotonic() + 10.0
    while sup.poll(job) == "running" and _t.monotonic() < deadline:
        _t.sleep(0.002)
    lat = sup.health()["latency_s"]["probe"]
    assert {"count", "mean_s", "max_s", "p50_s", "p99_s"} <= set(lat)
    assert lat["count"] == 1 and lat["p50_s"] >= 0.0


# ------------------------------------------------------- enable/disable
def test_enable_disable_idempotent_and_scoped():
    reg = obs.enable(clock=obs.ManualClock(1.0), sample=3, capacity=7)
    assert obs_metrics.active() is reg
    assert obs_trace.active().sample == 3
    obs.disable()
    assert obs_metrics.active() is None and obs_trace.active() is None
    obs.disable()  # idempotent


# ------------------------------------------------------ profiler spans
def _program_spans(log_dir):
    """``repro.*`` host events of the one profile under ``log_dir``:
    (name, start_ns, end_ns, thread line, {stat: value})."""
    import glob

    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line_i, ln in enumerate(plane.lines):
            for e in ln.events:
                if e.name.startswith(obs_trace.SPAN_PREFIX):
                    out.append((e.name, e.start_ns, e.start_ns + e.duration_ns,
                                (plane.name, line_i), {k: v for k, v in e.stats}))
    return out


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """One profile: a segmented engine (two sealed segments, a head of 12)
    answers an armed query of 6 rows, then takes an insert of 12 docs that
    fills the head to its 24 and seals it."""
    cfg, mapping, idx = _fixture()
    eng = SketchEngine.build(cfg, mapping, backend="oracle", mutable=True,
                             seal_rows=24)
    for lo in (0, 24, 48):
        eng.add(jnp.asarray(idx[lo : min(lo + 24, 60)]))
    q, new = jnp.asarray(idx[60:66]), jnp.asarray(idx[66:78])
    eng.query(q, 5)  # compile outside the profile
    log_dir = str(tmp_path_factory.mktemp("profile"))
    eng.enable_metrics()
    try:
        jax.profiler.start_trace(log_dir)
        try:
            s, _ = eng.query(q, 5)
            s.block_until_ready()
            eng.add(new)
            jax.block_until_ready(eng.store.sealed[-1].sketches)
        finally:
            jax.profiler.stop_trace()
        last = obs_trace.active().last()
    finally:
        obs.disable()
    return _program_spans(log_dir), last, len(eng.store.sealed)


def _one(spans, name):
    got = [s for s in spans if s[0] == name]
    assert len(got) == 1, (name, [s[0] for s in spans])
    return got[0]


@pytest.mark.parametrize("outer,inner", [
    ("repro.engine.query", "repro.query.rebucket"),
    ("repro.engine.query", "repro.query.kernel_score"),
    ("repro.engine.query", "repro.query.merge"),
    ("repro.store.add", "repro.store.count"),
    ("repro.store.add", "repro.store.head_write"),
    ("repro.store.add", "repro.store.index"),
    ("repro.store.add", "repro.store.seal"),
])
def test_program_spans_nest_on_the_calling_thread(profiled, outer, inner):
    spans, _, _ = profiled
    o = _one(spans, outer)
    inside = [s for s in spans if s[0] == inner]
    assert inside, f"no {inner} span in the profile"
    for s in inside:
        assert s[3] == o[3]  # the same host thread
        assert o[1] <= s[1] and s[2] <= o[2], f"{inner} outside {outer}"


@pytest.mark.parametrize("name,stats", [
    ("repro.engine.query", {"rows": 6, "k": 5, "path": "query", "call": 2}),
    ("repro.store.add", {"docs": 12, "call": 4}),
    ("repro.store.count", {"docs": 12}),
    ("repro.store.head_write", {"docs": 12}),
    ("repro.store.index", {"docs": 12}),
    ("repro.store.seal", {"rows": 24}),
])
def test_program_spans_carry_the_request_sizes(profiled, name, stats):
    spans, _, n_sealed = profiled
    got = _one(spans, name)[4]
    assert {k: got[k] for k in stats} == stats
    assert n_sealed == 3  # the insert sealed: 60 + 12 docs in 24-row segments


def test_armed_trace_stages_are_the_query_span_names(profiled):
    spans, last, _ = profiled
    prefix = obs_trace.SPAN_PREFIX + "query."
    named = {s[0][len(prefix):] for s in spans if s[0].startswith(prefix)}
    assert set(last["stages_s"]) == named
    assert named <= set(obs_trace.STAGES)
