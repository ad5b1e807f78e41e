"""Chaos suite (repro.faults + engine/supervision.py + verified
checkpoints, DESIGN.md §13): deterministic seeded fault injection drives
every failure path the supervision layer claims to survive — torn
checkpoint writes walk back a generation, background compaction /
distillation failures never reach queries (results stay identical to a
fresh rebuild over survivors), retries recover transients, quarantine
engages after N exhausted launches and a healthy probe clears it, the
watchdog abandons a stalled job without swapping, and query-path
accelerator failures (band lookup/build, placement) degrade to the exact
exhaustive paths with the degradation recorded in health()."""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import faults
from repro.checkpoint.manager import (
    BackgroundJob,
    CheckpointCorruptError,
    CheckpointManager,
)
from repro.core import BinSketchConfig, make_mapping
from repro.data.synthetic import DATASETS, generate_corpus
from repro.engine import (
    BandPolicy,
    ControllerPolicy,
    DistillPolicy,
    JobSupervisor,
    LifecycleController,
    SegmentedStore,
    SketchEngine,
    SupervisionPolicy,
)
from repro.engine.testing import assert_topk_equivalent, topk_truth
from repro.obs.probe import RecallProbe

SPEC = DATASETS["tiny"]

FAST = SupervisionPolicy(max_retries=1, backoff_base=0.005, backoff_cap=0.02)


@pytest.fixture(autouse=True)
def _disarm():
    """No test can leak an armed plan into the next."""
    yield
    faults.clear()


from conftest import corpus as _fixture
from conftest import multi_segment_engine as _multi_segment_engine


# ------------------------------------------------------------- fault plans
def test_plan_rejects_unknown_point_and_bad_spec():
    with pytest.raises(ValueError, match="unknown injection point"):
        faults.FaultPlan({"compact.wrok": faults.FaultSpec()})
    with pytest.raises(ValueError, match="mode"):
        faults.FaultSpec(mode="explode")


def test_plan_decisions_are_seed_deterministic():
    """Same seed + same per-point hit sequence -> identical firing pattern
    (the property that makes a CI chaos failure reproducible locally)."""
    mk = lambda seed: faults.FaultPlan(
        {"compact.work": faults.FaultSpec("raise", p=0.4),
         "band.lookup": faults.FaultSpec("raise", p=0.7)},
        seed=seed,
    )
    a, b = mk(7), mk(7)
    seq_a = [(p, a.decide(p) is not None)
             for p in ["compact.work", "band.lookup"] * 40]
    seq_b = [(p, b.decide(p) is not None)
             for p in ["compact.work", "band.lookup"] * 40]
    assert seq_a == seq_b
    assert any(fired for _, fired in seq_a)
    assert not all(fired for _, fired in seq_a)
    c = mk(8)
    seq_c = [(p, c.decide(p) is not None)
             for p in ["compact.work", "band.lookup"] * 40]
    assert seq_c != seq_a  # a different seed is a different schedule


def test_times_after_and_counters():
    plan = faults.FaultPlan(
        {"compact.work": faults.FaultSpec("raise", times=2, after=1)}
    )
    with faults.scoped(plan):
        faults.inject("compact.work")  # after=1: first hit passes
        for _ in range(2):
            with pytest.raises(faults.FaultError):
                faults.inject("compact.work")
        faults.inject("compact.work")  # times=2 budget spent
    c = plan.counters()
    assert c["hits"]["compact.work"] == 4
    assert c["fired"]["compact.work"] == 2
    faults.inject("compact.work")  # disarmed: no-op, not even a hit
    assert plan.counters()["hits"]["compact.work"] == 4


# ------------------------------------------------------- checkpoint integrity
def _tree(val=1.0):
    return {"a": jnp.full((1024,), val, jnp.float32),
            "b": jnp.arange(256, dtype=jnp.int32)}


def test_aux_serializability_fails_fast_on_caller_thread(tmp_path):
    """A non-JSON-serializable aux must raise at save() — synchronously —
    not at the next save()/wait() from inside the writer thread."""
    m = CheckpointManager(str(tmp_path))
    with pytest.raises(TypeError, match="JSON-serializable"):
        m.save(1, _tree(), aux={"bad": object()}, blocking=False)
    assert m._pending is None  # nothing was launched


def test_torn_leaf_walks_back_one_generation(tmp_path):
    """A torn leaf write (silently truncated after fsync — only the CRC
    can know) leaves LATEST pointing at garbage; restore lands on the
    previous generation, and explicitly requesting the torn step raises."""
    m = CheckpointManager(str(tmp_path), keep=3)
    m.save(1, _tree(1.0), aux={"gen": 1})
    with faults.scoped(faults.FaultPlan(
        {"checkpoint.leaf": faults.FaultSpec("torn-write", times=1)}, seed=3
    )) as plan:
        m.save(2, _tree(2.0), aux={"gen": 2})
    assert plan.counters()["fired"]["checkpoint.leaf"] == 1
    assert not m.verify_step(2) and m.verify_step(1)
    assert m.resolve_step(None) == 1
    tree, aux = m.restore(None, _tree(0.0))
    assert aux["gen"] == 1
    np.testing.assert_array_equal(np.asarray(tree["a"]), np.full(1024, 1.0))
    with pytest.raises(CheckpointCorruptError, match="leaf"):
        m.restore(2, _tree(0.0))


def test_vanished_latest_dir_walks_back_to_verifying(tmp_path):
    """latest_step with LATEST pointing at a vanished dir must not hand
    back a newer-but-corrupt step: it walks back to the newest generation
    that verifies."""
    import os
    import shutil

    m = CheckpointManager(str(tmp_path), keep=5)
    m.save(1, _tree(1.0), aux={"gen": 1})
    with faults.scoped(faults.FaultPlan(
        {"checkpoint.leaf": faults.FaultSpec("torn-write", times=1)}
    )):
        m.save(2, _tree(2.0), aux={"gen": 2})
    m.save(3, _tree(3.0), aux={"gen": 3})
    shutil.rmtree(os.path.join(str(tmp_path), "step_%012d" % 3))
    # LATEST -> 3 (gone); newest remaining dir is 2 (torn) -> must pick 1
    assert m.latest_step() == 1
    store_aux = m.load_aux(m.resolve_step(None))
    assert store_aux["gen"] == 1


def test_store_restore_pins_verified_step(tmp_path):
    """SegmentedStore round-trip through a torn newest checkpoint: aux and
    arrays both come from the older verifying generation."""
    cfg, mapping, idx = _fixture()
    eng = _multi_segment_engine(cfg, mapping, idx, n=48, seal_rows=24)
    m = CheckpointManager(str(tmp_path))
    eng.store.save(m, step=1)
    eng.add(jnp.asarray(idx[48:72]))  # diverge, then tear the newer save
    with faults.scoped(faults.FaultPlan(
        {"checkpoint.leaf": faults.FaultSpec("torn-write", times=1)}
    )):
        eng.store.save(m, step=2)
    back = SegmentedStore.restore(m)
    assert back.size == 48  # generation 1, not the torn generation 2
    q = jnp.asarray(idx[100:106])
    ref = SketchEngine.build(cfg, mapping, jnp.asarray(idx[:48]),
                             backend="oracle")
    got = SketchEngine(back, ref.backend)
    assert_topk_equivalent(got.query(q, 5), ref.query(q, 5))


def test_supervised_async_save_retries_transient_write_fault(tmp_path):
    """checkpoint.write raising once is absorbed by the supervisor's
    retry; the save lands and health records exactly one retry."""
    sup = JobSupervisor(FAST)
    m = CheckpointManager(str(tmp_path), supervisor=sup)
    with faults.scoped(faults.FaultPlan(
        {"checkpoint.write": faults.FaultSpec("raise", times=1)}
    )):
        m.save(5, _tree(5.0), aux={"gen": 5}, blocking=False)
        m.wait()  # never raises under supervision
    assert m.latest_step() == 5
    h = sup.health()
    assert h["jobs"]["checkpoint"]["retries"] == 1
    assert h["jobs"]["checkpoint"]["succeeded"] == 1


def test_unsupervised_async_save_still_raises(tmp_path):
    """Without a supervisor the legacy contract holds: background write
    errors re-raise at the next wait() on the caller's thread."""
    m = CheckpointManager(str(tmp_path))
    with faults.scoped(faults.FaultPlan(
        {"checkpoint.write": faults.FaultSpec("raise")}
    )):
        m.save(1, _tree(), blocking=False)
        with pytest.raises(faults.FaultError):
            m.wait()


# --------------------------------------------------- supervised maintenance
def test_compaction_failure_never_reaches_queries():
    """A terminally-failing background compaction must leave queries
    exception-free and bit-identical to a fresh rebuild over survivors —
    the store just keeps serving its pre-swap state."""
    cfg, mapping, idx = _fixture()
    sup = JobSupervisor(FAST)
    eng = _multi_segment_engine(cfg, mapping, idx, supervisor=sup)
    eng.delete([3, 30, 70])
    q = jnp.asarray(idx[100:108])
    with faults.scoped(faults.FaultPlan(
        {"compact.work": faults.FaultSpec("raise")}  # every attempt fails
    )):
        assert eng.store.compact_async() is True
        for _ in range(50):  # queries drive the poll/retry state machine
            sc, ids = eng.query(q, 5)
            if sup.health()["jobs"]["compact"]["failed"]:
                break
            time.sleep(0.01)
    h = sup.health()
    assert h["jobs"]["compact"]["failed"] == 1
    assert h["jobs"]["compact"]["retries"] == FAST.max_retries
    assert "FaultError" in h["last_error"]["error"]
    surv = np.asarray(sorted(set(range(96)) - {3, 30, 70}))
    fresh = SketchEngine.build(
        cfg, mapping, jnp.asarray(idx[surv]), backend="oracle")
    sc_f, id_f = fresh.query(q, 5)
    id_f = np.where(np.asarray(id_f) >= 0,
                    surv[np.maximum(np.asarray(id_f), 0)], -1)
    assert_topk_equivalent(eng.query(q, 5), (sc_f, id_f),
                           truth=topk_truth(fresh, q, id_map=surv))
    # and the *next* compaction (faults cleared) heals the store
    assert eng.store.compact_async() is True
    assert eng.store.wait_compaction()["rows_out"] == 93


def test_distill_transient_failure_retries_to_success():
    cfg, mapping, idx = _fixture()
    sup = JobSupervisor(FAST)
    eng = _multi_segment_engine(cfg, mapping, idx, n=48, seal_rows=24,
                                supervisor=sup)
    n_new = cfg.n_bins // 2
    policy = DistillPolicy(widths=(n_new,))
    with faults.scoped(faults.FaultPlan(
        {"distill.work": faults.FaultSpec("raise", times=1)}
    )):
        assert eng.store.distill_async(policy) is True
        stats = eng.store.wait_compaction()  # retry absorbs the transient
    assert stats is not None and stats["groups"] == 2
    assert {s.n_bins for s in eng.store.sealed} == {n_new}
    h = sup.health()
    assert h["jobs"]["distill"]["retries"] == 1
    assert h["jobs"]["distill"]["succeeded"] == 1


def test_quarantine_engages_and_healthy_probe_clears():
    """N consecutive exhausted launches of one (op, key) quarantine the
    pair (further launches refused for the probation window); a failed
    probe restarts probation; a healthy probe clears the quarantine."""
    cfg, mapping, idx = _fixture()
    t = [0.0]  # injectable clock: probation windows advance on demand
    sup = JobSupervisor(
        SupervisionPolicy(max_retries=0, quarantine_after=2, probation=30.0),
        clock=lambda: t[0],
    )
    eng = _multi_segment_engine(cfg, mapping, idx, supervisor=sup)
    eng.delete([3])
    store = eng.store
    with faults.scoped(faults.FaultPlan(
        {"compact.work": faults.FaultSpec("raise")}
    )):
        for _ in range(2):
            assert store.compact_async() is True
            assert store.wait_compaction() is None  # failed, not raised
        assert sup.health()["quarantined"], "2 failures must quarantine"
        assert store.compact_async() is False  # refused inside probation
        assert sup.health()["jobs"]["compact"]["refused"] == 1
        t[0] = 31.0  # probation over: exactly one probe is admitted...
        assert store.compact_async() is True
        assert store.wait_compaction() is None  # ...and it fails too
        assert store.compact_async() is False  # probation restarted
    # faults cleared + probation lapsed: the healthy probe clears it
    t[0] = 62.0
    assert store.compact_async() is True
    assert store.wait_compaction() is not None
    h = sup.health()
    assert h["quarantined"] == []
    assert h["jobs"]["compact"]["succeeded"] == 1


def test_watchdog_abandons_stalled_job_without_swapping():
    """A hung worker is abandoned at the deadline: terminal failure, no
    retry (threads would pile up), and its late result is never swapped."""
    cfg, mapping, idx = _fixture()
    sup = JobSupervisor(SupervisionPolicy(max_retries=3, deadline=0.05))
    eng = _multi_segment_engine(cfg, mapping, idx, supervisor=sup)
    eng.delete([3])
    store = eng.store
    sealed_before = list(store.sealed)
    hold = threading.Event()
    assert store.compact_async(_hold=hold) is True
    q = jnp.asarray(idx[100:104])
    deadline = time.time() + 5.0
    while not sup.health()["abandoned"] and time.time() < deadline:
        eng.query(q, 3)  # serving never blocks on the hung job
        time.sleep(0.02)
    h = sup.health()
    assert h["abandoned"] == 1
    assert h["jobs"]["compact"]["retries"] == 0  # hangs are never retried
    assert store._compaction is None
    hold.set()  # let the zombie thread finish; its result must be dropped
    time.sleep(0.05)
    eng.query(q, 3)
    assert store.sealed == sealed_before  # no swap, segments untouched
    assert isinstance(h["last_error"]["error"], str)
    assert "deadline" in h["last_error"]["error"]


# ----------------------------------------------------- degraded-mode serving
def test_band_lookup_failure_degrades_to_exhaustive():
    """band.lookup raising on the query thread: every indexed segment
    serves exhaustively, results identical to prefilter=False, and the
    degradation is visible in health()."""
    cfg, mapping, idx = _fixture()
    eng = _multi_segment_engine(
        cfg, mapping, idx,
        band_policy=BandPolicy(n_bands=8, max_candidate_frac=1.0, min_rows=8),
    )
    q = jnp.asarray(idx[100:108])
    exact = eng.query(q, 5, prefilter=False)
    with faults.scoped(faults.FaultPlan(
        {"band.lookup": faults.FaultSpec("raise")}
    )):
        got = eng.query(q, 5)  # banded by default; must not raise
    assert_topk_equivalent(got, exact)
    deg = {d["component"]: d for d in eng.health()["degraded"]}
    assert "band_lookup" in deg and deg["band_lookup"]["count"] >= 1


def test_band_build_failure_at_seal_degrades_not_raises():
    """band.build raising at seal time: the segment comes out unindexed
    (exhaustive member), the seal succeeds, queries stay exact."""
    cfg, mapping, idx = _fixture()
    eng = SketchEngine.build(
        cfg, mapping, backend="oracle", mutable=True,
        band_policy=BandPolicy(n_bands=8, min_rows=8),
    )
    with faults.scoped(faults.FaultPlan(
        {"band.build": faults.FaultSpec("raise")}
    )):
        eng.add(jnp.asarray(idx[:48]))
        eng.seal()
    assert eng.store.sealed[0].band_index is None
    deg = {d["component"] for d in eng.health()["degraded"]}
    assert "band_index" in deg
    q = jnp.asarray(idx[100:106])
    ref = SketchEngine.build(cfg, mapping, jnp.asarray(idx[:48]),
                             backend="oracle")
    assert_topk_equivalent(eng.query(q, 5), ref.query(q, 5))


def test_serve_exits_nonzero_on_fault_without_chaos():
    """A band-hash build failure degrades the sealed segment to unindexed
    and serving stays exact — but a serve run that was not asked to inject
    faults (no --chaos) must not exit 0 over it: the fallback would
    otherwise hide a real fault, such as a kernel the device refused."""
    from repro.launch import serve

    with faults.scoped(faults.FaultPlan(
        {"band.build": faults.FaultSpec("raise")}
    )):
        with pytest.raises(SystemExit, match="degraded band_index"):
            serve.main(["--dataset", "tiny", "--prefilter", "--queries", "8",
                        "--backend", "oracle"])


def test_placement_failure_falls_back_to_sliced_path():
    """placement.build raising: query_sharded serves through the sliced
    exhaustive path — same results — and records the degradation."""
    cfg, mapping, idx = _fixture()
    eng = _multi_segment_engine(cfg, mapping, idx)
    mesh = jax.make_mesh((1,), ("data",))
    q = jnp.asarray(idx[100:106])
    want = eng.query_sharded(mesh, "data", q, 5)  # healthy placed baseline
    eng._placement = None
    with faults.scoped(faults.FaultPlan(
        {"placement.build": faults.FaultSpec("raise")}
    )):
        got = eng.query_sharded(mesh, "data", q, 5)
    assert_topk_equivalent(got, want)
    deg = {d["component"] for d in eng.health()["degraded"]}
    assert "placement" in deg
    # faults gone: the placed path re-arms transparently
    assert_topk_equivalent(eng.query_sharded(mesh, "data", q, 5), want)


def test_full_chaos_cycle_zero_query_exceptions(tmp_path):
    """The acceptance scenario: a seeded FaultPlan across compaction,
    band build/lookup and checkpoint writes (including one torn leaf)
    while a mutate/maintain/query/save loop runs — zero query-path
    exceptions, final results identical to a fresh rebuild over
    survivors, and restore landing on the newest verifying checkpoint."""
    cfg, mapping, idx = _fixture()
    sup = JobSupervisor(FAST)
    eng = _multi_segment_engine(
        cfg, mapping, idx, supervisor=sup,
        band_policy=BandPolicy(n_bands=8, max_candidate_frac=1.0, min_rows=8),
    )
    mgr = CheckpointManager(str(tmp_path), keep=4, supervisor=sup)
    q = jnp.asarray(idx[100:108])
    deleted = {3, 30, 70}
    plan = faults.FaultPlan(
        {
            # launch 1: both attempts fail (2 firings); launch 2: first
            # attempt fails (3rd firing), its retry succeeds
            "compact.work": faults.FaultSpec("raise", times=3),
            "band.lookup": faults.FaultSpec("raise", times=4),
            "checkpoint.write": faults.FaultSpec("raise", times=1),
            "checkpoint.leaf": faults.FaultSpec("torn-write", times=1,
                                                after=20),
        },
        seed=1234,
    )
    with faults.scoped(plan):
        eng.delete(sorted(deleted))
        for round_i in range(3):
            eng.store.compact_async()
            for _ in range(3):
                eng.query(q, 5)  # drives poll + any retries; must not raise
                time.sleep(0.005)
            eng.store.wait_compaction()
            eng.store.save(mgr, step=round_i + 1, blocking=False)
        mgr.wait()
    assert plan.total_fired >= 5, "the chaos plan must actually have fired"
    h = sup.health()
    assert h["jobs"]["compact"]["failed"] >= 1
    assert h["retries"] >= 2
    surv = np.asarray(sorted(set(range(96)) - deleted))
    fresh = SketchEngine.build(cfg, mapping, jnp.asarray(idx[surv]),
                               backend="oracle")
    sc_f, id_f = fresh.query(q, 5)
    id_f = np.where(np.asarray(id_f) >= 0,
                    surv[np.maximum(np.asarray(id_f), 0)], -1)
    assert_topk_equivalent(eng.query(q, 5, prefilter=False), (sc_f, id_f),
                           truth=topk_truth(fresh, q, id_map=surv))
    # restore-after-chaos lands on the newest generation that verifies,
    # and the restored store serves the same survivors
    step = mgr.resolve_step(None)
    assert step is not None and mgr.verify_step(step)
    back = SegmentedStore.restore(mgr)
    assert back.size == len(surv)


def test_injected_faults_show_as_metric_deltas():
    """Telemetry x chaos (DESIGN.md §14): injected faults must be visible
    as counter deltas in the armed metrics registry — a band.build failure
    at seal lands as ``degraded.band_index``, and a band.lookup failure on
    the query path lands as ``degraded.band_lookup`` plus the trace-side
    ``query.degraded.band_lookup`` twin."""
    from repro import obs
    from repro.obs import trace as obs_trace

    cfg, mapping, idx = _fixture()
    eng = _multi_segment_engine(
        cfg, mapping, idx, n=48, seal_rows=48,
        band_policy=BandPolicy(n_bands=4, min_rows=8),
    )
    reg = obs.enable()
    try:
        before = reg.counter("degraded.band_index")
        with faults.scoped(faults.FaultPlan(
            {"band.build": faults.FaultSpec("raise")}
        )):
            eng.add(jnp.asarray(idx[48:96]))
            eng.seal()  # index build fails -> unindexed segment, recorded
        assert reg.counter("degraded.band_index") == before + 1
        before_q = reg.counter("degraded.band_lookup")
        with faults.scoped(faults.FaultPlan(
            {"band.lookup": faults.FaultSpec("raise")}
        )):
            eng.query(jnp.asarray(idx[:4]), 5)  # degrades; must not raise
        assert reg.counter("degraded.band_lookup") > before_q
        assert reg.counter("query.degraded.band_lookup") >= 1
        assert "band_lookup" in obs_trace.active().last()["degraded"]
    finally:
        obs.disable()


# ----------------------------------------------------- lifecycle controller
def test_controller_tick_failures_quarantine_without_stalling_serving():
    """A controller tick that raises (here: the probe-feed callback dies)
    is recorded by the supervisor and never reaches serving; consecutive
    failures quarantine the ("lifecycle", ("tick",)) pair — further ticks
    are refused, not run — and a healthy tick after probation clears it."""
    cfg, mapping, idx = _fixture()
    t = [0.0]  # injectable clock: probation windows advance on demand
    sup = JobSupervisor(
        SupervisionPolicy(max_retries=0, quarantine_after=2, probation=30.0),
        clock=lambda: t[0],
    )
    eng = _multi_segment_engine(cfg, mapping, idx, supervisor=sup)
    probe = RecallProbe(eng, clock=lambda: t[0])

    def bad_feed():
        raise RuntimeError("catalog service down")

    ctl = LifecycleController(
        eng, ControllerPolicy(probe_interval=1.0),
        probe=probe, probe_feed=bad_feed, clock=lambda: t[0])
    q = jnp.asarray(idx[100:104])
    for _ in range(2):
        t[0] += 2.0  # past the probe interval: the feed gets consulted
        assert ctl.tick() is None  # recorded, not raised
        eng.query(q, 3)  # serving is unaffected between failing ticks
    assert ctl.failed_ticks == 2
    h = sup.health()
    assert h["jobs"]["lifecycle"]["failed"] == 2
    assert h["quarantined"] and h["quarantined"][0]["op"] == "lifecycle"
    t[0] += 2.0
    assert ctl.tick() is None  # refused inside probation, body never runs
    assert sup.health()["jobs"]["lifecycle"]["refused"] == 1
    assert ctl.failed_ticks == 3
    # the feed recovers and probation lapses: the probe tick is admitted,
    # succeeds, and clears the quarantine — the loop heals itself
    ctl.probe_feed = lambda: (np.arange(32), idx[:32])
    t[0] = 60.0
    r = ctl.tick()
    assert r is not None and r["state"] == "steady"
    assert sup.health()["quarantined"] == []
    assert ctl.ticks >= 1 and eng.metrics()["controller"]["failed_ticks"] == 3


def test_controller_hung_merge_abandoned_then_tier_retried():
    """A merge the controller launched hangs (injected delay past the
    watchdog deadline): the supervisor abandons it on a later tick's poll,
    nothing swaps, and the same tick re-launches the still-over-fanout
    tier — which completes once the transient hang has cleared."""
    cfg, mapping, idx = _fixture()
    sup = JobSupervisor(SupervisionPolicy(max_retries=3, deadline=0.05))
    eng = _multi_segment_engine(cfg, mapping, idx, n=96, seal_rows=24,
                                supervisor=sup)  # 4 segments == fanout
    ctl = LifecycleController(eng, ControllerPolicy(tier_min_rows=24))
    q = jnp.asarray(idx[100:104])
    with faults.scoped(faults.FaultPlan(
        {"compact.work": faults.FaultSpec("delay", delay_s=0.5, times=1)}
    )):
        r = ctl.tick(now=1.0)
        assert r["action"]["kind"] == "merge"  # launched into the hang
        deadline = time.time() + 5.0
        while time.time() < deadline:
            time.sleep(0.08)
            eng.query(q, 3)  # serving never blocks on the hung worker
            r = ctl.tick(now=2.0)
            if sup.health()["abandoned"]:
                break
        h = sup.health()
        assert h["abandoned"] == 1
        assert h["jobs"]["compact"]["retries"] == 0  # hangs are not retried
        assert r["action"]["kind"] == "merge", \
            "the abandoning tick must re-launch the over-fanout tier"
    assert eng.store.wait_compaction() is not None
    assert len(eng.store.sealed) == 1
    assert eng.store.sealed[0].n_live == 96
    assert ctl.merges == 2
