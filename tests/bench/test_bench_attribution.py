"""Device time charged to the program's own spans (``bench/attribution.py``):
the link from a host launch to its device program and the union of device
time under a span, on hand-made traces with known answers; the readers built
on them, on a small trace recorded from a ``--trace 1`` chip run of each cell
whose program opens ``repro.*`` spans; and, on the two recorded traces of a
program without those spans, None from the new readers and the same numbers
as before from every old reduction and reader."""

import importlib.util
import pathlib
import types

import numpy as np
import pytest

from bench_testlib import PEAKS, REPO
from bench import attribution, tracefile
from bench.attribution import ProgramTrace
from bench.tracefile import Trace

HERE = pathlib.Path(__file__).resolve().parent
CFG = {"n_bins": 34851, "n_words": 1090}

ADD, COUNT, WRITE = "repro.store.add", "repro.store.count", "repro.store.head_write"
SPANS = [(ADD, 0, 100, "main"), (COUNT, 10, 30, "main"), (WRITE, 40, 60, "main"),
         ("repro.store.compact", 0, 100, "worker")]


@pytest.mark.parametrize("launch,stack", [
    (("main", 5), (ADD,)),
    (("main", 20), (ADD, COUNT)),  # outermost first
    (("main", 50), (ADD, WRITE)),
    (("worker", 20), ("repro.store.compact",)),  # a span on another thread is not open here
    (("main", 150), ()),  # launched outside every span
    (("main", 30), (ADD,)),  # a span's end is not inside it
    (None, ()),  # no launch found
])
def test_a_program_gets_the_spans_open_where_it_was_launched(launch, stack):
    assert attribution.stacks_at(SPANS, [launch]) == [stack]


def _host_events():
    """A TPU runtime's host events, as a profile records them: the Python
    thread's launch call is a flow producer (``_pt`` 14); the runtime's
    execute on its own line of the same thread consumes it and produces
    (``_pt`` 7) what enqueues the program, on that thread or, deferred, on a
    task thread; the enqueue produces (``_pt`` 12) the device program. On
    the CPU the launch carries the program's ``run_id`` on the Python line."""
    host = attribution._HostEvents()
    host.add_thread("python", [
        (100, 101, {"_pt": 14, "_p": 1}), (205, 206, {"_pt": 14, "_p": 2}),
        (400, 450, {"run_id": 9})])
    host.add_thread("runtime main", [
        (101, 200, {"_ct": 14, "_c": 1}), (120, 180, {"_pt": 7, "_p": 11}),
        (130, 170, {"_ct": 7, "_c": 11}), (140, 160, {"_pt": 12, "_p": 21, "run_id": 5}),
        (206, 260, {"_ct": 14, "_c": 2}), (220, 250, {"_pt": 7, "_p": 12})])
    host.add_thread("runtime task", [
        (290, 330, {"_ct": 7, "_c": 12}), (300, 320, {"_pt": 12, "_p": 22, "run_id": 6}),
        (340, 350, {"run_id": 6})])
    return host


@pytest.mark.parametrize("program,launch", [
    ({"_ct": 12, "_c": 21, "run_id": 5}, ("python", 100)),  # enqueued inside the call
    ({"_ct": 12, "_c": 22, "run_id": 6}, ("python", 205)),  # enqueued later, elsewhere
    ({"run_id": 6}, ("python", 205)),  # no flow stat: the run's first host event
    ({"run_id": 9}, ("python", 400)),  # the CPU's launch
    ({"run_id": 77}, None),
])
def test_a_launch_is_followed_back_to_the_calling_thread(program, launch):
    assert _host_events().launch(program) == launch


def _hand_made() -> ProgramTrace:
    # window [0, 100); one insert request [5, 95); three programs: the
    # first launched under count, the second under head_write, the third
    # under no span; ops nest inside the first two
    mods = np.array([[10, 40], [40, 70], [70, 90]], float)
    ops = np.array([[10, 30], [12, 20], [32, 38], [40, 60], [45, 50], [62, 70], [70, 90]],
                   float)
    names = ["a:%count_update.1", "a:%fusion", "a:%copy", "b:%scatter", "b:%dus", "b:%copy",
             "c:%x"]
    spans = [("bench.window", 0, 100, {}), ("bench.request", 5, 95, {"kind": "insert"}),
             (ADD, 6, 94, {"docs": "4"}), (COUNT, 7, 8, {"docs": "4"}),
             (WRITE, 8, 9, {"docs": "4"}), ("repro.store.seal", 9, 10, {"rows": "8"})]
    stacks = [(ADD, COUNT), (ADD, WRITE), ()]
    return ProgramTrace((0, 100), [ops], [names], [mods], [["a", "b", "c"]], spans,
                        [stacks])


@pytest.mark.parametrize("span,within,op,ns", [
    (COUNT, None, None, 26),  # [10, 30) holds [12, 20): counted once, + [32, 38)
    (WRITE, None, None, 28),  # [40, 60) + [62, 70)
    (ADD, None, None, 54),
    (None, None, None, 54),  # any span
    ("repro.store.seal", None, None, 0),
    (WRITE, [[0, 50]], None, 10),
    (COUNT, None, "count_update", 20),
    (COUNT, None, "copy", 6),
])
def test_device_time_under_a_span_is_the_union_of_its_programs_ops(span, within, op, ns):
    tr = _hand_made()
    iv = None if within is None else np.asarray(within, float)
    assert attribution.busy_ns_under(tr, span, within=iv, op=op) == ns


def test_shares_idle_time_and_readers_on_a_hand_made_trace():
    tr = _hand_made()
    req = tr.request_spans("insert")
    # busy in [5, 95): [10, 30) + [32, 38) + [40, 60) + [62, 90) = 74
    assert attribution.charged_share_pct(tr, req) == pytest.approx(100 * 54 / 74)
    # inside [6, 94), the union of the repro spans: 88 - 74 busy
    assert attribution.program_idle_ns(tr, req) == 14
    notes = []
    ctx = types.SimpleNamespace(trace=tr, traced=[{"op": "insert", "docs": 4}], cfg=CFG,
                                peaks=PEAKS, note=notes.append)
    assert _reader("head_write_device_us_per_doc").read(ctx) == pytest.approx(28 / 1e3 / 4)
    assert _reader("count_device_us_per_doc").read(ctx) == pytest.approx(26 / 1e3 / 4)
    assert _reader("seal_device_ms").read(ctx) == 0
    assert _reader("program_idle_ms.ingest").read(ctx) == pytest.approx(14 / 1e6)
    assert "72.973 %" in notes[-1]
    # a gap inside a program span is named by it, not by the request
    assert tracefile.idle_gaps(tr)[:3] == [["bench.request", pytest.approx(10e-9)],  # [0, 10)
                                           ["outside requests", pytest.approx(10e-9)],
                                           [ADD, pytest.approx(2e-9)]]  # [30, 32)


def test_stacks_survive_the_json_file_and_a_file_without_them_reads_empty(tmp_path):
    tr = _hand_made()
    tr.save(str(tmp_path / "p.json.gz"))
    back = ProgramTrace.open(str(tmp_path / "p.json.gz"))
    assert back.stacks == tr.stacks and back.spans == [tuple(s) for s in tr.spans]
    assert attribution.busy_ns_under(back, COUNT) == 26
    Trace(*[getattr(tr, f) for f in ("window", "ops", "op_names", "modules", "module_names",
                                     "spans")]).save(str(tmp_path / "t.json.gz"))
    old = ProgramTrace.open(str(tmp_path / "t.json.gz"))
    assert old.stacks == [[(), (), ()]]
    assert not attribution.has_program_spans(old)


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        f"reader_{name}", REPO / "bench" / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


NEW_READERS = ["head_write_device_us_per_doc", "count_device_us_per_doc", "seal_device_ms",
               "score_overhead_ms", "program_idle_ms.query", "program_idle_ms.ingest"]


def _ctx(tr, kind, notes=None):
    spans = tr.request_spans(kind)
    traced = [{"op": kind, "docs": 32 if kind == "query" else 1024, "live": 300_000}
              for _ in spans]
    return types.SimpleNamespace(trace=tr, traced=traced, cfg=CFG, peaks=PEAKS,
                                 note=(notes if notes is not None else []).append)


# ---------------------------------------------- traces without program spans
OLD = {"trace_nytimes-ro-mlt32.json.gz": ("query", {
           "query_score_roofline_pct": 4.417756261819401,
           "programs_per_query_request": 11.5,
           "device_idle_share.query": 15.279517072370318}),
       "trace_nytimes-seg-ingest.json.gz": ("insert", {
           "ingest_device_us_per_doc": 134.77969360351562,
           "device_idle_share.ingest": 8.63283234723059})}


@pytest.mark.parametrize("path,name", [(p, n) for p, (_, v) in OLD.items() for n in v])
def test_old_readers_read_what_they_read_before(path, name):
    kind, want = OLD[path]
    for tr in (Trace.open(str(HERE / path)), ProgramTrace.open(str(HERE / path))):
        assert _reader(name).read(_ctx(tr, kind)) == pytest.approx(want[name], rel=1e-12)


@pytest.mark.parametrize("path", sorted(OLD))
def test_old_reductions_and_new_readers_on_a_trace_without_program_spans(path):
    kind, _ = OLD[path]
    old, new = Trace.open(str(HERE / path)), ProgramTrace.open(str(HERE / path))
    assert not attribution.has_program_spans(new)
    assert tracefile.idle_gaps(new) == tracefile.idle_gaps(old)
    assert tracefile.top_ops(new) == tracefile.top_ops(old)
    assert tracefile.busy_ns(new) == tracefile.busy_ns(old)
    for name in NEW_READERS:
        assert _reader(name).read(_ctx(new, kind)) is None, name
        assert _reader(name).read(_ctx(old, kind)) is None, name


# ------------------------------------------------ traces with program spans
RECORDED = {"program_trace_nytimes-seg-ingest.json.gz": ("insert", {
                "head_write_device_us_per_doc": 94.35637760416667,
                "count_device_us_per_doc": 40.491040690104164,
                "seal_device_ms": 2.911514,
                "program_idle_ms.ingest": 19.957154}),
            "program_trace_nytimes-ro-mlt32.json.gz": ("query", {
                "score_overhead_ms": 4.12341025,
                "program_idle_ms.query": 4.631071})}


@pytest.mark.parametrize("path,name", [(p, n) for p, (_, v) in RECORDED.items() for n in v])
def test_new_readers_on_a_recorded_chip_trace(path, name):
    kind, want = RECORDED[path]
    tr = ProgramTrace.open(str(HERE / path))
    assert _reader(name).read(_ctx(tr, kind)) == pytest.approx(want[name], rel=1e-12)


@pytest.mark.parametrize("path", sorted(RECORDED))
def test_every_program_of_a_recorded_request_is_charged_to_a_program_span(path):
    kind, _ = RECORDED[path]
    tr = ProgramTrace.open(str(HERE / path))
    notes = []
    _reader(f"program_idle_ms.{'ingest' if kind == 'insert' else 'query'}").read(
        _ctx(tr, kind, notes))
    assert attribution.charged_share_pct(tr, tr.request_spans(kind)) >= 99.0
    assert "% of the device's busy time" in notes[-1]


def test_a_recorded_insert_splits_into_count_head_write_and_seal():
    tr = ProgramTrace.open(str(HERE / "program_trace_nytimes-seg-ingest.json.gz"))
    ctx = _ctx(tr, "insert")
    parts = {n: _reader(n).read(ctx) for n in ("head_write_device_us_per_doc",
                                               "count_device_us_per_doc")}
    whole = _reader("ingest_device_us_per_doc").read(ctx)
    assert sum(parts.values()) <= whole
    # what is left is the one seal's device time, spread over the docs
    seal_us = _reader("seal_device_ms").read(ctx) * 1e3 / (1024 * len(ctx.traced))
    assert whole - sum(parts.values()) == pytest.approx(seal_us, rel=1e-6)
    # no idle gap is left to the harness's add: the store's spans name them
    named = [name for name, _ in tracefile.idle_gaps(tr, n=50)]
    assert "bench.add" not in named
    assert named[:2] == ["repro.store.index", "repro.store.seal"]


def test_a_recorded_query_splits_into_the_kernel_and_its_overhead():
    tr = ProgramTrace.open(str(HERE / "program_trace_nytimes-ro-mlt32.json.gz"))
    notes = []
    ctx = _ctx(tr, "query", notes)
    spans = tr.request_spans("query")
    stage = attribution.busy_ns_under(tr, "repro.query.kernel_score", within=spans)
    kernel = attribution.busy_ns_under(tr, "repro.query.kernel_score", within=spans,
                                       op="topk_stream")
    overhead = _reader("score_overhead_ms").read(ctx)
    assert overhead + kernel / 1e6 / len(spans) == pytest.approx(stage / 1e6 / len(spans))
    assert "topk_stream 34.1474 ms" in notes[-1]
