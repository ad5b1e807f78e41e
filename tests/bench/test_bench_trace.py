"""The trace reductions: on a hand-made trace with known answers, and on a
small trace recorded from a ``--trace 1`` chip run of each cell."""

import importlib.util
import pathlib
import types

import numpy as np
import pytest

from bench_testlib import PEAKS, REPO
from bench import tracefile
from bench.tracefile import Trace

HERE = pathlib.Path(__file__).resolve().parent


def _hand_made():
    # window [0, 100); two requests [10, 40) and [50, 90); ops overlap and
    # nest; one op straddles the window's start
    ops = np.array([[-5, 5], [12, 20], [15, 18], [19, 30], [55, 60], [70, 95]], float)
    mods = np.array([[-6, 6], [11, 31], [54, 61], [69, 96]], float)
    spans = [("bench.window", 0, 100, {}),
             ("bench.request", 10, 40, {"kind": "query"}),
             ("bench.query", 11, 32, {}),
             ("bench.request", 50, 90, {"kind": "query"}),
             ("bench.fetch", 60, 70, {})]
    names = ["a:%x", "b:%k", "b:%k", "b:%y", "c:%k", "d:%z"]
    return Trace((0, 100), [ops], [names], [mods], [["a", "b", "c", "d"]], spans)


def test_union_busy_idle_and_programs_on_a_hand_made_trace():
    tr = _hand_made()
    # busy in window: [0,5) + [12,30) + [55,60) + [70,95) = 5 + 18 + 5 + 25
    assert tracefile.busy_ns(tr) == 53
    assert tracefile.idle_share_pct(tr) == pytest.approx(47.0)
    assert tracefile.window_s(tr) == pytest.approx(100e-9)
    # inside requests: [12,30) + [55,60) + [70,90) = 18 + 5 + 20
    assert tracefile.busy_ns(tr, within=tr.request_spans("query")) == 43
    assert tracefile.programs_in(tr, tr.request_spans("query")) == 3
    top = dict(tracefile.top_ops(tr))
    assert top["d:%z"] == pytest.approx(25e-9) and top["b:%k"] == pytest.approx(11e-9)  # 8 + 3, nested
    gaps = tracefile.idle_gaps(tr)
    assert gaps == [["outside requests", pytest.approx(25e-9)],  # [30, 55)
                    ["bench.fetch", pytest.approx(10e-9)],  # [60, 70)
                    ["outside requests", pytest.approx(7e-9)],  # [5, 12)
                    ["outside requests", pytest.approx(5e-9)]]  # [95, 100)


def test_a_hand_made_trace_survives_its_json_file(tmp_path):
    tr = _hand_made()
    tr.save(str(tmp_path / "t.json.gz"))
    back = Trace.open(str(tmp_path / "t.json.gz"))
    assert back.window == tr.window and back.op_names == tr.op_names
    np.testing.assert_array_equal(back.ops[0], tr.ops[0])
    assert tracefile.busy_ns(back) == 53


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        f"reader_{name}", REPO / "bench" / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


RECORDED = sorted(HERE.glob("trace_*.json.gz"))


@pytest.mark.parametrize("path", RECORDED, ids=[p.name for p in RECORDED])
def test_reductions_on_a_recorded_chip_trace(path):
    tr = Trace.open(str(path))
    kind = "query" if "mlt32" in path.name else "insert"
    spans = tr.request_spans(kind)
    assert len(spans) >= 2
    busy = tracefile.busy_ns(tr)
    inside = tracefile.busy_ns(tr, within=spans)
    assert 0 < inside <= busy <= tr.window[1] - tr.window[0]
    # device work of a request happens inside its span: little busy time
    # falls between requests in a closed loop with one client
    assert inside > 0.8 * busy
    idle = tracefile.idle_share_pct(tr)
    assert 0.0 < idle < 100.0
    per = tracefile.programs_in(tr, spans) / len(spans)
    assert per >= 1
    traced = [{"op": kind, "docs": 32 if kind == "query" else 1024, "live": 300_000}
              for _ in spans]
    cfg = {"n_bins": 34851, "n_words": 1090}
    ctx = types.SimpleNamespace(trace=tr, traced=traced, cfg=cfg, peaks=PEAKS,
                                note=lambda m: None)
    if kind == "query":
        share = _reader("query_score_roofline_pct").read(ctx)
        assert 0 < share < 100
        assert _reader("programs_per_query_request").read(ctx) == per
        assert _reader("device_idle_share.query").read(ctx) == pytest.approx(idle)
    else:
        us = _reader("ingest_device_us_per_doc").read(ctx)
        assert us == pytest.approx(inside / 1e3 / (1024 * len(spans)))
        assert _reader("device_idle_share.ingest").read(ctx) == pytest.approx(idle)
