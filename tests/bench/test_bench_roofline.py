"""The scoring roofline's yardstick at the NYTimes shapes, and the peaks."""

import importlib.util
import json

import pytest

from bench_testlib import REPO
from bench import run


def _reader():
    path = REPO / "bench" / "metrics" / "query_score_roofline_pct.py"
    spec = importlib.util.spec_from_file_location("roofline_under_test", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_work_and_bytes_of_one_request_at_nytimes_shapes():
    rf = _reader()
    peaks = run.load_peaks(REPO, "TPU v5 lite")
    ops, nbytes = rf.work(32, 300_000, 34_851, 1_090)
    assert ops == 2 * 32 * 300_000 * 34_851
    assert nbytes == 4 * 1_090 * 300_032 + 4 * 300_032
    assert ops / peaks["int8_ops_s"] * 1e3 == pytest.approx(1.70, abs=0.005)
    assert nbytes / peaks["hbm_bytes_s"] * 1e3 == pytest.approx(1.60, abs=0.005)
    t, bound = rf.least_s(32, 300_000, 34_851, 1_090, peaks)
    assert bound == "ops" and t * 1e3 == pytest.approx(1.70, abs=0.005)
    # a lone query reads the whole corpus for 1/32 of the work: bytes-bound
    assert rf.least_s(1, 300_000, 34_851, 1_090, peaks)[1] == "bytes"


def test_peaks_table_has_the_v5e_row_and_its_source():
    table = json.loads((REPO / "bench" / "peaks.json").read_text())
    assert "cloud.google.com/tpu/docs/v5e" in table["source"]
    assert table["devices"]["TPU v5 lite"] == {
        "bf16_flops_s": 197e12, "int8_ops_s": 393e12, "hbm_bytes_s": 819e9, "hbm_bytes": 16e9}
    with pytest.raises(run.Refused):
        run.load_peaks(REPO, "TPU v9 imaginary")
