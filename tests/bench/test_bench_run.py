"""A whole run of each cell at a tiny size on the CPU, past the chip check:
set-up, warm-up, window, the reference check and the result line."""

import pytest

from bench_testlib import run_tiny


@pytest.mark.parametrize("workload,metrics", [
    ("nytimes-ro-mlt32", ["query_qps", "query_p90_ms", "recall_at_10", "setup_s"]),
    ("nytimes-seg-ingest", ["ingest_docs_s", "setup_s"]),
])
def test_a_tiny_run_is_correct_and_compiles_nothing_in_the_window(workload, metrics):
    out = run_tiny(workload, seconds=0.5)
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out["metrics"]) == metrics
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert list(out)[-2] == "checks"  # the result line ends with the checks
    assert all(c["value"] <= c["limit"] for c in out["checks"].values())
    window = [line for line in out["_log"] if "compiles inside the window" in line]
    assert window and window[0].endswith(" 0 compiles inside the window")
    assert out["_log"][-1].startswith("check ")


def test_a_tiny_ycsb_run_is_correct(tmp_path):
    """YCSB-B on the segmented store: 95 % queries and 5 % updates over Zipf
    keys, each answer judged against the corpus as it stood when sent. The
    warm-up takes each op in turn, so updates are followed from set-up on."""
    from bench_testlib import YCSB_CELL, root_for

    out = run_tiny(YCSB_CELL, seconds=0.5, root=root_for(YCSB_CELL, tmp_path))
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out["metrics"]) == ["query_qps", "query_p90_ms", "recall_at_10", "setup_s"]
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert list(out["checks"]) == ["failed_requests", "rank_gap", "score_gap", "lost_docs",
                                   "wrong_sketches"]
    updates = [line for line in out["_log"] if line.startswith("updates: ")]
    assert updates and not updates[0].startswith("updates: 0 ")
    assert any("0 inserts and" in line and "updates compared" in line for line in out["_log"])
