"""``correct`` comes out false for each cell's control and for every fault a
cell can have, planted under the harness at a tiny size."""

import pytest

from bench_testlib import YCSB_CELL, root_for, run_tiny
from bench import check, control


@pytest.mark.parametrize("workload,system,number", [
    ("nytimes-ro-mlt32", control.Bfloat16Reference, "score_gap"),
    ("nytimes-seg-ingest", control.Distilled, "wrong_sketches"),
    ("nytimes-ro-mlt32", control.AlteredAnswer, "rank_gap"),
    ("nytimes-ro-mlt32", control.HalfBatch, "rank_gap"),
    ("nytimes-seg-ingest", control.UnchangedState, "lost_docs"),
    (YCSB_CELL, control.UnchangedUpdate, "wrong_sketches"),
    (YCSB_CELL, control.UntombstonedUpdate, "lost_docs"),
    (YCSB_CELL, control.StaleReads, "score_gap"),
])
def test_control_and_faults_are_not_correct(workload, system, number, tmp_path):
    out = run_tiny(workload, seconds=0.3, system=system, root=root_for(workload, tmp_path))
    assert out["correct"] is False
    assert out["checks"][number]["value"] > check.LIMITS[number], out["checks"]


def test_the_controls_cover_every_configuration():
    from bench_testlib import REPO
    import json

    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    stores = {json.loads((REPO / c["file"]).read_text())["store"] for c in bench["configs"]}
    assert stores <= set(control.CONTROLS)
