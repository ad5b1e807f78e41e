"""The harness: what it refuses, and what it finds by name."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from bench_testlib import REPO, YCSB_CELL, checkout_with_ycsb, run_tiny
from bench import run


def _cli(cwd, *args, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_off_chip_it_exits_nonzero_and_prints_no_result():
    r = _cli(REPO, "--workload", "nytimes-ro-mlt32", "--seed", "3000000001",
             "--seconds", "10", "--trace", "0")
    assert r.returncode == 3, r.stderr
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr


def test_in_a_checkout_of_the_benchmark_alone_it_exits_nonzero(tmp_path):
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    for p in bench["paths"]:
        shutil.copytree(REPO / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    r = _cli(tmp_path, "--workload", "nytimes-seg-ingest", "--seed", "1", "--seconds", "10",
             "--trace", "1")
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "system under test" in r.stderr


def test_an_unknown_cell_or_device_kind_is_refused():
    with pytest.raises(run.Refused):
        run.load_spec(REPO, "no-such-cell")
    with pytest.raises(run.Refused) as e:
        run.load_peaks(REPO, "TPU v4")
    assert e.value.code == 2


def test_the_cells_resolve_their_files_and_metrics():
    q = run.load_spec(REPO, "nytimes-ro-mlt32")
    assert q.cfg["store"] == "readonly" and q.cfg["n_bins"] == 34851
    assert [m["name"] for m in q.e2e] == ["query_qps", "query_p90_ms", "recall_at_10",
                                          "setup_s"]
    assert {m["name"] for m in q.per_layer} == {
        "query_score_roofline_pct", "programs_per_query_request", "device_idle_share.query"}
    i = run.load_spec(REPO, "nytimes-seg-ingest")
    assert [m["name"] for m in i.e2e] == ["ingest_docs_s", "setup_s"]
    for m in q.per_layer + i.per_layer:
        reader = run.load_reader(REPO, m["name"])
        assert reader.UNIT == m["unit"]


def test_a_new_config_mix_and_metric_are_picked_up_by_name(tmp_path):
    """A later change adds files and entries; no existing file is edited."""
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    cfg = json.loads((REPO / "bench" / "configs" / "nytimes-readonly.json").read_text())
    cfg.update(name="kos-readonly", n_docs=3430, vocab=6906, psi=457, mean_distinct=103.0)
    cfg.pop("n_bins"), cfg.pop("n_words")
    (tmp_path / "bench" / "configs" / "kos-readonly.json").write_text(json.dumps(cfg))
    mix = {"loop": "closed", "clients": 1, "op": "query", "docs": 8, "k": 10,
           "warmup_requests": 2, "check_queries": 256, "trace_seconds": 5}
    (tmp_path / "bench" / "traffic" / "mlt8.json").write_text(json.dumps(mix))
    (tmp_path / "bench" / "metrics" / "queries_per_request.py").write_text(
        'UNIT = "queries"\n\ndef read(ctx):\n'
        '    q = [r for r in ctx.traced if r["op"] == "query"]\n'
        '    return sum(r["docs"] for r in q) / len(q) if q else None\n')
    bench["configs"].append({"name": "kos-readonly", "source": "UCI KOS", "reduced": [],
                             "file": "bench/configs/kos-readonly.json", "why": "test"})
    bench["workloads"].append({"name": "kos-ro-mlt8", "config": "kos-readonly",
                               "traffic": "mlt8", "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "queries_per_request", "unit": "queries",
                               "better": "higher", "source": "host_clock", "layer": "engine",
                               "moves": "query_qps", "workloads": ["kos-ro-mlt8"]})
    bench["end_to_end"][0]["workloads"].append("kos-ro-mlt8")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    spec = run.load_spec(tmp_path, "kos-ro-mlt8")
    assert spec.cfg["n_docs"] == 3430 and spec.mix["docs"] == 8
    assert [m["name"] for m in spec.e2e] == ["query_qps", "setup_s"]
    assert [m["name"] for m in spec.per_layer] == ["queries_per_request"]
    reader = run.load_reader(tmp_path, "queries_per_request")
    ctx = type("Ctx", (), {"traced": [{"op": "query", "docs": 8}] * 3})()
    assert reader.read(ctx) == 8
    # the cells already there are untouched by the additions
    assert run.load_spec(tmp_path, "nytimes-ro-mlt32").cfg == run.load_spec(
        REPO, "nytimes-ro-mlt32").cfg


def test_the_generator_draws_distinct_keys_and_cycles_the_pool():
    import numpy as np

    from bench import traffic

    cfg = {"n_docs": 1000, "vocab": 2048, "mean_distinct": 40.0, "psi": 96, "zipf_a": 1.3,
           "length_sigma": 0.5}
    standing = np.arange(1000 * 96, dtype=np.int32).reshape(1000, 96)
    mix = {"loop": "closed", "clients": 1, "op": "query", "docs": 8, "k": 10}
    gen = traffic.Generator(mix, cfg, 7, standing)
    reqs = [gen.next() for _ in range(125)]
    assert {r.op for r in reqs} == {"query"} and {r.k for r in reqs} == {10}
    rows = np.concatenate([r.idx[:, 0] for r in reqs]) // 96
    np.testing.assert_array_equal(np.sort(rows), np.arange(1000))  # each doc once
    again = traffic.Generator(mix, cfg, 7, standing)
    assert all((again.next().idx == r.idx).all() for r in reqs)
    ins = traffic.Generator(dict(mix, op="insert", docs=16, pool_docs=64), cfg, 7, standing)
    reqs = [ins.next() for _ in range(5)]
    assert [r.pool_lo for r in reqs] == [0, 16, 32, 48, 0]
    np.testing.assert_array_equal(reqs[0].idx, ins.pool[:16])
    np.testing.assert_array_equal(reqs[4].idx, reqs[0].idx)
    with pytest.raises(ValueError):
        traffic.validate(dict(mix, loop="open"))
    with pytest.raises(ValueError):
        traffic.validate(dict(mix, op="delete"))
    with pytest.raises(ValueError):  # an update needs a pool of contents
        traffic.validate(dict(mix, op="update"))


def test_a_segmented_config_and_a_ycsb_mix_make_a_cell_by_name(tmp_path):
    """The next cell, YCSB workload B on the segmented store, needs only a
    configuration file, a mix file and entries: no existing file is edited."""
    root = checkout_with_ycsb(tmp_path)
    spec = run.load_spec(root, YCSB_CELL)
    assert spec.cfg["store"] == "segmented" and spec.cfg["n_bins"] == 34851
    assert spec.mix["ops"] == {"query": 0.95, "update": 0.05} and spec.mix["keys"] == "zipf"
    assert [m["name"] for m in spec.e2e] == ["query_qps", "query_p90_ms", "recall_at_10",
                                             "setup_s"]
    for name in ("nytimes-ro-mlt32", "nytimes-seg-ingest"):
        assert run.load_spec(root, name).mix == run.load_spec(REPO, name).mix
    out = run_tiny(YCSB_CELL, root=root, seconds=0.5)
    assert out["correct"] is True, out["checks"]
    assert list(out["metrics"]) == ["query_qps", "query_p90_ms", "recall_at_10", "setup_s"]
    assert any(line.startswith("updates: ") for line in out["_log"])


def test_an_update_mix_on_a_read_only_store_is_refused(tmp_path):
    root = checkout_with_ycsb(tmp_path)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "ro-ycsb", "config": "nytimes-readonly",
                               "traffic": "ycsb-b32", "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    with pytest.raises(run.Refused) as e:
        run.load_spec(root, "ro-ycsb")
    assert e.value.code == 2 and "updates" in str(e.value)
    (root / "bench" / "traffic" / "ycsb-b32.json").write_text(json.dumps(
        {"loop": "closed", "clients": 1, "op": "query", "ops": {"query": 1.0}}))
    with pytest.raises(run.Refused) as e:  # a malformed mix is refused, not raised
        run.load_spec(root, YCSB_CELL)
    assert e.value.code == 2
