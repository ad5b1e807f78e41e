"""Shared set-up of the benchmark's CPU tests: a tiny cell of each store."""

import json
import pathlib
import shutil
import sys
import types

REPO = pathlib.Path(__file__).resolve().parents[2]
for p in (str(REPO), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import corpus, run, traffic  # noqa: E402

#: a corpus small enough for interpret-mode kernels on the CPU
TINY = {"n_docs": 512, "vocab": 2048, "mean_distinct": 40.0, "psi": 96, "rho": 0.1,
        "build_batch": 128, "seal_rows": 256}

FAKE_DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}
PEAKS = json.loads((REPO / "bench" / "peaks.json").read_text())["devices"]["TPU v5 lite"]

#: YCSB workload B (Cooper et al., SoCC 2010: 95 % reads, 5 % updates, Zipf
#: 0.99 keys) over the segmented NYTimes store, as a later change adds it: a
#: configuration file, a mix file and entries, and no file edited
YCSB_CELL, YCSB_CONFIG, YCSB_TRAFFIC = "nytimes-seg-ycsb-b", "nytimes-seg-ycsb", "ycsb-b32"
YCSB_MIX = {"loop": "closed", "clients": 1, "ops": {"query": 0.95, "update": 0.05},
            "keys": "zipf", "zipf_s": 0.99, "docs": 32, "k": 10, "pool_docs": 16384,
            "warmup_requests": 4, "check_queries": 4096, "findability_queries": 64,
            "check_sample": 4096, "trace_seconds": 5}


def checkout_with_ycsb(dst: pathlib.Path) -> pathlib.Path:
    """A copy of the benchmark under ``dst`` with the YCSB-B cell added as
    data: its configuration and mix files, and its entries."""
    shutil.copytree(REPO / "bench", dst / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    cfg = json.loads((REPO / "bench" / "configs" / "nytimes-segmented.json").read_text())
    cfg["name"] = YCSB_CONFIG
    (dst / "bench" / "configs" / f"{YCSB_CONFIG}.json").write_text(json.dumps(cfg))
    (dst / "bench" / "traffic" / f"{YCSB_TRAFFIC}.json").write_text(json.dumps(YCSB_MIX))
    bench["configs"].append({"name": YCSB_CONFIG, "source": "YCSB workload B", "reduced": [],
                             "file": f"bench/configs/{YCSB_CONFIG}.json", "why": "test"})
    bench["workloads"].append({"name": YCSB_CELL, "config": YCSB_CONFIG,
                               "traffic": YCSB_TRAFFIC, "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] in ("query_qps", "query_p90_ms", "recall_at_10"):
            m["workloads"].append(YCSB_CELL)
    (dst / "BENCHMARK.json").write_text(json.dumps(bench))
    return dst


def root_for(workload: str, tmp: pathlib.Path) -> pathlib.Path:
    """The checkout that holds ``workload``: this one, or for the YCSB-B
    cell while it is not in BENCHMARK.json, a copy under ``tmp`` with it."""
    names = {w["name"] for w in json.loads((REPO / "BENCHMARK.json").read_text())["workloads"]}
    if workload in names or workload != YCSB_CELL:
        return REPO
    return checkout_with_ycsb(tmp)


def tiny_spec(workload: str, root: pathlib.Path = REPO, **mix_overrides) -> types.SimpleNamespace:
    """The named cell of ``root``'s BENCHMARK.json with its configuration cut
    to TINY."""
    spec = run.load_spec(root, workload)
    cfg = dict(spec.cfg, **TINY)
    cfg["n_bins"] = corpus.theorem1_n_bins(cfg["psi"], cfg["rho"])
    cfg["n_words"] = corpus.n_words(cfg["n_bins"])
    mix = json.loads(json.dumps(spec.mix))
    if {"insert", "update"} & set(traffic.op_weights(mix)):
        mix["docs"], mix["pool_docs"] = 32, 128
    mix.update({"warmup_requests": 9 if cfg["store"] == "segmented" else 2,
                "check_queries": 64, "check_sample": 64, "trace_seconds": 1})
    mix.update(mix_overrides)
    spec.cfg, spec.mix = cfg, mix
    return spec


def run_tiny(workload: str, seed: int = 2**31 + 11, seconds: float = 1.0, trace=False,
             system=None, root: pathlib.Path = REPO, **mix_overrides) -> dict:
    spec = tiny_spec(workload, root, **mix_overrides)
    lines = []
    out = run.run_cell(spec, seed, seconds, trace, FAKE_DEVICE, PEAKS, system=system,
                       log=lines.append)
    out["_log"] = lines
    return out
