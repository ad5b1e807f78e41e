"""Shared set-up of the benchmark's CPU tests: a tiny cell of each store."""

import json
import pathlib
import sys
import types

REPO = pathlib.Path(__file__).resolve().parents[2]
for p in (str(REPO), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import corpus, run  # noqa: E402

#: a corpus small enough for interpret-mode kernels on the CPU
TINY = {"n_docs": 512, "vocab": 2048, "mean_distinct": 40.0, "psi": 96, "rho": 0.1,
        "build_batch": 128, "seal_rows": 256}

FAKE_DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}
PEAKS = json.loads((REPO / "bench" / "peaks.json").read_text())["devices"]["TPU v5 lite"]


def tiny_spec(workload: str, **mix_overrides) -> types.SimpleNamespace:
    """The named cell of BENCHMARK.json with its configuration cut to TINY."""
    spec = run.load_spec(REPO, workload)
    cfg = dict(spec.cfg, **TINY)
    cfg["n_bins"] = corpus.theorem1_n_bins(cfg["psi"], cfg["rho"])
    cfg["n_words"] = corpus.n_words(cfg["n_bins"])
    mix = json.loads(json.dumps(spec.mix))
    if mix["op"] == "insert":
        mix["docs"], mix["pool_docs"] = 32, 128
    mix.update({"warmup_requests": 9 if cfg["store"] == "segmented" else 2,
                "check_queries": 64, "check_sample": 64, "trace_seconds": 1})
    mix.update(mix_overrides)
    spec.cfg, spec.mix = cfg, mix
    return spec


def run_tiny(workload: str, seed: int = 2**31 + 11, seconds: float = 1.0, trace=False,
             system=None, **mix_overrides) -> dict:
    spec = tiny_spec(workload, **mix_overrides)
    lines = []
    out = run.run_cell(spec, seed, seconds, trace, FAKE_DEVICE, PEAKS, system=system,
                       log=lines.append)
    out["_log"] = lines
    return out
