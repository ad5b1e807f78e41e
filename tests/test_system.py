"""End-to-end behaviour: the paper's pipeline (sketch -> estimate -> rank),
dedup application, serving driver, train-loop fault tolerance, dry-run
machinery on a small mesh."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import BinSketchConfig, make_mapping
from repro.core.index import SketchIndex
from repro.data.synthetic import DATASETS, generate_corpus, generate_similar_pairs


def test_ranking_pipeline_recall_high_similarity():
    """Paper §IV-B: for near-duplicate queries the sketch index must rank
    the true near-duplicate first."""
    spec = DATASETS["tiny"]
    a, b, js = generate_similar_pairs(spec, jaccard=0.9, n_pairs=32, seed=0)
    corpus = np.concatenate([a, np.full_like(a[:8], -1)])  # 32 targets + noise rows
    rng = np.random.default_rng(1)
    for i in range(8):  # noise docs
        w = rng.choice(spec.d, 40, replace=False)
        corpus[32 + i, :40] = np.sort(w)
    cfg = BinSketchConfig.from_sparsity(spec.d, spec.max_nnz, rho=0.05)
    mapping = make_mapping(cfg, jax.random.PRNGKey(0))
    index = SketchIndex.build(cfg, mapping, jnp.asarray(corpus))
    scores, ids = index.query(jnp.asarray(b), k=1)
    hit = (np.asarray(ids)[:, 0] == np.arange(32)).mean()
    assert hit >= 0.95, f"top-1 recall {hit} for 0.9-Jaccard pairs"


def test_dedup_finds_planted_duplicates():
    from repro.data.dedup import find_near_duplicates

    spec = DATASETS["tiny"]
    a, b, _ = generate_similar_pairs(spec, jaccard=0.95, n_pairs=8, seed=3)
    idx, _ = generate_corpus(spec, seed=9)
    docs = np.concatenate([idx[:48], a[:4], b[:4]])  # dups at (48..51, 52..55)
    pairs = find_near_duplicates(docs, spec.d, threshold=0.8, rho=0.05)
    found = {(i, j) for i, j, _ in pairs}
    for k in range(4):
        assert (48 + k, 52 + k) in found, f"planted dup {k} missed: {found}"


def test_serve_driver_runs_with_recall():
    from repro.launch import serve

    recall = serve.main(["--dataset", "tiny", "--queries", "16", "--topk", "5"]).recall
    assert recall is not None and recall > 0.3


def test_chip_smoke_refuses_cpu():
    """Off a TPU the chip smoke exits non-zero with a one-line reason before
    any phase runs, and prints no verdict line."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(repo, "chip_smoke.py")],
                       capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert '"ok"' not in r.stdout and "[" not in r.stdout


def test_compile_cache_location(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR, where set, is left to jax; otherwise the
    persistent cache goes to the fixed ``<checkout>/.jax_cache``."""
    from repro.launch import cache

    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert cache.enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == was
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert cache.enable_compile_cache() == str(cache.DEFAULT_DIR)
        assert jax.config.jax_compilation_cache_dir == str(cache.DEFAULT_DIR)
        assert (cache.DEFAULT_DIR.parent / "chip_smoke.py").is_file()
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_train_loop_checkpoint_restart(tmp_path):
    """Kill-and-restart: the restarted run resumes from the manifest."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(os.path.dirname(__file__)), "src")
    args = [sys.executable, "-m", "repro.launch.train", "--arch", "qwen2.5-14b",
            "--ckpt-dir", str(tmp_path), "--ckpt-every", "2"]
    r1 = subprocess.run(args + ["--steps", "4"], capture_output=True, text=True, env=env, timeout=600)
    assert r1.returncode == 0, r1.stderr
    r2 = subprocess.run(args + ["--steps", "6"], capture_output=True, text=True, env=env, timeout=600)
    assert r2.returncode == 0, r2.stderr
    assert "[resume] restored step 3" in r2.stdout, r2.stdout


def test_straggler_detector():
    from repro.launch.train import StragglerDetector

    d = StragglerDetector()
    flagged = [d.observe(i, 0.1) for i in range(20)]
    assert not any(flagged)
    assert d.observe(20, 1.0) is True  # 10x spike
    assert len(d.events) == 1


def test_hlo_analysis_trip_counts(multidevice):
    out = multidevice(
        """
import jax, jax.numpy as jnp
from repro.launch.hlo_analysis import analyze
def f(x, w):
    def body(c, _):
        return jnp.tanh(c @ w), None
    y, _ = jax.lax.scan(body, x, None, length=7)
    return y
c = jax.jit(f).lower(jax.ShapeDtypeStruct((128, 128), jnp.bfloat16),
                     jax.ShapeDtypeStruct((128, 128), jnp.bfloat16)).compile()
t = analyze(c.as_text())
assert t["flops"] == 7 * 2 * 128**3, t["flops"]
print("HLO_OK")
""",
        2,
    )
    assert "HLO_OK" in out


def test_dryrun_cell_small_mesh(multidevice):
    """The dry-run machinery end-to-end on an 8-device mesh with a smoke
    config — validates lowering + compile + roofline extraction offline."""
    out = multidevice(
        """
import jax, numpy as np
from repro.configs import get
from repro.launch.hlo_analysis import analyze
from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 2), ("data", "model"))
spec = get("deepseek-v2-lite-16b")
b = spec.build(mesh, shape_name="train_4k", smoke=True)
args = b["inputs"]("train_4k")
with mesh:
    compiled = jax.jit(b["steps"]["train"]).lower(*args).compile()
t = analyze(compiled.as_text())
assert t["flops"] > 0
mem = compiled.memory_analysis()
assert mem.temp_size_in_bytes >= 0
print("DRYRUN_OK", t["flops"], t["collective_bytes"])
""",
        8,
    )
    assert "DRYRUN_OK" in out
