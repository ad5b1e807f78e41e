"""Compile the main-path Pallas kernels for a described TPU v5e.

Every other kernel test runs in interpret mode, which cannot see what the
chip's compiler refuses: block shapes off the (8, 128) tile, reshapes and
gathers Mosaic cannot lower, more VMEM than a kernel may use. These tests
lower each ``kernels.ops`` entry point with ``interpret=False`` against a
``v5e:2x2`` topology described without a chip, at the NYTimes published
width (N = 34,851 bins, W = 1,090 words), and check that the compiled
program holds the Mosaic kernel. Nothing runs; no chip is needed.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import BinSketchConfig
from repro.data.synthetic import DATASETS
from repro.kernels import ops


def _width(name):
    spec = DATASETS[name]
    return BinSketchConfig.from_sparsity(spec.d, spec.max_nnz, 0.05).n_bins


N_NYT = _width("nytimes-full")  # 34,851 bins, 1,090 words
N_TINY = _width("tiny")  # 1,278 bins, 40 words
P_NYT = DATASETS["nytimes-full"].max_nnz


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler installed here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _nwords(n_bins):
    return (n_bins + 31) // 32


CASES = {
    "sketch_build": (lambda b: ops.build_sketch(b, N_NYT, interpret=False),
                     [((1024, P_NYT), jnp.int32)]),
    "hash_build": (lambda i, c: ops.hash_build_sketch(i, c, N_NYT,
                                                      interpret=False),
                   [((1024, P_NYT), jnp.int32), ((2,), jnp.uint32)]),
    "count_update": (lambda b: ops.count_bins(b, N_NYT, interpret=False),
                     [((1024, P_NYT), jnp.int32)]),
    "popcount_sim": (lambda a, b: ops.sketch_score(a, b, N_NYT,
                                                   interpret=False),
                     [((32, _nwords(N_NYT)), jnp.uint32),
                      ((8192, _nwords(N_NYT)), jnp.uint32)]),
    "topk_stream": (lambda a, b: ops.sketch_topk(a, b, N_NYT, k=10,
                                                 interpret=False),
                    [((32, _nwords(N_NYT)), jnp.uint32),
                     ((300000, _nwords(N_NYT)), jnp.uint32)]),
    "rebucket": (lambda a: ops.rebucket(a, N_NYT, N_NYT // 2, interpret=False),
                 [((16384, _nwords(N_NYT)), jnp.uint32)]),
    "band_hash": (lambda a: ops.band_hash(a, 8, interpret=False),
                  [((16384, _nwords(N_NYT)), jnp.uint32)]),
    # the fused top-k at the tiny width (W = 40 < one lane tile), and the
    # small shapes the planner and the prefilter gather produce
    "topk_stream_tiny": (lambda a, b: ops.sketch_topk(a, b, N_TINY, k=10,
                                                      interpret=False),
                         [((32, _nwords(N_TINY)), jnp.uint32),
                          ((256, _nwords(N_TINY)), jnp.uint32)]),
    "popcount_sim_gather": (lambda a, b: ops.sketch_score(a, b, N_NYT // 2,
                                                          interpret=False),
                            [((8, _nwords(N_NYT // 2)), jnp.uint32),
                             ((16, _nwords(N_NYT // 2)), jnp.uint32)]),
}


@pytest.mark.parametrize("kernel", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, kernel):
    fn, shapes = CASES[kernel]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
