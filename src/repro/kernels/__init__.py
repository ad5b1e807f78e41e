"""TPU Pallas kernels for the paper's compute hot spots.

| kernel | file | hot spot |
|---|---|---|
| build_sketch | sketch_build.py | sketch construction (per-word compare-OR, packed emission) |
| hash_build_sketch | hash_build.py | fused multiply-shift hash + construction (tera-scale d: no pi table, indices stream from HBM once) |
| sketch_score | popcount_sim.py | Q x C retrieval scoring (AND-popcount + fused Alg 1/3/4 epilogue) |
| sketch_topk | topk_stream.py | serving hot path: fused streaming score -> top-k, O(Q·k) HBM output instead of the (Q, C) matrix (DESIGN.md §7) |

``ops`` holds the jit'd public wrappers, ``ref`` the pure-jnp oracles.
Off-TPU the kernels run in interpret mode (correctness-validated on CPU).
"""

from . import ops, ref  # noqa: F401
