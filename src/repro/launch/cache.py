"""Where JAX's persistent compilation cache lives — one rule for every
entry point (``serve``, ``bench_engine``, ``chip_smoke.py``).

Called from each ``main``, never at import time. A cold process on a TPU
spends most of its set-up compiling Pallas kernels and step programs; with
the cache on, a second process on the same checkout reads them back.
"""

from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["enable_compile_cache"]

#: ``<checkout>/.jax_cache`` — a fixed path (gitignored), never derived
#: from a temp name, a pid or the time: a directory that moves never hits
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here. Otherwise the cache goes to :data:`DEFAULT_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
