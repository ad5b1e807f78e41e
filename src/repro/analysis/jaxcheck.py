"""Trace-level JAX analyzers (DESIGN.md §15 family 2).

Three checks that *run* the stack under tracing instead of reading its
source:

  * ``check_recompilation`` — builds a tiny engine, queries it across
    every :class:`QueryPlanner` bucket size, and asserts each jitted
    kernel entry point compiled exactly once per planned bucket shape.
    A query path that hands an unpadded batch to the kernels shows up
    as an extra cache entry (rule ``recompile-guard``).
  * ``check_host_sync`` — traces the hot query entry points to jaxprs
    and fails on callback / host-transfer primitives (rule
    ``host-sync``): one hidden ``pure_callback`` serializes every
    query behind a device→host round trip.
  * ``check_vmem_budget`` — intercepts ``pl.pallas_call`` while tracing
    every kernel wrapper at production-representative shapes, computes
    per-kernel block-residency bytes from the *actual* ``BlockSpec``s
    and scratch shapes, and gates them under a VMEM limit (rule
    ``vmem-budget`` — the DESIGN §7 table, executable).

This module is the one analyzer family that needs jax importable; the
CLI runner skips it (with a visible note) when jax is absent so the AST
families still run on a bare Python.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

from .findings import Finding
from .rules import trace_rule

__all__ = [
    "DEFAULT_VMEM_LIMIT", "KernelCall", "capture_pallas_calls",
    "check_host_sync", "check_recompilation", "check_vmem_budget",
    "kernel_call_bytes", "run_trace_checks",
]

_OPS_REL = "src/repro/kernels/ops.py"

#: every jitted entry point in kernels/ops.py, in __all__ order
_JIT_FNS = ("band_hash", "build_sketch", "count_bins", "hash_build_sketch",
            "rebucket", "sketch_score", "sketch_topk")

#: default per-kernel VMEM budget: 16 MiB of a TPU core's ~128 MiB,
#: leaving headroom for double buffering and the compiler's own spills.
DEFAULT_VMEM_LIMIT = 16 * 1024 * 1024


# ==========================================================================
# recompilation guard
# ==========================================================================

@trace_rule("recompile-guard",
            "one kernel compile per planned query bucket shape")
def check_recompilation(
    sizes: Sequence[int] = (1, 5, 8, 9, 17, 32),
    *,
    min_batch: int = 8,
    max_batch: int = 32,
    k: int = 4,
    _leak: Optional[Callable[[], None]] = None,
) -> List[Finding]:
    """One compile per planned bucket shape, none for raw batch sizes.

    The QueryPlanner pads every query batch to a power-of-two bucket in
    ``[min_batch, max_batch]`` precisely so the jitted kernels see a
    small closed set of shapes. This check queries a tiny engine at
    ragged sizes covering every bucket, then reads the kernels' own jit
    caches: ``build_sketch`` must hold exactly one entry per planned
    bucket, and the scoring entry points (``sketch_score`` +
    ``sketch_topk``) exactly one per bucket between them. ``_leak`` is a
    test seam: a callable run before counting that simulates a code path
    bypassing the planner.
    """
    import jax

    from ..core import BinSketchConfig, make_mapping
    from ..data.synthetic import DATASETS, generate_corpus
    from ..engine import QueryPlanner, SketchEngine
    from ..kernels import ops

    spec = DATASETS["tiny"]
    idx, lens = generate_corpus(spec, seed=0)
    # cache counting only needs a corpus big enough to cover the largest
    # query bucket — interpret-mode build over the full 256 docs would
    # triple this check's wall time for no extra signal
    n_docs = max(2 * max_batch, max(sizes))
    idx, lens = idx[:n_docs], lens[:n_docs]
    cfg = BinSketchConfig.from_sparsity(spec.d, int(lens.max()), 0.05)
    mapping = make_mapping(cfg, jax.random.PRNGKey(0))
    planner = QueryPlanner(min_batch=min_batch, max_batch=max_batch)
    engine = SketchEngine.build(
        cfg, mapping, corpus_idx=idx, backend="pallas-interpret",
        planner=planner,
    )

    # ingest polluted the caches with corpus-shaped entries; start clean
    for name in _JIT_FNS:
        getattr(ops, name)._clear_cache()

    for n in sizes:
        engine.query(idx[:n], k)
    if _leak is not None:
        _leak()

    planned = len(planner.shapes(sizes))
    findings: List[Finding] = []

    def cache(name: str) -> int:
        return getattr(ops, name)._cache_size()

    build_entries = cache("build_sketch")
    if build_entries != planned:
        findings.append(Finding(
            "recompile-guard", _OPS_REL, 0,
            f"build_sketch compiled {build_entries} variants for "
            f"{planned} planned bucket shapes over sizes {tuple(sizes)}",
            "every query batch must be padded through QueryPlanner.plan() "
            "before it reaches the kernels"))
    score_entries = cache("sketch_score") + cache("sketch_topk")
    if score_entries != planned:
        findings.append(Finding(
            "recompile-guard", _OPS_REL, 0,
            f"scoring kernels compiled {score_entries} variants for "
            f"{planned} planned bucket shapes over sizes {tuple(sizes)}",
            "score/topk must only ever see planner bucket shapes — check "
            "for a path slicing queries after padding"))
    return findings


# ==========================================================================
# host-sync detector
# ==========================================================================

_SYNC_PRIMITIVES = ("callback", "debug_print", "infeed", "outfeed",
                    "host_local_array")


def _scan_jaxpr(jaxpr, hits: List[str]) -> None:
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if any(tok in name for tok in _SYNC_PRIMITIVES):
            hits.append(name)
        for val in eqn.params.values():
            for sub in _subjaxprs(val):
                _scan_jaxpr(sub, hits)


def _subjaxprs(val):
    import jax.extend.core as jcore
    if isinstance(val, jcore.ClosedJaxpr):
        yield val.jaxpr
    elif isinstance(val, jcore.Jaxpr):
        yield val
    elif isinstance(val, (list, tuple)):
        for v in val:
            yield from _subjaxprs(v)


def default_query_entry_points() -> List[Tuple[str, Callable, tuple]]:
    """(name, fn, abstract args) for the hot query path: sketch the
    query batch, then score/top-k it against the corpus."""
    import jax
    import jax.numpy as jnp

    from ..kernels import ops

    q, c, w, p, n_bins = 32, 1024, 64, 48, 2048
    u32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.uint32)
    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32)
    return [
        ("build_sketch",
         functools.partial(ops.build_sketch, n_bins=n_bins, interpret=True),
         (i32((q, p)),)),
        ("sketch_score",
         functools.partial(ops.sketch_score, n_bins=n_bins, interpret=True),
         (u32((q, w)), u32((c, w)))),
        ("sketch_topk",
         functools.partial(ops.sketch_topk, n_bins=n_bins, k=8,
                           interpret=True),
         (u32((q, w)), u32((c, w)))),
    ]


@trace_rule("host-sync", "the hot query path never syncs with the host")
def check_host_sync(
    entry_points: Optional[Iterable[Tuple[str, Callable, tuple]]] = None,
) -> List[Finding]:
    """No callback/transfer primitives anywhere in the hot query jaxprs.

    A ``pure_callback`` / ``io_callback`` / debug print buried in the
    query path forces a device→host synchronization per dispatch —
    under load that is the whole latency budget. Tracing the actual
    entry points catches it regardless of which module introduced it.
    """
    import jax

    findings: List[Finding] = []
    for name, fn, args in (entry_points if entry_points is not None
                           else default_query_entry_points()):
        closed = jax.make_jaxpr(fn)(*args)
        hits: List[str] = []
        _scan_jaxpr(closed.jaxpr, hits)
        if hits:
            findings.append(Finding(
                "host-sync", _OPS_REL, 0,
                f"hot query entry point {name} traces to host-sync "
                f"primitives: {sorted(set(hits))}",
                "move the callback off the query path (maintenance thread "
                "or post-hoc telemetry); queries must stay device-only"))
    return findings


# ==========================================================================
# Pallas VMEM-budget checker
# ==========================================================================

@dataclasses.dataclass
class KernelCall:
    """One intercepted ``pl.pallas_call``: everything needed to price its
    VMEM block residency."""

    name: str
    module: str
    in_specs: list
    out_specs: object
    out_shape: object
    scratch_shapes: list
    arg_dtypes: list


@contextlib.contextmanager
def capture_pallas_calls(records: List[KernelCall]):
    """Intercept ``pl.pallas_call`` module-wide. Every kernel module does
    ``from jax.experimental import pallas as pl`` and resolves
    ``pl.pallas_call`` at call time, so patching the attribute on the
    shared module object sees every kernel launch; the real call still
    runs, so tracing semantics are unchanged."""
    from jax.experimental import pallas as pl

    real = pl.pallas_call

    def wrapper(kernel, *a, **kw):
        inner = real(kernel, *a, **kw)

        base = kernel
        while isinstance(base, functools.partial):
            base = base.func

        def call(*args, **kwargs):
            records.append(KernelCall(
                name=getattr(base, "__name__", str(base)),
                module=getattr(base, "__module__", "?"),
                in_specs=list(kw.get("in_specs") or ()),
                out_specs=kw.get("out_specs"),
                out_shape=kw.get("out_shape"),
                scratch_shapes=list(kw.get("scratch_shapes") or ()),
                arg_dtypes=[getattr(x, "dtype", None) for x in args],
            ))
            return inner(*args, **kwargs)

        return call

    pl.pallas_call = wrapper
    try:
        yield records
    finally:
        pl.pallas_call = real


def _block_bytes(spec, dtype) -> int:
    shape = getattr(spec, "block_shape", None)
    if shape is None or dtype is None:
        return 0
    n = 1
    for dim in shape:
        n *= 1 if dim is None else int(dim)
    return n * dtype.itemsize


def kernel_call_bytes(rec: KernelCall) -> int:
    """Block-residency bytes for one launch: every input block + every
    output block + every VMEM scratch buffer resident at once."""
    import numpy as np

    total = 0
    for spec, dt in zip(rec.in_specs, rec.arg_dtypes):
        total += _block_bytes(spec, np.dtype(dt) if dt is not None else None)
    out_specs = rec.out_specs if isinstance(rec.out_specs, (list, tuple)) \
        else [rec.out_specs]
    out_shapes = rec.out_shape if isinstance(rec.out_shape, (list, tuple)) \
        else [rec.out_shape]
    for spec, sds in zip(out_specs, out_shapes):
        dt = getattr(sds, "dtype", None)
        total += _block_bytes(spec, np.dtype(dt) if dt is not None else None)
    for scratch in rec.scratch_shapes:
        shape = getattr(scratch, "shape", None)
        dt = getattr(scratch, "dtype", None)
        if shape is not None and dt is not None:
            total += math.prod(int(s) for s in shape) * np.dtype(dt).itemsize
    return total


def trace_default_kernels(records: List[KernelCall]) -> None:
    """Trace every ops entry point at production-representative worst-case
    shapes (64k-bin sketches, 4k-doc corpus blocks) under the capture
    context. Uses the unjitted ``__wrapped__`` functions so the trace
    always runs — the jit jaxpr cache would otherwise swallow repeat
    traces and leave ``records`` silently empty."""
    import jax
    import jax.numpy as jnp

    from ..kernels import ops

    n_bins, w = 65536, 65536 // 32
    q, c, p, k = 1024, 4096, 64, 128
    u32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.uint32)
    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32)
    plans = [
        ("build_sketch", (i32((q, p)),), dict(n_bins=n_bins)),
        ("count_bins", (i32((q, p)),), dict(n_bins=n_bins)),
        ("hash_build_sketch", (i32((q, p)), u32((2,))), dict(n_bins=n_bins)),
        ("rebucket", (u32((q, w)),), dict(n_bins=n_bins, n_bins_new=n_bins // 4)),
        ("band_hash", (u32((q, w)),), dict(n_bands=16)),
        ("sketch_score", (u32((q, w)), u32((c, w))), dict(n_bins=n_bins)),
        ("sketch_topk", (u32((q, w)), u32((c, w))), dict(n_bins=n_bins, k=k)),
    ]
    for name, args, kw in plans:
        fn = getattr(ops, name)
        raw = getattr(fn, "__wrapped__", fn)
        jax.eval_shape(functools.partial(raw, **kw, interpret=True), *args)


@trace_rule("vmem-budget", "kernel block residency fits the VMEM budget")
def check_vmem_budget(
    limit_bytes: int = DEFAULT_VMEM_LIMIT,
    records: Optional[List[KernelCall]] = None,
) -> List[Finding]:
    """Every kernel's block residency fits the VMEM budget.

    Block shapes that fit at today's defaults can silently outgrow VMEM
    when someone bumps a ``block_*`` default or widens the sketch; on a
    real TPU that is a compile-time OOM in production, not a test
    failure. This prices the blocks from the BlockSpecs the kernels
    actually pass (plus scratch), so the DESIGN §7 budget table can
    never drift from the code. Pass ``records`` to price a synthetic
    capture (test seam); default traces all kernels.
    """
    if records is None:
        records = []
        with capture_pallas_calls(records):
            trace_default_kernels(records)
        if not records:
            return [Finding(
                "vmem-budget", _OPS_REL, 0,
                "VMEM checker traced all kernels but intercepted zero "
                "pallas_call launches — the capture hook is broken",
                "kernels must call pl.pallas_call via the pallas module "
                "attribute")]
    findings: List[Finding] = []
    for rec in records:
        used = kernel_call_bytes(rec)
        if used > limit_bytes:
            rel = "src/" + rec.module.replace(".", "/") + ".py" \
                if rec.module.startswith("repro.") else rec.module
            findings.append(Finding(
                "vmem-budget", rel, 0,
                f"kernel {rec.name}: {used} bytes block residency exceeds "
                f"the {limit_bytes}-byte VMEM budget",
                "shrink the BlockSpec tile (block_q/block_c/block_w) or "
                "split the scratch accumulator"))
    return findings


# ==========================================================================

def run_trace_checks(vmem_limit: int = DEFAULT_VMEM_LIMIT) -> List[Finding]:
    """All three trace-level analyzers, in CLI order."""
    out: List[Finding] = []
    out.extend(check_recompilation())
    out.extend(check_host_sync())
    out.extend(check_vmem_budget(vmem_limit))
    return out
