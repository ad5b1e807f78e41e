"""repro.engine — streaming, shard-aware sketch serving (DESIGN.md §6).

| piece | file | role |
|---|---|---|
| SketchStore | store.py | packed corpus, incremental OR-ingest, fill cache |
| SegmentedStore | segments.py | mutable lifecycle: counting head, sealed segments, tombstones, (background) compaction, TTL, distillation |
| DistillPolicy | segments.py | which sealed segments drop to which smaller sketch width, and when |
| SegmentPlacer | placement.py | segment-as-shard device placement (per-width resident slabs) for the sharded query path |
| BandPolicy / BandIndex | banding.py | banded LSH prefilter: per-segment bucket index over packed sketch words |
| Backend registry | backends.py | oracle / pallas / pallas-interpret behind one name |
| QueryPlanner | planner.py | ragged batches -> bounded set of jit shapes |
| JobSupervisor | supervision.py | retries / watchdog / quarantine / health() for background jobs; maintenance errors never reach queries |
| LifecycleController | lifecycle.py | autonomous maintenance: size-tiered merges, distill ladder, recall guardrail — telemetry in, supervised jobs out |
| SketchEngine | engine.py | build + query + sharded query (mixed-width) on the pieces above |

The telemetry plane — metrics registry, sampled query traces, the online
recall probe, and the shared injectable clock — lives in the sibling
package ``repro.obs`` (DESIGN.md §14); the engine threads it through every
query path and exposes one snapshot via ``SketchEngine.metrics()``.

``core.index.SketchIndex`` is the deprecated batch-era front-end, kept as a
thin shim over this package.
"""

from .banding import BandIndex, BandPolicy
from .backends import (
    Backend,
    available_backends,
    from_legacy_scorer,
    get_backend,
    register_backend,
)
from .engine import SketchEngine, merge_segment_topk, shard_topk
from .lifecycle import ControllerPolicy, LifecycleController
from .placement import SegmentPlacement, SegmentPlacer, WidthSlab
from .planner import QueryChunk, QueryPlanner
from .segments import DistillPolicy, SealedSegment, SegmentedStore
from .store import SegmentView, SketchStore
from .supervision import (
    DegradedMode,
    JobSupervisor,
    SupervisedJob,
    SupervisionPolicy,
    health_faults,
)

__all__ = [
    "Backend",
    "BandIndex",
    "BandPolicy",
    "ControllerPolicy",
    "DegradedMode",
    "DistillPolicy",
    "JobSupervisor",
    "LifecycleController",
    "QueryChunk",
    "QueryPlanner",
    "SealedSegment",
    "SegmentPlacement",
    "SegmentPlacer",
    "SegmentView",
    "SegmentedStore",
    "SketchEngine",
    "SketchStore",
    "SupervisedJob",
    "SupervisionPolicy",
    "WidthSlab",
    "available_backends",
    "from_legacy_scorer",
    "get_backend",
    "health_faults",
    "merge_segment_topk",
    "register_backend",
    "shard_topk",
]
