"""repro.obs — the telemetry plane (DESIGN.md §14).

Observability for the sketch engine:

========================  ==================================================
module                    what it holds
========================  ==================================================
:mod:`repro.obs.clock`    one injectable time source (`Clock`, `ManualClock`)
                          shared by supervision, TTL, and metrics
:mod:`repro.obs.metrics`  `MetricsRegistry`: counters / gauges / log-bucketed
                          histograms, JSON snapshot
:mod:`repro.obs.trace`    ``repro.*`` profiler spans (`span`, `stage`);
                          sampled per-query `QueryTrace` (stage host time,
                          candidate fractions, widths, degraded hits)
:mod:`repro.obs.probe`    `RecallProbe`: online recall vs exact ground truth
                          on a supervised background job; `exact_topk`
========================  ==================================================

Arming follows `repro.faults`: a module-global registry/collector that
the engine's instrumentation checks with a single ``is None`` when
disarmed. `enable()` / `disable()` flip both at once::

    from repro import obs
    reg = obs.enable()            # arm metrics + tracing
    engine.query(q, k)
    print(engine.metrics())       # JSON-safe composite snapshot
    obs.disable()
"""

from __future__ import annotations

from typing import Callable, Optional

from . import metrics, trace
from .clock import MONOTONIC, Clock, ManualClock, SystemClock, ensure_clock
from .metrics import Histogram, MetricsRegistry
from .probe import RecallProbe, exact_topk
from .trace import STAGES, QueryTrace, TraceCollector

__all__ = [
    "Clock",
    "Histogram",
    "ManualClock",
    "MetricsRegistry",
    "MONOTONIC",
    "QueryTrace",
    "RecallProbe",
    "STAGES",
    "SystemClock",
    "TraceCollector",
    "disable",
    "enable",
    "ensure_clock",
    "exact_topk",
    "metrics",
    "trace",
]


def enable(clock: Optional[Callable[[], float]] = None, *,
           sample: int = 1, capacity: int = 64,
           alpha: float = 0.05) -> MetricsRegistry:
    """Arm the telemetry plane: install a fresh `MetricsRegistry` and a
    `TraceCollector` feeding it. Returns the registry (also reachable
    via ``metrics.active()``)."""
    reg = metrics.install(MetricsRegistry(clock=clock, alpha=alpha))
    trace.install(TraceCollector(sample=sample, capacity=capacity,
                                 clock=clock, registry=reg))
    return reg


def disable() -> None:
    """Disarm both metrics and tracing (instrumentation reverts to the
    one-None-check no-op path)."""
    metrics.clear()
    trace.clear()
