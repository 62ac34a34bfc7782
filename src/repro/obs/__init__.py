"""Fleet observability: device-side metrics rings, event tracing, live
contract monitors, and profiling hooks for the streaming runtime.

Quickstart::

    from repro.fleet.stream import FleetRuntime
    from repro.obs import ObsConfig

    rt = FleetRuntime(spec, obs=ObsConfig(divergence=True))
    for t in range(T):
        rt.step(demand[:, t])
    rt.obs_check()                       # raises ContractViolation on breach
    print(rt.obs_report().render_text())
    rt.obs.trace.save_chrome("trace.json")   # open in Perfetto

Design notes live in the submodules: :mod:`repro.obs.metrics` (the in-jit
ring and why drains ride the tick's own packed transfer),
:mod:`repro.obs.trace` (Chrome trace-event export), :mod:`repro.obs.monitors`
(the four contracts), :mod:`repro.obs.profile` (the program's spans and
counters, and the per-call profiler that reads them). Decisions are bit-identical with observability on or off —
the ring consumes tick outputs, it never feeds back.
"""
from .metrics import (
    DrainedMetrics,
    MetricsRing,
    default_hist_edges,
    flatten_ring,
    init_ring,
    init_tenant_ring,
    reset_ring,
    reset_ring_slot,
    ring_layout,
    ring_size,
    update_ring,
)
from .monitors import (
    BillingMonitor,
    CalibrationMonitor,
    ContractViolation,
    DivergenceMonitor,
    RegretMonitor,
    TenantSLOMonitor,
)
from .observer import FleetObserver, ObsConfig, ObsReport
from .profile import TickProfiler
from .trace import TraceRecorder, trace_from_plan

__all__ = [
    "BillingMonitor",
    "CalibrationMonitor",
    "ContractViolation",
    "DivergenceMonitor",
    "DrainedMetrics",
    "FleetObserver",
    "MetricsRing",
    "ObsConfig",
    "ObsReport",
    "RegretMonitor",
    "TenantSLOMonitor",
    "TickProfiler",
    "TraceRecorder",
    "default_hist_edges",
    "flatten_ring",
    "init_ring",
    "init_tenant_ring",
    "reset_ring",
    "reset_ring_slot",
    "ring_layout",
    "ring_size",
    "trace_from_plan",
    "update_ring",
]
