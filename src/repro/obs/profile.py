"""Spans and counters of the program's host path, and the tick profiler.

The one span/counter facility of the program. :class:`span` marks a piece
of host work; :func:`count` notes a number (bytes moved, arrays fetched).
Program spans are named ``fleet.*``:

* ``fleet.step`` — one :meth:`repro.fleet.runtime.FleetRuntime.step_many`
  call, split into five children that follow each other and cover it:
  ``fleet.step.pack`` (ring gathers and the flat host-to-device block),
  ``fleet.step.dispatch`` (host-to-device copy and the jitted call's
  enqueue), ``fleet.step.wait`` (the host blocked on the device program),
  ``fleet.step.fetch`` (every device-to-host copy of the call) and
  ``fleet.step.mirror`` (the host float64 mirror and the output dict);
  counters ``fleet.step.d2h_arrays``, ``fleet.step.h2d_bytes`` and
  ``fleet.step.d2h_bytes`` once per call;
* ``fleet.plan`` — the host part of :func:`repro.fleet.engine.plan_fleet`
  (spec stacking, policy resolution, the jitted call's enqueue), with
  children ``fleet.plan.policy`` and ``fleet.plan.dispatch``. The caller's
  fetch of the plan's outputs lies outside it.

Every span is a ``jax.profiler.TraceAnnotation`` while a profiler trace is
active, so it shows in Perfetto or TensorBoard on the same clock as the
device's operations (under ``jax.profiler.trace(dir)``). While *recording*
is on — a profiler trace is active, or a
:class:`repro.obs.observer.FleetObserver` is attached — each span also
appends ``(name, start, end)`` in ``time.perf_counter`` seconds, and each
count ``(name, t, n)``, to a bounded process-wide buffer that
:func:`recorded` reads back for a host-clock window. With recording off a
span costs one check and stores nothing.

:class:`TickProfiler` is the observer's reader of that buffer: the
``fleet.step`` span and byte counters of each call, for
:meth:`repro.fleet.runtime.FleetRuntime.obs_report`.
"""
from __future__ import annotations

import collections
import time
import weakref
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from jax._src.lib import _profiler
from jax.profiler import TraceAnnotation

Span = Tuple[str, float, float]        # (name, start_s, end_s)
Count = Tuple[str, float, int]         # (name, t_s, n)

CAPACITY = 1 << 20                     # newest events kept, spans and counts each
_SPANS: collections.deque = collections.deque(maxlen=CAPACITY)
_COUNTS: collections.deque = collections.deque(maxlen=CAPACITY)
_OBSERVERS: "weakref.WeakSet" = weakref.WeakSet()
_FORCED: Optional[bool] = None
_trace_active = _profiler.TraceMe.is_enabled


def attach(observer) -> None:
    """Keep recording on while ``observer`` lives."""
    _OBSERVERS.add(observer)


def force_recording(on: Optional[bool]) -> None:
    """Override when recording is on (``None``: back to the rule above).
    Profiler annotations follow the trace either way; this only decides
    whether the buffer is written, e.g. to measure what recording costs."""
    global _FORCED
    _FORCED = on


def recording() -> bool:
    """Whether spans and counts are being kept now."""
    if _FORCED is not None:
        return _FORCED
    return bool(_OBSERVERS) or _trace_active()


class span:
    """``with span("fleet.step.fetch"): ...`` — see the module docstring."""

    __slots__ = ("name", "_ann", "_t0")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self) -> "span":
        traced = _trace_active()
        self._ann = TraceAnnotation(self.name) if traced else None
        if self._ann is not None:
            self._ann.__enter__()
        on = _FORCED if _FORCED is not None else (traced or bool(_OBSERVERS))
        self._t0 = time.perf_counter() if on else None
        return self

    def __exit__(self, *exc) -> bool:
        if self._t0 is not None:
            _SPANS.append((self.name, self._t0, time.perf_counter()))
        if self._ann is not None:
            self._ann.__exit__(*exc)
        return False


def count(name: str, n) -> None:
    """Note ``n`` under ``name`` (while recording is on)."""
    if recording():
        _COUNTS.append((name, time.perf_counter(), n))


def recorded(
    since: float = float("-inf"), until: float = float("inf")
) -> Tuple[List[Span], List[Count]]:
    """The buffered spans lying wholly inside ``[since, until]`` and the
    counts noted in it, oldest first, on the ``time.perf_counter`` clock."""
    spans = [s for s in _SPANS if since <= s[1] and s[2] <= until]
    counts = [c for c in _COUNTS if since <= c[1] <= until]
    return spans, counts


def _newest(buf: collections.deque, name: str):
    for ev in reversed(buf):
        if ev[0] == name:
            return ev
    return None


class TickProfiler:
    """Per-call latency and transfer bytes of one observed runtime, read
    from the recorder. The runtime calls :meth:`record_call` right after
    its ``fleet.step`` span closes, so that span, and the byte counters
    noted inside it, are the newest of their names in the buffer."""

    def __init__(self):
        self.calls: List[Span] = []
        self.h2d_bytes = 0
        self.d2h_bytes = 0
        self.drains = 0

    def record_call(self) -> None:
        s = _newest(_SPANS, "fleet.step")
        if s is None or (self.calls and self.calls[-1] is s):
            return                       # recording forced off
        self.calls.append(s)
        for name, attr in (("fleet.step.h2d_bytes", "h2d_bytes"),
                           ("fleet.step.d2h_bytes", "d2h_bytes")):
            c = _newest(_COUNTS, name)
            if c is not None and s[1] <= c[1] <= s[2]:
                setattr(self, attr, getattr(self, attr) + int(c[2]))

    def note_drain(self) -> None:
        self.drains += 1

    @property
    def n_calls(self) -> int:
        return len(self.calls)

    def percentiles(self, qs: Sequence[float] = (50, 95, 99)) -> Dict[str, float]:
        """Per-call latency percentiles in MICROSECONDS (µs)."""
        if not self.calls:
            return {f"p{int(q)}": float("nan") for q in qs}
        arr = np.asarray([e - s for _, s, e in self.calls]) * 1e6
        return {f"p{int(q)}": float(np.percentile(arr, q)) for q in qs}

    def summary(self) -> dict:
        pct = self.percentiles()
        return {
            "calls": self.n_calls,
            "call_us_p50": pct["p50"],
            "call_us_p95": pct["p95"],
            "call_us_p99": pct["p99"],
            "h2d_bytes": self.h2d_bytes,
            "d2h_bytes": self.d2h_bytes,
            "drains": self.drains,
        }
