"""The profiler trace of a run's window, reduced to what the metrics read.

The harness marks each call of the window, and the resets inside it, with ``jax.profiler.TraceAnnotation`` spans named ``bench.*``; they land
in the trace on the same clock as the device's operations. ``load`` reads
the ``.xplane.pb`` the profiler wrote into two event lists, and ``reduce``
turns them into:

* ``busy_s``: the union of the intervals in which an operation or a program
  ran on the device, inside the window, averaged over the devices used (a
  program's interval covers its operations, so that busy time stays whole
  where the profiler kept fewer operation events than ran);
* per call: the device-busy seconds inside the call's span, so that the
  host time the call exposed is its span less that;
* the ``breakdown`` of the result line: the device operations that took most
  time, and the longest idle gaps, each cut where a host span begins or ends
  and named after the innermost ``bench.*`` span the host was in.

Everything after ``load`` is plain Python over ``(name, start_ns, end_ns)``
tuples, so it is tested on small event lists without a chip.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]          # (name, start_ns, end_ns)

SPAN_PREFIX = "bench."
CALL_SPAN = "bench.call"
# Lines of a device plane: one event per operation, and one per program run.
OPS_LINE, PROGRAMS_LINE = "XLA Ops", "XLA Modules"


@dataclasses.dataclass
class TraceEvents:
    device: Dict[str, List[Event]]        # device plane name -> its operations
    spans: List[Event]                    # the harness's bench.* host spans
    lines: Dict[str, int] = dataclasses.field(default_factory=dict)  # events per device line
    programs: Dict[str, List[Event]] = dataclasses.field(default_factory=dict)  # program runs


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float                          # mean over devices
    call_s: List[float]                    # each call span's length
    call_busy_s: List[float]               # device busy inside each call span
    device_ops: List[list]                 # [[name, seconds]], most time first
    idle_gaps: List[list]                  # [[span, seconds]], longest first


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge intervals into disjoint, sorted ones."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


class Coverage:
    """Length of any ``[lo, hi]`` covered by disjoint sorted intervals, in
    logarithmic time (a window holds up to some hundred thousand)."""

    def __init__(self, merged: List[Tuple[float, float]]):
        self.starts = [s for s, _ in merged]
        self.ends = [e for _, e in merged]
        self.cum = [0.0]
        for s, e in merged:
            self.cum.append(self.cum[-1] + (e - s))

    def upto(self, t: float) -> float:
        """Covered length of ``(-inf, t]``."""
        i = bisect.bisect_right(self.starts, t)        # intervals starting <= t
        if i == 0:
            return 0.0
        return self.cum[i - 1] + min(t, self.ends[i - 1]) - self.starts[i - 1]

    def __call__(self, lo: float, hi: float) -> float:
        return max(0.0, self.upto(hi) - self.upto(lo))


def innermost(spans: Sequence[Event], t: float) -> str:
    """Name of the shortest span that contains ``t`` (of two alike, the one
    inside ``bench.call``), or ``outside``."""
    inside = [(e - s, name == CALL_SPAN, name) for name, s, e in spans if s <= t <= e]
    return min(inside)[2] if inside else "outside"


def idle_pieces(merged: List[Tuple[float, float]], w0: float, w1: float,
                cuts: List[float]) -> List[Tuple[float, float]]:
    """The device's idle intervals inside ``[w0, w1]``, cut where a host span
    starts or ends (``cuts``, sorted), so that each piece lies in one span."""
    edges = [w0] + [x for iv in merged for x in iv] + [w1]
    out = []
    for a, b in zip(edges[::2], edges[1::2]):
        a, b = max(a, w0), min(b, w1)
        if b <= a:
            continue
        inner = cuts[bisect.bisect_right(cuts, a):bisect.bisect_left(cuts, b)]
        pts = [a] + inner + [b]
        out += [(x, y) for x, y in zip(pts, pts[1:]) if y > x]
    return out


def reduce(ev: TraceEvents, top: int = 10) -> Optional[TraceSummary]:
    """The window is the first call's start to the last call's end. Returns
    ``None`` where the trace holds no call or no device operation."""
    calls = sorted((s, e) for name, s, e in ev.spans if name == CALL_SPAN)
    ops = {d: [o for o in v if o[2] > o[1]] for d, v in ev.device.items()}
    ops = {d: v for d, v in ops.items() if v}
    if not calls or not ops:
        return None
    w0, w1 = calls[0][0], calls[-1][1]
    merged = {d: union([(s, e) for _, s, e in v + ev.programs.get(d, [])])
              for d, v in ops.items()}
    cover = [Coverage(m) for m in merged.values()]
    n = len(cover)
    busy = sum(c(w0, w1) for c in cover) / n
    call_busy = [sum(c(s, e) for c in cover) / n for s, e in calls]
    per_op: Dict[str, float] = {}
    for v in ops.values():
        for name, s, e in v:
            if e > w0 and s < w1:
                per_op[name] = per_op.get(name, 0.0) + (min(e, w1) - max(s, w0))
    # Idle gaps of the first device (one chip per cell today), inside the window.
    cuts = sorted({x for _, s, e in ev.spans for x in (s, e)})
    pieces = idle_pieces(next(iter(merged.values())), w0, w1, cuts)
    gaps = sorted(((b - a, a, b) for a, b in pieces), reverse=True)[:top]
    return TraceSummary(
        window_s=(w1 - w0) * 1e-9,
        busy_s=busy * 1e-9,
        call_s=[(e - s) * 1e-9 for s, e in calls],
        call_busy_s=[b * 1e-9 for b in call_busy],
        device_ops=[[k, v / n * 1e-9] for k, v in
                    sorted(per_op.items(), key=lambda kv: -kv[1])[:top]],
        idle_gaps=[[innermost(ev.spans, 0.5 * (a + b)), g * 1e-9] for g, a, b in gaps],
    )


def xplane_path(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}, found {paths}")
    return paths[0]


def load(log_dir: str) -> TraceEvents:
    """Device operations and ``bench.*`` spans from the profiler's output."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(xplane_path(log_dir))
    device: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    lines: Dict[str, int] = {}
    programs: Dict[str, List[Event]] = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            got = {l.name: [(e.name, e.start_ns, e.start_ns + e.duration_ns) for e in l.events]
                   for l in plane.lines if l.name in (OPS_LINE, PROGRAMS_LINE)}
            lines.update({f"{plane.name}:{n}": len(v) for n, v in got.items()})
            programs[plane.name] = got.get(PROGRAMS_LINE, [])
            device[plane.name] = got.get(OPS_LINE, programs[plane.name])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                          for e in line.events if e.name.startswith(SPAN_PREFIX)]
    return TraceEvents(device, spans, lines, programs)


def describe(log_dir: str, per_line: int = 3) -> List[str]:
    """A short listing of the trace's planes and lines, for a reader."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(xplane_path(log_dir))
    out = []
    for plane in pd.planes:
        out.append(f"plane {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            out.append(f"  line {line.name!r}: {len(evs)} events")
            out += [f"    {e.name} start_ns={e.start_ns} dur_ns={e.duration_ns}"
                    for e in evs[:per_line]]
    return out
