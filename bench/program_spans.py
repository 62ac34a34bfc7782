"""Per-call means of the program's own spans and counters over a run's window.

While a profiler trace is active the program records its ``fleet.*`` host
spans and counters (``repro.obs.profile``) on the clock the harness stamps
``run.calls`` with. Each reader here takes those that lie in the window, the
first call's start to the last call's end, and divides their total by the
number of calls. It returns ``None`` where the run recorded nothing of that
name: an untraced run, a program without the recorder, or the control
(``bench/control.py``), which is not the program.
"""
from __future__ import annotations

from typing import Optional


def _recorded(run):
    try:
        from repro.obs.profile import recorded
    except ImportError:
        return [], []
    if not run.calls:
        return [], []
    return recorded(run.calls[0][0], run.calls[-1][1])


def span_ms_per_call(run, name: str) -> Optional[float]:
    """Milliseconds inside spans named ``name``, per call of the window."""
    spans, _ = _recorded(run)
    got = [e - s for n, s, e in spans if n == name]
    return sum(got) / len(run.calls) * 1e3 if got else None


def count_per_call(run, name: str) -> Optional[float]:
    """The total of the counts named ``name``, per call of the window."""
    _, counts = _recorded(run)
    got = [c for n, _, c in counts if n == name]
    return sum(got) / len(run.calls) if got else None
