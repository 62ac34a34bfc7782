"""What a window produced, and its comparison with the reference.

A driver files every call's decisions (``x`` and ``state`` for each row and
hour it decided) and, for a sample of calls drawn from the seed, the float64
cost planes too. After the window, ``Compare`` looks every one of them up in
the reference's outputs for the same rows and hours and reduces the gaps to
the numbers that decide ``correct``:

* ``decisions_wrong``: (row, hour) cells whose ``x`` or ``state`` differs;
* ``plane_err``: the widest gap of a cost plane (window sums ``r_vpn`` and
  ``r_cci``, hourly ``vpn_cost``, ``cci_cost`` and the served ``cost``), as
  a share of that row's largest reference value of the plane.

Each number is held to its own limit; ``PERF.md`` gives the readings each
limit was set from.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Sequence

import numpy as np

PLANES = ("r_vpn", "r_cci", "vpn_cost", "cci_cost", "cost")


@dataclasses.dataclass
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(self.value <= self.limit)


class Record:
    """Decisions of every call, and the planes of a seeded sample of calls.

    ``start`` is the hour of the demand year at which the call's block
    begins. The sample is a reservoir of ``keep`` calls, uniform over all
    the calls of the window. With ``every_call=False`` decisions too are
    kept for the sample only (a year-long plan per call is too large to keep
    them all).
    """

    def __init__(self, seed: int, keep: int, planes: Sequence[str] = PLANES,
                 every_call: bool = True):
        self.rng = np.random.default_rng([int(seed) % 2**64, 7])
        self.keep = keep
        self.planes = tuple(planes)
        self.every_call = every_call
        self.n = 0
        self.calls: List[tuple] = []            # (call index, start, x, state)
        self.sample: List[tuple] = []           # (call index, start, planes)

    def add(self, start, out: Dict[str, np.ndarray]) -> None:
        i, self.n = self.n, self.n + 1
        if self.every_call:
            self.calls.append((i, start, out["x"].astype(np.int8),
                               out["state"].astype(np.int8)))
        j = i if i < self.keep else int(self.rng.integers(i + 1))
        if j < self.keep:
            entry = (i, start, {k: out[k] for k in self.planes})
            if not self.every_call:
                entry[2].update(x=out["x"], state=out["state"])
            if i < self.keep:
                self.sample.append(entry)
            else:
                self.sample[j] = entry

    def decisions(self):
        if self.every_call:
            return self.calls
        return [(i, s, p["x"], p["state"]) for i, s, p in self.sample]

    def hours_needed(self) -> int:
        return max(s + x.shape[1] for _, s, x, _ in self.decisions())


class Compare:
    """The reference's outputs, and per-row scales of its cost planes."""

    def __init__(self, ref: Dict[str, np.ndarray]):
        self.ref = ref
        self.scale = {k: np.maximum(np.abs(ref[k]).max(axis=1, keepdims=True), 1e-300)
                      for k in PLANES}
        self.bad_calls = set()

    def decisions_wrong(self, rec: Record) -> int:
        wrong = 0
        for i, start, x, state in rec.decisions():
            k = x.shape[1]
            n = (int(np.count_nonzero(x != self.ref["x"][:, start:start + k]))
                 + int(np.count_nonzero(state != self.ref["state"][:, start:start + k])))
            if n:
                self.bad_calls.add(i)
            wrong += n
        return wrong

    def plane_err(self, rec: Record, limit: float) -> float:
        worst = 0.0
        for i, start, planes in rec.sample:
            for k in rec.planes:
                got = planes[k]
                want = self.ref[k][:, start:start + got.shape[1]]
                err = float(np.max(np.abs(got - want) / self.scale[k]))
                if not err <= limit:          # NaN counts as wrong
                    self.bad_calls.add(i)
                worst = max(worst, err) if err == err else float("inf")
        return worst


def rel_err(got, want) -> float:
    """Widest ``|got - want| / |want|``; infinite where any is NaN."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    err = np.abs(got - want) / np.maximum(np.abs(want), 1e-300)
    return float("inf") if np.isnan(err).any() else float(err.max(initial=0.0))

