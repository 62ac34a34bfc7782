"""Stream a fleet's year through ``FleetRuntime``, one block of hours per call.

The caller is one actuation loop: it sends hour block ``t`` only after the
decisions of the block before it have come back, since they carry the FSM
and the windows forward. The traffic mix sets the hours per call
(``hours_per_call``) and whether a one-hour call goes through ``step``
(``"entry": "step"``) or ``step_many``. When the stream reaches the end of
the demand year the runtime is ``reset()`` and the year streams again, so a
faster program never runs out of hours; the reset's time is part of the
call that reached the end.

Set-up compiles and runs every block length the window will use (the last
block of the year is shorter where the year is not a whole number of
blocks), streams the first ``warm_hours`` and resets, so the window starts
at hour 0 with every program it calls, the reset's among them, compiled.
"""
from __future__ import annotations

import numpy as np

from bench.checks import Check, Compare, Record

# Limits, each set from readings given in PERF.md.
LIMITS = {"decisions_wrong": 0, "plane_err": 1e-9}
KEEP = 48          # calls whose cost planes are compared


class Driver:
    def __init__(self, fleet, config, traffic, seed, span, program):
        self.fleet, self.traffic, self.span, self.program = fleet, traffic, span, program
        self.K = int(traffic["hours_per_call"])
        self.per_hour = traffic.get("entry") == "step"
        if self.per_hour and self.K != 1:
            raise ValueError("step() decides one hour per call")
        self.demand = fleet.demand
        self.rows, self.T = self.demand.shape
        self.record = Record(seed, KEEP)

    def setup(self) -> None:
        self.rt = self.program.fleet_runtime(self.fleet)
        tail = self.T % self.K
        if tail:
            self.rt.step_many(self.demand[:, :tail])
            self.rt.reset()
        t = 0
        while t < int(self.traffic["warm_hours"]):
            self._advance(t)
            t += self.K
        self.rt.reset()
        self.t = 0

    def _advance(self, t: int):
        if self.per_hour:
            out = self.rt.step(self.demand[:, t])
            return {k: v[:, None] for k, v in out.items()}
        return self.rt.step_many(self.demand[:, t:t + self.K])

    def call(self) -> int:
        t = self.t
        with self.span("bench.step"):
            out = self._advance(t)
        k = out["x"].shape[1]
        self.last = (t, out)
        self.t = t + k
        if self.t >= self.T:
            with self.span("bench.reset"):
                self.rt.reset()
            self.t = 0
        return self.rows * k

    def keep(self) -> None:
        self.record.add(*self.last)

    def finish(self) -> None:
        del self.rt

    def checks(self, ref) -> list:
        cmp = Compare(ref)
        return [
            Check("decisions_wrong", cmp.decisions_wrong(self.record),
                  LIMITS["decisions_wrong"]),
            Check("plane_err", cmp.plane_err(self.record, LIMITS["plane_err"]),
                  LIMITS["plane_err"]),
        ], cmp.bad_calls

    def hours_needed(self) -> int:
        return self.record.hours_needed()
