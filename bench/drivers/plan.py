"""Plan a fleet's whole year with ``plan_fleet``, once per call.

The what-if planning path: every call plans the same year from operands
already on the device and brings the decisions (``x``, ``state``) and each
link's ToggleCCI cost home. Set-up compiles and runs the plan twice.
Every call's costs are compared; a seeded sample of calls has its year of
decisions compared too.
"""
from __future__ import annotations

from bench.checks import Check, Compare, Record, rel_err

# Limits, each set from readings given in PERF.md.
LIMITS = {"decisions_wrong": 0, "toggle_cost_err": 1e-9}
KEEP = 2


class Driver:
    def __init__(self, fleet, config, traffic, seed, span, program):
        self.fleet, self.span, self.program = fleet, span, program
        self.rows, self.T = fleet.demand.shape
        self.record = Record(seed, KEEP, planes=(), every_call=False)
        self.costs = []

    def setup(self) -> None:
        self.plan = self.program.planner(self.fleet)
        for _ in range(2):
            self.plan()

    def call(self) -> int:
        with self.span("bench.plan"):
            self.last = self.plan()
        return self.rows * self.T

    def keep(self) -> None:
        self.costs.append(self.last["toggle_cost"])
        self.record.add(0, self.last)

    def finish(self) -> None:
        del self.plan

    def hours_needed(self) -> int:
        return self.T

    def checks(self, ref) -> list:
        cmp = Compare(ref)
        errs = [rel_err(c, ref["toggle_cost"]) for c in self.costs]
        cmp.bad_calls |= {i for i, e in enumerate(errs) if not e <= LIMITS["toggle_cost_err"]}
        return [
            Check("decisions_wrong", cmp.decisions_wrong(self.record),
                  LIMITS["decisions_wrong"]),
            Check("toggle_cost_err", max(errs), LIMITS["toggle_cost_err"]),
        ], cmp.bad_calls
