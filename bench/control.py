"""The control: the benchmark's reference in float32, put in the program's place.

The configurations state float64. The control computes the same ToggleCCI
with every sum and product in float32, the precision a later change would
be tempted to drop to, and serves it through the same calls the drivers
make of ``bench.sut``. A sound comparison must find it not correct, and the
readings it gives are the upper ends the limits are set below (``PERF.md``).

    python3 bench/control.py --workload fleet2048.stream_k24 --seed 7 --seconds 20

runs one cell with its kind's control (``CONTROL`` of ``bench/kinds/<kind>.py``;
for a ``fleet``, this module) in the program's place and prints the numbers
compared beside their limits. It needs no accelerator. The
benchmark's own runs never run it; ``bench/tests/test_control.py`` runs it
at a small size.
"""
from __future__ import annotations

import os
import sys
from typing import Dict

import numpy as np

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import reference, scenario  # noqa: E402

DTYPE = np.float32
PLANES = ("x", "state", "r_vpn", "r_cci", "vpn_cost", "cci_cost", "cost")


def _year(links, hours_per_month: int, demand: np.ndarray) -> Dict[str, np.ndarray]:
    out = reference.run(scenario.LinkArrays(links, hours_per_month), demand, DTYPE)
    return {k: out[k].astype(np.float64) if out[k].dtype.kind == "f" else out[k]
            for k in out}


class _Spec:
    def __init__(self, links, hours_per_month):
        self.links, self.hours_per_month = tuple(links), int(hours_per_month)


def fleet_spec(links, hours_per_month: int) -> _Spec:
    return _Spec(links, hours_per_month)


class Runtime:
    """``FleetRuntime``'s calls over a float32 year computed up front from
    the demand the stream sends (hour ``t`` of a call is hour ``t`` of the
    same demand year)."""

    def __init__(self, fleet):
        self.year = _year(fleet.links, fleet.hours_per_month, fleet.demand)
        self.t = 0

    def reset(self) -> None:
        self.t = 0

    def step_many(self, block) -> Dict[str, np.ndarray]:
        k = np.asarray(block).shape[1]
        out = {p: self.year[p][:, self.t:self.t + k].copy() for p in PLANES}
        self.t += k
        return out

    def step(self, column) -> Dict[str, np.ndarray]:
        return {p: v[:, 0] for p, v in self.step_many(np.asarray(column)[:, None]).items()}


def fleet_runtime(fleet) -> Runtime:
    return Runtime(fleet)


def planner(fleet):
    year = _year(fleet.links, fleet.hours_per_month, fleet.demand)
    return lambda: {k: year[k] for k in ("x", "state", "toggle_cost")}


def main(argv=None) -> int:
    import argparse
    import time

    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    from bench.harness import Registry, report_checks, run_cell

    registry = Registry(root)
    program = registry.kind(registry.cell(args.workload)).CONTROL
    result = run_cell(registry, args.workload, args.seed, args.seconds, False,
                      t_start=t_start, program=program,
                      require_accelerator=False, compile_cache=False)
    report_checks(result, out=sys.stdout)
    return 0


if __name__ == "__main__":
    sys.exit(main())
