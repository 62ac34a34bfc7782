"""The system under test, reached only through its public entry points.

The drivers build the program's objects here from the benchmark's own
scenario (``bench.scenario``), so that swapping this module for another
with the same functions puts something else in the program's place: the
lower-precision control (``bench.control``) or a broken program in the
tests.
"""
from __future__ import annotations

from typing import Dict, Sequence

import numpy as np


def fleet_spec(links: Sequence, hours_per_month: int):
    """The program's ``FleetSpec`` of the benchmark's links."""
    from repro.core.pricing import CostParams, TieredRate
    from repro.fleet.plan import FleetSpec, LinkSpec

    return FleetSpec(tuple(
        LinkSpec(
            name=l.name,
            params=CostParams(
                L_cci=l.L_cci, V_cci=l.V_cci, c_cci=l.c_cci, L_vpn=l.L_vpn,
                vpn_tier=TieredRate(l.tier_bounds, l.tier_rates),
                D=l.D, T_cci=l.T_cci, h=l.h, theta1=l.theta1, theta2=l.theta2,
                hours_per_month=hours_per_month,
            ),
            capacity_gb_hr=l.capacity,
            family=l.family,
        )
        for l in links
    ))


def fleet_runtime(fleet):
    """A ``FleetRuntime`` over the whole fleet, observability off."""
    from repro.fleet.stream import FleetRuntime

    return FleetRuntime(fleet_spec(fleet.links, fleet.hours_per_month))


class Planner:
    """``plan_fleet`` over a year whose operands already sit on the device;
    ``__call__`` returns the plan's decisions and costs in host memory."""

    def __init__(self, fleet):
        import jax
        import jax.numpy as jnp

        self.hours_per_month = fleet.hours_per_month
        with jax.enable_x64():
            spec = fleet_spec(fleet.links, fleet.hours_per_month)
            self.arrays = spec.stack(jnp.float64)
            self.demand = jax.device_put(np.asarray(fleet.demand, np.float64))

    def __call__(self) -> Dict[str, np.ndarray]:
        from repro.fleet.plan import plan_fleet

        out = plan_fleet(self.arrays, self.demand,
                         hours_per_month=self.hours_per_month)
        return {k: np.asarray(out[k]) for k in ("x", "state", "toggle_cost")}


def planner(fleet):
    return Planner(fleet)

