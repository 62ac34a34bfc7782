"""The benchmark's copies of the program's generator and reference agree with
their originals, so the yardstick measures what the program's own code
would have generated and decided."""
from __future__ import annotations

import math

import numpy as np
import pytest

from bench import reference, scenario, sut


def _original(family, pairs, hours, seed):
    from repro.traffic.mirage import mirage_trace
    from repro.traffic.puffer import puffer_trace
    from repro.traffic.traces import bursty_trace

    days = math.ceil(hours / 24)
    if family == "mirage":
        return mirage_trace(2000 * pairs, horizon_days=days, n_pairs=pairs, seed=seed)[:hours]
    if family == "puffer":
        return puffer_trace(horizon_days=days, n_channels=pairs, seed=seed)[:hours]
    rng = np.random.default_rng(seed)
    return np.concatenate([bursty_trace(horizon=hours, n_pairs=1, rng=rng)
                           for _ in range(pairs)], axis=1)


@pytest.mark.parametrize("family", ["mirage", "puffer", "bursty"])
@pytest.mark.parametrize("seed,pairs,hours", [(3, 4, 480), (2**31 + 5, 16, 1000), (11, 1, 49)])
def test_trace_family_equals_the_original(family, seed, pairs, hours):
    got = scenario.FAMILY_COLUMNS[family](pairs, hours, np.random.default_rng(seed))
    np.testing.assert_array_equal(got, _original(family, pairs, hours, seed))


@pytest.mark.parametrize("seed", [2**31 + 11, 2**40 + 3])
def test_reference_equals_the_programs_float64_reference(seed):
    from repro.fleet.engine import plan_fleet_reference

    fleet = scenario.build_fleet(seed, n_links=8, horizon=480, hours_per_month=120,
                                 families=("constant", "bursty", "mirage", "puffer"),
                                 demand_scale=1.0)
    got = reference.run(scenario.LinkArrays(fleet.links, fleet.hours_per_month),
                        fleet.demand)
    want = plan_fleet_reference(sut.fleet_spec(fleet.links, fleet.hours_per_month),
                                fleet.demand)
    np.testing.assert_array_equal(got["x"], want["x"])
    np.testing.assert_array_equal(got["state"], want["state"])
    np.testing.assert_allclose(got["toggle_cost"], want["toggle_cost"], rtol=1e-12)
    assert 0 < got["x"].mean() < 1, "no link toggled: the comparison saw one side only"


def test_fleet_is_fixed_by_its_seed():
    kw = dict(n_links=8, horizon=240, hours_per_month=120,
              families=("constant", "bursty", "mirage", "puffer"), demand_scale=1.0)
    a, b = scenario.build_fleet(2**33 + 1, **kw), scenario.build_fleet(2**33 + 1, **kw)
    c = scenario.build_fleet(2**33 + 2, **kw)
    np.testing.assert_array_equal(a.demand, b.demand)
    assert a.links == b.links
    assert not np.array_equal(a.demand, c.demand)
