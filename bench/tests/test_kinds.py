"""Every cell's configuration leads to a kind module that holds what the
harness needs, whatever kinds ``BENCHMARK.json`` mixes; and the ``fleet``
kind gives, bit for bit, the scenario and reference the harness built before
kinds: ``bench.scenario.build`` and ``bench.reference.run`` over
``LinkArrays``. So no fleet cell's yardstick moved when the harness began to
reach them through the kind."""
from __future__ import annotations

import json
import os

import numpy as np
import pytest

from bench import control, reference, scenario, sut
from bench.harness import Registry
from bench.tests.conftest import REPO


@pytest.fixture(scope="module")
def fleet_kind():
    return Registry(REPO).kind_of({})


@pytest.fixture(scope="module")
def tiny_config(fleet_kind):
    with open(os.path.join(REPO, "bench", "configs", "fleet2048.json")) as f:
        cfg = json.load(f)
    cfg.update(fleet_kind.TINY)
    return cfg


KIND_NAMES = ("build", "reference", "SUT", "CONTROL", "TINY", "FAULTS")


def check_kinds(registry: Registry) -> None:
    """What holds of every cell, whatever the mix of kinds: a configuration
    that names no kind loads ``bench/kinds/fleet.py``, one that names a kind
    loads ``bench/kinds/<kind>.py``, the module holds every name the harness
    and the tests take of a kind, and each of its ``FAULTS`` has a planter."""
    for cell in registry.bench["workloads"]:
        name = registry.config(cell).get("kind", "fleet")
        kind = registry.kind(cell)
        assert kind.__file__ == os.path.join(registry.root, "bench", "kinds", name + ".py"), cell
        missing = [k for k in KIND_NAMES if not hasattr(kind, k)]
        assert not missing, f"kind {name!r} lacks {missing}"
        assert kind.FAULTS, f"kind {name!r} names no timed program to plant faults in"
        for family in kind.FAULTS:
            assert callable(registry.planter(family).plant), family


def test_a_config_without_kind_is_a_fleet(fleet_kind):
    check_kinds(Registry(REPO))
    assert fleet_kind.__file__ == os.path.join(REPO, "bench", "kinds", "fleet.py")
    assert (fleet_kind.SUT, fleet_kind.CONTROL) == (sut, control)


@pytest.mark.parametrize("seed", [2**31 + 11, 2**40 + 3])
def test_fleet_kind_builds_the_scenario(fleet_kind, tiny_config, seed):
    got, want = fleet_kind.build(tiny_config, seed), scenario.build(tiny_config, seed)
    assert got.links == want.links
    assert got.hours_per_month == want.hours_per_month
    np.testing.assert_array_equal(got.demand, want.demand)


@pytest.mark.parametrize("hours", [480, 301])
@pytest.mark.parametrize("seed", [2**31 + 11, 2**40 + 3])
def test_fleet_kind_reference_is_the_reference(fleet_kind, tiny_config, seed, hours):
    fleet = scenario.build(tiny_config, seed)
    got = fleet_kind.reference(fleet, hours)
    want = reference.run(scenario.LinkArrays(fleet.links, fleet.hours_per_month),
                         fleet.demand[:, :hours])
    assert set(got) == set(want)
    for plane in want:
        assert got[plane].dtype == want[plane].dtype, plane
        np.testing.assert_array_equal(got[plane], want[plane], err_msg=plane)
    assert got["x"].shape == (tiny_config["n_links"], hours)
