"""Compile the main path's device programs for a described TPU v5e chip.

Nothing runs: the TPU compiler, which is installed with JAX, compiles for a
chip that is described and not attached, and refuses what the chip would
refuse (unaligned blocks, scoped-VMEM overruns, programs too large for the
device). Shapes are the real ones: 2048 rows x a year of hours.

The topology is described inside a module fixture, never at import, so
every test worker collects the same tests and only the one running this
file loads the TPU library.
"""
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

N_ROWS, HOURS, TIERS, CHUNK = 2048, 8760, 4, 24


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it.
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def shape(one_chip):
    return lambda s, dt=jnp.float32: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)


@pytest.fixture(scope="module")
def fleet_operands(shape):
    """Shapes of a stacked 2048-link fleet, its demand and reactive policy."""
    from repro.core.pricing import make_scenario
    from repro.fleet.policy import make_policy
    from repro.fleet.spec import fleet_from_params

    with jax.enable_x64():
        arrays = fleet_from_params([make_scenario("gcp", "aws")] * N_ROWS).stack(
            jnp.float64
        )
        policy = make_policy("reactive", arrays.toggle)
    to_shape = lambda tree: jax.tree.map(lambda a: shape(a.shape, a.dtype), tree)
    return to_shape(arrays), shape((N_ROWS, HOURS), jnp.float64), to_shape(policy)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def test_tiered_cost_batched_compiles(shape):
    from repro.kernels.tiered_cost import tiered_cost_batched

    plane, table = shape((N_ROWS, HOURS)), shape((N_ROWS, TIERS))
    hlo = _compile(tiered_cost_batched, plane, plane, table, table).as_text()
    assert "tpu_custom_call" in hlo


def test_tiered_cost_compiles(shape):
    from repro.core.pricing import AWS_EGRESS_INTERNET as tier
    from repro.kernels.tiered_cost import tiered_cost

    plane = shape((HOURS, N_ROWS))
    fn = lambda c, d: tiered_cost(c, d, tier.bounds_gb, tier.rates)
    assert "tpu_custom_call" in _compile(fn, plane, plane).as_text()


def test_tiered_cost_scan_compiles(shape):
    from repro.kernels.tiered_cost import tiered_cost_scan

    table = shape((N_ROWS, TIERS))
    hlo = _compile(
        tiered_cost_scan, shape((N_ROWS,)), shape((N_ROWS, CHUNK)), table, table,
        shape((CHUNK,), jnp.int32),
    ).as_text()
    assert "tpu_custom_call" in hlo


def test_plan_fleet_program_compiles(fleet_operands):
    """The jitted plan_fleet program (float64 pricing + policy scan) fits
    one chip at 2048 links x 8760 h."""
    from repro.fleet.engine import _build_plan_fn

    with jax.enable_x64():
        compiled = _compile(_build_plan_fn(730, False), *fleet_operands)
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes
    assert used < 16e9, used
    assert "f64" in compiled.as_text()


def test_plan_fleet_pallas_stage_compiles(fleet_operands, monkeypatch):
    """``use_pallas=True`` puts the compiled kernel (not the interpreter)
    into the float64 plan program. The engine picks interpret mode from the
    default backend, which is the CPU here, so the test steers it."""
    from repro.fleet.engine import _build_plan_fn

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with jax.enable_x64():
        compiled = _compile(_build_plan_fn(730, True), *fleet_operands)
    assert "tpu_custom_call" in compiled.as_text()
