"""The program's spans and counters (:mod:`repro.obs.profile`) around the
streaming runtime's call and the offline planner's host part, on the CPU."""
import gc
import glob
import os
import time

import pytest

import jax
import jax.numpy as jnp

from repro.fleet.plan import build_fleet_scenario, plan_fleet
from repro.fleet.stream import FleetRuntime, streaming_forecast_policy
from repro.obs import profile

STEP_CHILDREN = (
    "fleet.step.pack", "fleet.step.dispatch", "fleet.step.wait",
    "fleet.step.fetch", "fleet.step.mirror",
)
N_LINKS, K = 6, 24


@pytest.fixture(scope="module")
def sc():
    return build_fleet_scenario(N_LINKS, horizon=120, history_hours=96, seed=1)


@pytest.fixture
def recording():
    profile.force_recording(True)
    yield
    profile.force_recording(None)


def _children(spans, parent, prefix):
    """The spans named ``prefix*`` inside ``parent``, in start order."""
    _, s0, e0 = parent
    return sorted(
        (s for s in spans if s[0].startswith(prefix) and s0 <= s[1] and s[2] <= e0),
        key=lambda s: s[1],
    )


def _assert_children_in_order(spans, parent_name, names):
    parents = [s for s in spans if s[0] == parent_name]
    assert parents
    for parent in parents:
        kids = _children(spans, parent, parent_name + ".")
        assert tuple(k[0] for k in kids) == names
        for a, b in zip(kids, kids[1:]):
            assert a[2] <= b[1], (a, b)        # one after the other


def test_step_children_follow_each_other_inside_the_call(sc, recording):
    rt = FleetRuntime(sc.fleet)
    t0 = time.perf_counter()
    rt.step_many(sc.demand[:, :K])
    rt.step(sc.demand[:, K])                   # step() is step_many on one hour
    spans, _ = profile.recorded(t0)
    assert sum(s[0] == "fleet.step" for s in spans) == 2
    _assert_children_in_order(spans, "fleet.step", STEP_CHILDREN)


def _live_runtime(sc):
    with jax.enable_x64():
        arrays = sc.fleet.stack(jnp.float64)
    pol, fc = streaming_forecast_policy(
        arrays, sc.history, steps=3, hours_per_month=sc.fleet.hours_per_month
    )
    return FleetRuntime(arrays, policy=pol, forecaster=fc,
                        hours_per_month=sc.fleet.hours_per_month)


@pytest.mark.parametrize("live, obs, planes",
                         [(False, False, 6), (True, False, 7), (False, True, 7)])
def test_step_counts_every_transfer(sc, recording, live, obs, planes):
    """Two packed buffers come home: the int8 ``x``/``state`` planes and one
    float64 vector of six (K, rows) planes (seven with a live forecaster's
    prediction or the observer's ``d_pair``) and four (rows,) accumulators;
    a drain call brings the metrics ring as a third. The H2D block is the
    demand and two window-read planes."""
    from repro.obs import ObsConfig

    if live:
        rt = _live_runtime(sc)
    else:
        rt = FleetRuntime(sc.fleet, obs=ObsConfig(cadence=K) if obs else None)
    drained = []
    if obs:
        record_drain = rt.obs.record_drain
        rt.obs.record_drain = lambda t, v: (drained.append(v), record_drain(t, v))
    t0 = time.perf_counter()
    rt.step_many(sc.demand[:, :K])
    _, counts = profile.recorded(t0)
    got = {name: n for name, _, n in counts}
    assert len(drained) == int(obs)
    assert got["fleet.step.d2h_arrays"] == 2 + len(drained)
    assert got["fleet.step.d2h_bytes"] == (
        2 * K * N_LINKS + 8 * N_LINKS * (planes * K + 4)
        + sum(v.nbytes for v in drained)
    )
    assert got["fleet.step.h2d_bytes"] == 8 * N_LINKS * 3 * K


def test_nothing_is_buffered_with_recording_off(sc):
    gc.collect()                               # observers of earlier runtimes
    assert not profile.recording()
    rt = FleetRuntime(sc.fleet)
    t0 = time.perf_counter()
    rt.step_many(sc.demand[:, :K])
    plan_fleet(sc.fleet, sc.demand)
    assert profile.recorded(t0) == ([], [])

    observed = FleetRuntime(sc.fleet, obs=True)
    assert profile.recording()                 # an attached observer
    del observed
    gc.collect()
    assert not profile.recording()


def test_outputs_are_bit_identical_with_recording_on_and_off(sc):
    outs = []
    for on in (True, False):
        profile.force_recording(on)
        try:
            rt = FleetRuntime(sc.fleet)
            outs.append([rt.step_many(sc.demand[:, t:t + 40])
                         for t in range(0, 120, 40)])
        finally:
            profile.force_recording(None)
    for a, b in zip(*outs):
        assert a.keys() == b.keys()
        for k in a:
            assert a[k].dtype == b[k].dtype
            assert a[k].tobytes() == b[k].tobytes(), k


def test_plan_children_lie_inside_the_plan(sc, recording):
    t0 = time.perf_counter()
    plan_fleet(sc.fleet, sc.demand)
    spans, _ = profile.recorded(t0)
    _assert_children_in_order(
        spans, "fleet.plan", ("fleet.plan.policy", "fleet.plan.dispatch")
    )


def test_spans_reach_the_profiler_trace(sc, tmp_path):
    """Under ``jax.profiler.trace`` every span is a host annotation in the
    trace, and the recorder keeps it too."""
    from jax.profiler import ProfileData

    rt = FleetRuntime(sc.fleet)
    rt.step_many(sc.demand[:, :K])             # compile outside the trace
    gc.collect()
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert profile.recording()
        t0 = time.perf_counter()
        rt.step_many(sc.demand[:, K:2 * K])
    finally:
        jax.profiler.stop_trace()
    assert not profile.recording()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    names = {e.name for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events}
    assert {"fleet.step", *STEP_CHILDREN} <= names
    spans, _ = profile.recorded(t0)
    assert {s[0] for s in spans} == {"fleet.step", *STEP_CHILDREN}
