"""Streaming fleet runtime tests (the tentpole's bit-exactness contract).

The load-bearing property: N incremental ``FleetRuntime.step`` calls
reproduce one offline ``policy_scan`` DECISION-BIT-EXACTLY for all three
toggle policies. The airtight form pins the per-hour mode-cost series to the
runtime's own emitted columns (the same pinning contract
``plan_topology_reference`` documents): the runtime's carried prefix-ring
window state must then replicate ``policy_scan``'s float64 ``np.cumsum``
windows and FSM transitions exactly, over random windows/delays/thresholds
and regime-switching demand. Sampled-scenario tests additionally check the
streaming pricing stage against the jitted ``plan_fleet``/``plan_topology``
engines end-to-end (both policies' decisions and the cost series), plus the
live-SSM forecast mode, the endogenous-demand planner, and the collective
actuation path (int8 vs hierarchical selected by link modes).
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

import jax
import jax.numpy as jnp

from repro.core.pricing import CostParams, TieredRate
from repro.fleet.plan import (
    build_fleet_scenario,
    build_topology_scenario,
    forecast_fleet_policy,
    forecast_gated_policy,
    forecast_topology_policy,
    hysteresis_policy,
    make_policy,
    optimize_routing,
    plan_fleet,
    plan_topology,
    policy_scan,
    reactive_policy,
)
from repro.fleet.stream import (
    ElasticFleetPlanner,
    FleetRuntime,
    streaming_forecast_policy,
)
from repro.fleet.policy import fit_cost_coef
from repro.fleet.spec import fleet_from_params

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _random_params(rng: np.random.Generator) -> CostParams:
    k = int(rng.integers(1, 4))
    bounds = np.sort(rng.uniform(50, 5000, size=k))
    rates = np.sort(rng.uniform(0.02, 0.2, size=k))[::-1]
    tier = TieredRate(tuple(bounds[:-1]) + (np.inf,), tuple(rates))
    return CostParams(
        L_cci=float(rng.uniform(0.5, 8.0)),
        V_cci=float(rng.uniform(0.05, 0.5)),
        c_cci=float(rng.uniform(0.005, 0.05)),
        L_vpn=float(rng.uniform(0.05, 0.5)),
        vpn_tier=tier,
        D=int(rng.integers(0, 30)),
        T_cci=int(rng.integers(1, 60)),
        h=int(rng.integers(1, 60)),
        theta1=float(rng.uniform(0.8, 1.0)),
        theta2=float(rng.uniform(1.0, 1.25)),
    )


def _random_demand(rng: np.random.Generator, n: int, T: int) -> np.ndarray:
    """Regime-switching rows so the FSMs actually transition."""
    d = np.empty((n, T))
    for i in range(n):
        base = rng.uniform(0, 400)
        row = np.full(T, base)
        for _ in range(int(rng.integers(1, 6))):
            a, b = np.sort(rng.integers(0, T, size=2))
            row[a:b] = rng.uniform(0, 4000)
        d[i] = row * rng.uniform(0.8, 1.2, size=T)
    return d


def _policies_for(arrays, out, rng):
    """One instance of each policy kind over ``arrays``, forecast included
    (predictions = noisy forward means, coefficients fitted on the runtime's
    own emitted series — how they were derived is irrelevant to exactness)."""
    with jax.enable_x64():
        tp = arrays.toggle
        n, T = out["vpn_cost"].shape
        pred = _random_demand(rng, n, T) * rng.uniform(0.3, 1.2)
        coef = np.asarray(
            fit_cost_coef(
                jnp.asarray(pred), jnp.asarray(out["vpn_cost"]),
                jnp.asarray(out["cci_cost"]),
            )
        )
        return [
            reactive_policy(tp),
            hysteresis_policy(tp, up_hold=int(rng.integers(1, 8)),
                              down_hold=int(rng.integers(1, 8))),
            forecast_gated_policy(tp, pred, margin=0.05, cost_coef=coef),
        ]


# ---------------------------------------------------------------------------
# The tentpole property: streaming == policy_scan, bit for bit
# ---------------------------------------------------------------------------


@given(seed=st.integers(0, 10_000))
@settings(max_examples=6, deadline=None)
def test_streaming_steps_match_policy_scan_bit_for_bit(seed):
    """Random links + regime-switching demand, all three policies: N
    streaming steps must equal one offline policy_scan on the identical
    per-hour cost series (the runtime's emitted columns), bit for bit."""
    rng = np.random.default_rng(seed)
    n, T = 3, int(rng.integers(150, 400))
    fleet = fleet_from_params([_random_params(rng) for _ in range(n)])
    demand = _random_demand(rng, n, T)
    with jax.enable_x64():
        arrays = fleet.stack(jnp.float64)

    # Prime with a reactive pass to get the emitted cost series.
    rt = FleetRuntime(arrays, hours_per_month=fleet.hours_per_month)
    base = rt.run(demand)
    vpn, cci = base["vpn_cost"], base["cci_cost"]

    for pol in _policies_for(arrays, base, rng):
        rt = FleetRuntime(arrays, policy=pol,
                          hours_per_month=fleet.hours_per_month)
        out = rt.run(demand)
        # Identical pricing stage across policies (it is policy-independent).
        np.testing.assert_array_equal(out["vpn_cost"], vpn)
        np.testing.assert_array_equal(out["cci_cost"], cci)
        for i in range(n):
            with jax.enable_x64():
                row_pol = jax.tree.map(lambda a: a[i], pol)
                ref = policy_scan(
                    row_pol, jnp.asarray(vpn[i]), jnp.asarray(cci[i])
                )
            np.testing.assert_array_equal(out["x"][i], np.asarray(ref["x"]))
            np.testing.assert_array_equal(
                out["state"][i], np.asarray(ref["state"])
            )
            # Window sums are part of the contract too (prefix-ring == cumsum).
            np.testing.assert_array_equal(
                out["r_vpn"][i], np.asarray(ref["r_vpn"])
            )


# ---------------------------------------------------------------------------
# End-to-end vs the jitted offline engines (sampled scenarios)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", [0, 1])
def test_streaming_matches_plan_fleet(seed):
    sc = build_fleet_scenario(8, horizon=600, history_hours=300, seed=seed)
    with jax.enable_x64():
        arrays = sc.fleet.stack(jnp.float64)
    hpm = sc.fleet.hours_per_month

    plan = plan_fleet(sc.fleet, sc.demand)
    out = FleetRuntime(sc.fleet).run(sc.demand)
    np.testing.assert_array_equal(out["x"], np.asarray(plan["x"]))
    np.testing.assert_array_equal(out["state"], np.asarray(plan["state"]))
    np.testing.assert_allclose(
        out["vpn_cost"], np.asarray(plan["vpn_hourly"]), rtol=1e-12
    )

    with jax.enable_x64():
        hy = make_policy("hysteresis", arrays.toggle)
    hplan = plan_fleet(arrays, sc.demand, policy=hy, hours_per_month=hpm)
    hout = FleetRuntime(arrays, policy=hy, hours_per_month=hpm).run(sc.demand)
    np.testing.assert_array_equal(hout["x"], np.asarray(hplan["x"]))

    fpol = forecast_fleet_policy(
        arrays, sc.demand, sc.history, steps=30, hours_per_month=hpm
    )
    fplan = plan_fleet(arrays, sc.demand, policy=fpol, hours_per_month=hpm)
    fout = FleetRuntime(arrays, policy=fpol, hours_per_month=hpm).run(sc.demand)
    np.testing.assert_array_equal(fout["x"], np.asarray(fplan["x"]))


@pytest.mark.parametrize("seed", [0, 1])
def test_streaming_matches_plan_topology(seed):
    sc = build_topology_scenario(
        10, n_facilities=3, horizon=600, history_hours=300, seed=seed
    )
    routing = optimize_routing(sc.topo, sc.demand)
    hpm = sc.topo.hours_per_month
    with jax.enable_x64():
        arrays = sc.topo.stack(routing, jnp.float64)

    plan = plan_topology(arrays, sc.demand, hours_per_month=hpm)
    out = FleetRuntime(arrays, hours_per_month=hpm).run(sc.demand)
    np.testing.assert_array_equal(out["x"], np.asarray(plan["x"]))
    np.testing.assert_array_equal(out["state"], np.asarray(plan["state"]))
    np.testing.assert_allclose(
        out["cci_cost"], np.asarray(plan["cci_hourly"]), rtol=1e-12
    )

    fpol = forecast_topology_policy(
        arrays, sc.demand, sc.history, steps=30, hours_per_month=hpm
    )
    fplan = plan_topology(arrays, sc.demand, policy=fpol, hours_per_month=hpm)
    fout = FleetRuntime(arrays, policy=fpol, hours_per_month=hpm).run(sc.demand)
    np.testing.assert_array_equal(fout["x"], np.asarray(fplan["x"]))


def test_streaming_spec_entry_points_and_reset():
    """Spec-level construction (fleet + topology), mid-stream determinism:
    reset() replays identically; t tracks ticks."""
    sc = build_topology_scenario(6, n_facilities=2, horizon=200, seed=5)
    routing = optimize_routing(sc.topo, sc.demand)
    rt = FleetRuntime(sc.topo, routing=routing)
    a = rt.run(sc.demand)
    assert rt.t == sc.demand.shape[1]
    rt.reset()
    assert rt.t == 0
    b = rt.run(sc.demand)
    np.testing.assert_array_equal(a["x"], b["x"])
    with pytest.raises(AssertionError, match="routing"):
        FleetRuntime(sc.topo)


def test_month_boundary_streaming():
    """Short billing months force several within-stream tier resets; the
    streaming tier state must match the offline monthly_cumsum exactly.

    Pre-stacked arrays on purpose: with a FleetSpec both plan_fleet and
    FleetRuntime take hours_per_month from the spec (730 — no boundary
    inside 260 hours), silently ignoring the kwarg."""
    rng = np.random.default_rng(7)
    fleet = fleet_from_params([_random_params(rng) for _ in range(3)])
    demand = _random_demand(rng, 3, 260)
    with jax.enable_x64():
        arrays = fleet.stack(jnp.float64)
    plan = plan_fleet(arrays, demand, hours_per_month=48)
    out = FleetRuntime(arrays, hours_per_month=48).run(demand)
    assert FleetRuntime(arrays, hours_per_month=48).hours_per_month == 48
    np.testing.assert_array_equal(out["x"], np.asarray(plan["x"]))
    np.testing.assert_allclose(
        out["vpn_cost"], np.asarray(plan["vpn_hourly"]), rtol=1e-12
    )
    # And the boundary really is exercised: tier positions reset at 48/96/...
    assert np.any(np.diff(np.asarray(plan["vpn_hourly"])[:, 47:49], axis=1) != 0)


# ---------------------------------------------------------------------------
# Live re-routing: reroute() == offline replay_plan_topology, bit for bit
# ---------------------------------------------------------------------------


def _alternative_routing(topo, r0, rng, max_moved=6):
    """A valid RoutingPlan that moves a few pairs to other candidate ports."""
    idx = np.asarray(r0.primary).copy()
    moved = 0
    for i, pr in enumerate(topo.pairs):
        others = [c for c in pr.candidates if c != idx[i]]
        if others and moved < max_moved and rng.random() < 0.8:
            idx[i] = int(rng.choice(others))
            moved += 1
    return topo.plan(idx), moved


@given(seed=st.integers(0, 10_000))
@settings(max_examples=4, deadline=None)
def test_reroute_matches_offline_replay_bit_for_bit(seed):
    """The tentpole's re-routing contract: streaming with reroute() at hour
    s equals an offline replay that applies the same routing at the same
    hour — decisions bit-for-bit over the WHOLE horizon (window sums near
    the swap mix old- and new-routing hours identically on both sides),
    for reactive, hysteresis and forecast-replay policies."""
    from repro.fleet.plan import replay_plan_topology

    rng = np.random.default_rng(seed)
    sc = build_topology_scenario(
        8, n_facilities=3, horizon=int(rng.integers(250, 450)), seed=seed
    )
    r0 = optimize_routing(sc.topo, sc.demand)
    r1, moved = _alternative_routing(sc.topo, r0, rng)
    if moved == 0:
        return  # no alternative candidates sampled — nothing to swap
    T = sc.demand.shape[1]
    s = int(rng.integers(50, T - 50))
    hpm = sc.topo.hours_per_month
    with jax.enable_x64():
        arrays = sc.topo.stack(r0, jnp.float64)

    base = FleetRuntime(arrays, hours_per_month=hpm).run(sc.demand)
    for pol in _policies_for(arrays, base, rng):
        rt = FleetRuntime(arrays, policy=pol, hours_per_month=hpm)
        outs = []
        for t in range(T):
            if t == s:
                rt.reroute(r1)
            outs.append(rt.step(sc.demand[:, t]))
        x = np.stack([o["x"] for o in outs], axis=1)
        state = np.stack([o["state"] for o in outs], axis=1)
        replay = replay_plan_topology(
            arrays, sc.demand, [(0, r0), (s, r1)],
            policy=pol, hours_per_month=hpm,
        )
        np.testing.assert_array_equal(x, np.asarray(replay["x"]))
        np.testing.assert_array_equal(state, np.asarray(replay["state"]))


@given(seed=st.integers(0, 10_000))
@settings(max_examples=3, deadline=None)
def test_obs_on_off_decisions_bit_exact(seed):
    """Observability is a pure CONSUMER of the tick: with the device metrics
    ring in the carry (small drain cadence so drains actually interleave),
    tracing, monitors and divergence recording all on, every decision — and
    the realized cost — equals the obs-off stream bit for bit, for all three
    policies, across a mid-stream reroute(). And the honest stream passes
    every contract monitor."""
    from repro.obs import ObsConfig

    rng = np.random.default_rng(seed)
    sc = build_topology_scenario(
        8, n_facilities=3, horizon=int(rng.integers(180, 320)), seed=seed
    )
    r0 = optimize_routing(sc.topo, sc.demand)
    r1, moved = _alternative_routing(sc.topo, r0, rng)
    T = sc.demand.shape[1]
    s = int(rng.integers(40, T - 40))
    hpm = sc.topo.hours_per_month
    with jax.enable_x64():
        arrays = sc.topo.stack(r0, jnp.float64)

    base = FleetRuntime(arrays, hours_per_month=hpm).run(sc.demand)
    for pol in _policies_for(arrays, base, rng):

        def stream(obs):
            rt = FleetRuntime(arrays, policy=pol, hours_per_month=hpm, obs=obs)
            outs = []
            for t in range(T):
                if moved and t == s:
                    rt.reroute(r1)
                outs.append(rt.step(sc.demand[:, t]))
            return rt, {
                k: np.stack([o[k] for o in outs], axis=1)
                for k in ("x", "state", "cost")
            }

        _, plain = stream(None)
        ort, traced = stream(ObsConfig(cadence=7, divergence=True))
        np.testing.assert_array_equal(plain["x"], traced["x"])
        np.testing.assert_array_equal(plain["state"], traced["state"])
        np.testing.assert_array_equal(plain["cost"], traced["cost"])
        ort.obs_check(final=True)
        rep = ort.obs_report()
        assert rep.hours == T and rep.violations == []


@given(seed=st.integers(0, 10_000))
@settings(max_examples=3, deadline=None)
def test_step_many_chunking_bit_exact(seed):
    """The chunked-stepping contract: ``step_many`` over any chunking of the
    demand stream equals per-tick ``step()`` BIT-EXACTLY — decisions, window
    sums, costs, and the carried billing prefixes — for all three policies,
    K in {1, 7, 24}, across a reroute() at a chunk boundary, with obs off
    and on (drain cadence a chunk multiple), and interleaved with a
    per-tick ragged tail."""
    from repro.obs import ObsConfig

    rng = np.random.default_rng(seed)
    sc = build_topology_scenario(
        8, n_facilities=3, horizon=int(rng.integers(210, 300)), seed=seed
    )
    r0 = optimize_routing(sc.topo, sc.demand)
    r1, moved = _alternative_routing(sc.topo, r0, rng)
    T = sc.demand.shape[1]
    s = 168  # chunk boundary for every K in {1, 7, 24} (168 = 7 * 24)
    hpm = sc.topo.hours_per_month
    with jax.enable_x64():
        arrays = sc.topo.stack(r0, jnp.float64)

    fields = ("x", "state", "r_vpn", "r_cci", "vpn_cost", "cci_cost", "cost")
    base = FleetRuntime(arrays, hours_per_month=hpm).run(sc.demand)
    for pol in _policies_for(arrays, base, rng):
        # Per-tick reference stream (reroute at hour s).
        rt = FleetRuntime(arrays, policy=pol, hours_per_month=hpm)
        ref = []
        for t in range(T):
            if moved and t == s:
                rt.reroute(r1)
            ref.append(rt.step(sc.demand[:, t]))
        want = {f: np.stack([o[f] for o in ref], axis=1) for f in fields}
        want_state = rt._state

        for K in (1, 7, 24):
            for obs in (None, ObsConfig(cadence=3 * K, divergence=True)):
                rt2 = FleetRuntime(arrays, policy=pol,
                                   hours_per_month=hpm, obs=obs)
                outs, t = [], 0
                while t + K <= T:
                    if moved and t == s:
                        rt2.reroute(r1)
                    o = rt2.step_many(sc.demand[:, t:t + K])
                    outs.append({f: o[f] for f in fields})
                    t += K
                while t < T:  # ragged tail: chunked and per-tick interleave
                    if moved and t == s:
                        rt2.reroute(r1)
                    o = rt2.step(sc.demand[:, t])
                    outs.append({f: np.asarray(o[f])[:, None]
                                 for f in fields})
                    t += 1
                got = {f: np.concatenate([o[f] for o in outs], axis=1)
                       for f in fields}
                ctx = f"K={K} obs={'on' if obs else 'off'}"
                for f in fields:
                    np.testing.assert_array_equal(
                        got[f], want[f], err_msg=f"{ctx}:{f}"
                    )
                # Carried billing prefixes resync identically at boundaries.
                for f in ("vpn_pref", "cci_pref", "dcum", "month_vol"):
                    np.testing.assert_array_equal(
                        getattr(rt2._state, f), getattr(want_state, f),
                        err_msg=f"{ctx}:{f}",
                    )
                if obs is not None:
                    rt2.obs_check(final=True)
                    rep = rt2.obs_report()
                    assert rep.hours == T and rep.violations == []


def _variant_runtime(variant):
    """(runtime, demand, cci_demand or None) for one step_many variant."""
    from repro.obs import ObsConfig

    if variant.startswith("topology"):
        sc = build_topology_scenario(8, n_facilities=3, horizon=120, seed=4)
        hpm = sc.topo.hours_per_month
        with jax.enable_x64():
            arrays = sc.topo.stack(optimize_routing(sc.topo, sc.demand),
                                   jnp.float64)
    else:
        sc = build_fleet_scenario(6, horizon=120, history_hours=96, seed=4)
        hpm = sc.fleet.hours_per_month
        with jax.enable_x64():
            arrays = sc.fleet.stack(jnp.float64)
    kw = dict(hours_per_month=hpm)
    if variant in ("obs", "topology_obs"):
        kw["obs"] = ObsConfig(cadence=48)
    if variant == "replay":
        kw["policy"] = forecast_fleet_policy(
            arrays, sc.demand, sc.history, steps=3, hours_per_month=hpm
        )
    if variant == "live":
        kw["policy"], kw["forecaster"] = streaming_forecast_policy(
            arrays, sc.history, steps=3, hours_per_month=hpm
        )
    cci = 0.5 * sc.demand if variant == "endo" else None
    return FleetRuntime(arrays, **kw), sc.demand, cci


def _bits(a):
    return np.ascontiguousarray(a).tobytes()


@pytest.mark.parametrize("variant", [
    "plain", "endo", "replay", "live", "obs", "topology", "topology_obs",
])
def test_step_many_out_is_the_builders_planes(variant):
    """The packed trip home changes no bit: every key of ``step_many``'s
    ``out``, the mirrored accumulators, the live forecast, the observer's
    ``d_pair`` and the drained ring equal what the unwrapped
    ``_build_step_many`` returns for the same inputs, in ``out``'s dtypes
    and shapes; every FSM state fits the int8 decision buffer."""
    from repro.fleet import runtime

    rt, demand, cci = _variant_runtime(variant)
    obs = rt.obs is not None
    seen = {}
    if obs:
        chunk, drain_ = rt.obs.record_chunk, rt.obs.record_drain
        rt.obs.record_chunk = lambda t, outs, **kw: (
            seen.update(d_pair=kw["d_pair"]), chunk(t, outs, **kw))
        rt.obs.record_drain = lambda t, v: (
            seen.update(drain=v), drain_(t, v))
    K, M, P = 24, rt.n_rows, rt.n_demand_rows
    assert rt.topology == variant.startswith("topology")
    assert (rt.pred_source or "") == (
        variant if variant in ("replay", "live") else "")
    for t in range(0, 96, K):
        st, d = rt._state, demand[:, t:t + K]
        c = None if cci is None else cci[:, t:t + K]
        drain = obs and (t + K) % rt.obs.cadence == 0
        ref_fn = jax.jit(runtime._build_step_many(
            rt.topology, rt.pred_source, c is not None, obs, drain, K))
        with jax.enable_x64():
            ref = ref_fn(rt.arrays, rt.policy, rt._fc, st.fsm, st.ssm_h,
                         st.t_dev, st.routing, st.metrics, rt._obs_edges,
                         rt._hpm_dev, rt._device_seq(),
                         jax.device_put(rt._pack(st, d, c)))
            _, _, _, _, seq, planes, drain_vec = jax.tree.map(np.asarray, ref)
        x, state, vpn_t, cci_t, d_pair, *rest = planes
        pred = rest.pop(0) if rt.pred_source == "live" else None
        r_vpn, r_cci, snap_v, snap_c = rest
        assert np.array_equal(state.astype(np.int8), state)
        assert set(np.unique(state)) <= {0, 1, 2}

        seen.clear()
        out = rt.step_many(d, cci_demand_block=c)
        want = {
            "x": x.astype(np.int64).T, "state": state.astype(np.int64).T,
            "r_vpn": r_vpn.T, "r_cci": r_cci.T,
            "vpn_cost": vpn_t.T, "cci_cost": cci_t.T,
            "cost": np.where(x == 1, cci_t, vpn_t).T,
        }
        assert out.keys() == want.keys()
        for k, v in want.items():
            assert out[k].dtype == v.dtype and out[k].shape == (M, K), k
            assert _bits(out[k]) == _bits(v), (variant, t, k)
        got = rt._state
        for k, v in zip(("dcum", "month_vol", "vpn_pref", "cci_pref"), seq):
            assert _bits(getattr(got, k)) == _bits(v), (variant, t, k)
        assert _bits(got.ring_vpn[(t + K - 1) % rt.hbuf]) == _bits(snap_v[-1])
        assert _bits(got.ring_cci[(t + K - 1) % rt.hbuf]) == _bits(snap_c[-1])
        if pred is not None:
            assert _bits(got.pred_live) == _bits(pred[-1])
        if obs:
            assert seen["d_pair"].shape == (K, P)
            assert _bits(seen["d_pair"]) == _bits(d_pair)
            assert ("drain" in seen) == drain
            if drain:
                assert _bits(seen["drain"]) == _bits(drain_vec)


@pytest.mark.parametrize("case, t, K", [
    ("early_stream", (2, 6), 5),
    ("wrap", (13, 30), 7),
    ("k_over_hbuf", (20, 41), 11),
])
def test_ring_reads_match_a_per_hour_loop(case, t, K):
    """The shared ring read (``runtime._ring_reads``) over two slots on
    different clocks equals a plain loop over every (slot, hour, row):
    the prefix at hour ``t + k - h`` from slot ``(t + k - h) % hbuf``,
    slot 0 before hour 0, and zero where ``k >= hbuf``."""
    from repro.fleet import runtime

    rng = np.random.default_rng(len(case))
    S, hbuf, M = 2, 8, 5
    t = np.asarray(t)
    h = rng.integers(1, hbuf, size=(S, M))
    rings = tuple(rng.normal(size=(S, hbuf, M)) for _ in range(2))
    outs = tuple(np.full((S, K, M), np.nan) for _ in range(2))
    runtime._ring_reads(t, h, rings, outs)
    for ring, out in zip(rings, outs):
        want = np.empty((S, K, M))
        for s in range(S):
            for k in range(K):
                for m in range(M):
                    hour = t[s] + k - h[s, m]
                    want[s, k, m] = (
                        0.0 if k >= hbuf else ring[s, max(hour, 0) % hbuf, m]
                    )
        np.testing.assert_array_equal(out, want, err_msg=case)
    if case == "early_stream":
        assert (t[:, None, None] + np.arange(K)[:, None] < h[:, None, :]).any()
    else:
        assert (t >= hbuf).all()
    assert (K > hbuf) == (case == "k_over_hbuf")


def test_replay_single_segment_is_plan_topology():
    """A one-entry schedule must reproduce plan_topology bit-for-bit (the
    replay oracle degenerates to the offline planner)."""
    from repro.fleet.plan import plan_topology, replay_plan_topology

    sc = build_topology_scenario(8, n_facilities=3, horizon=400, seed=2)
    r0 = optimize_routing(sc.topo, sc.demand)
    hpm = sc.topo.hours_per_month
    with jax.enable_x64():
        arrays = sc.topo.stack(r0, jnp.float64)
    plan = plan_topology(arrays, sc.demand, hours_per_month=hpm)
    rep = replay_plan_topology(arrays, sc.demand, [(0, r0)], hours_per_month=hpm)
    np.testing.assert_array_equal(np.asarray(rep["x"]), np.asarray(plan["x"]))
    np.testing.assert_array_equal(
        np.asarray(rep["state"]), np.asarray(plan["state"])
    )
    np.testing.assert_array_equal(
        np.asarray(rep["toggle_cost"]), np.asarray(plan["toggle_cost"])
    )


def test_reroute_guards_and_modes_mapping():
    """reroute() is topology-only, validates against the spec, and modes()
    maps port states onto PAIRS through the current routing."""
    from repro.fleet.plan import build_reroute_scenario

    sc = build_reroute_scenario(horizon=300, shift_hour=150, seed=0)
    rt = FleetRuntime(sc.topo, routing=sc.topo.plan([0, 0, 1]))
    out = rt.step(sc.demand[:, 0])
    modes = rt.modes(out)
    assert len(modes) == 3  # per PAIR, not per port
    states = np.asarray(out["state"])
    from repro.core.planner import collective_mode

    assert modes == [collective_mode(int(states[m])) for m in (0, 0, 1)]
    np.testing.assert_array_equal(rt.port_occupancy(), [2.0, 1.0])
    rt.reroute(sc.topo.plan([0, 0, 0]))
    np.testing.assert_array_equal(rt.port_occupancy(), [3.0, 0.0])
    with pytest.raises(AssertionError, match="non-candidate"), \
            pytest.warns(DeprecationWarning):
        rt.reroute([1, 0, 0])  # pair 0's only candidate is port 0
    with pytest.raises(AssertionError, match="non-candidate"), \
            pytest.warns(DeprecationWarning):
        # The legacy matrix form goes through the SAME candidate validation.
        rt.reroute(np.array([[0.0, 1.0, 1.0], [1.0, 0.0, 0.0]]))
    with pytest.raises(AssertionError, match="one-hot"), \
            pytest.warns(DeprecationWarning):
        rt.reroute(np.ones((2, 3)))
    fleet_rt = FleetRuntime(_planner_fleet())
    with pytest.raises(AssertionError, match="topology"):
        fleet_rt.reroute([0, 0])
    assert fleet_rt.modes(fleet_rt.step(np.zeros(2))) == ["compressed"] * 2


def test_reroute_demo_scenario_realizes_savings():
    """The CI demo's core claim, in-tree: live re-routing onto the freed
    hub port beats the frozen day-one routing on realized streamed cost."""
    from repro.fleet.plan import build_reroute_scenario

    sc = build_reroute_scenario(horizon=1400, shift_hour=500, seed=1)
    r0 = optimize_routing(sc.topo, sc.demand[:, :168])
    assert list(r0.primary) == [0, 0, 1]  # hub full -> hot pair spills

    def run(live):
        rt = FleetRuntime(sc.topo, routing=r0)
        cost = 0.0
        for t in range(sc.demand.shape[1]):
            if live and t > 0 and t % 24 == 0:
                seen = sc.demand[:, max(0, t - 168):t].mean(axis=1)
                r_new = optimize_routing(sc.topo, mean_demand=seen)
                if not np.array_equal(r_new.primary, rt.routing_plan.primary):
                    rt.reroute(r_new)
            cost += float(rt.step(sc.demand[:, t])["cost"].sum())
        return cost, rt

    frozen, _ = run(False)
    lively, rt = run(True)
    assert lively < frozen
    np.testing.assert_array_equal(rt.port_occupancy(), [3.0, 0.0])


# ---------------------------------------------------------------------------
# Live-SSM forecast mode (causal, endogenous-capable)
# ---------------------------------------------------------------------------


def test_live_forecast_mode_matches_pinned_replay():
    """The carried SSM state must reproduce the offline forecaster's causal
    prediction columns: with the coefficients pinned, live streaming equals
    the offline plan on the replayed predictions."""
    from repro.fleet.policy import forecast_horizon_hours, forecast_port_demand

    sc = build_fleet_scenario(6, horizon=400, history_hours=300, seed=3)
    hpm = sc.fleet.hours_per_month
    with jax.enable_x64():
        arrays = sc.fleet.stack(jnp.float64)
    pol, fc = streaming_forecast_policy(
        arrays, sc.history, steps=30, hours_per_month=hpm
    )
    out = FleetRuntime(
        arrays, policy=pol, forecaster=fc, hours_per_month=hpm
    ).run(sc.demand)

    cap = np.asarray(arrays.capacity)[:, None]
    clip = lambda d: np.minimum(np.asarray(d, np.float64), cap)
    pred = forecast_port_demand(
        clip(sc.history), clip(sc.demand),
        forecast_horizon_hours(arrays.toggle), steps=30,
    )
    with jax.enable_x64():
        replay = forecast_gated_policy(
            arrays.toggle, pred, margin=0.05, cost_coef=np.asarray(pol.cost_coef)
        )
    rplan = plan_fleet(arrays, sc.demand, policy=replay, hours_per_month=hpm)
    np.testing.assert_array_equal(out["x"], np.asarray(rplan["x"]))


def test_streaming_forecast_requires_cost_coef():
    rng = np.random.default_rng(0)
    fleet = fleet_from_params([_random_params(rng)])
    with jax.enable_x64():
        arrays = fleet.stack(jnp.float64)
        pol = forecast_gated_policy(arrays.toggle, np.zeros((1, 100)))
    with pytest.raises(AssertionError, match="cost_coef"):
        FleetRuntime(arrays, policy=pol)


# ---------------------------------------------------------------------------
# Endogenous-demand actuation (ElasticFleetPlanner)
# ---------------------------------------------------------------------------


def _planner_fleet():
    """One cold link (stays on the compressed pay-per-GB path) and one hot
    link (leases)."""
    from repro.core.planner import dci_scenario

    return fleet_from_params([dci_scenario(), dci_scenario()])


def test_elastic_planner_modes_split_per_link():
    pl = ElasticFleetPlanner(_planner_fleet())
    modes = None
    for _ in range(1500):
        modes = pl.feed_hour(np.array([1e9, 200e12]))  # 1 GB vs 200 TB hourly
    rep = pl.report()
    assert modes == ["compressed", "hierarchical"]
    assert rep.on_fraction[0] == 0.0 and rep.on_fraction[1] > 0.5
    # Per-link realized costs beat the wrong static policy on each side.
    assert rep.total_cost <= rep.cost_always_cci
    assert rep.link_cost[1] < pl.cost_vpn_only[1]


def test_elastic_planner_matches_single_link_controller():
    """N=1 ElasticFleetPlanner == core's InterconnectPlanner on the same
    byte stream (same FSM decisions; costs equal to float tolerance — the
    single-link controller slides its window with add/subtract, the runtime
    with exact prefix differences)."""
    from repro.core.planner import InterconnectPlanner, dci_scenario

    rng = np.random.default_rng(11)
    gb = np.where(rng.random(2500) < 0.5, 40e3, 20.0)  # regime flips, GB/h
    single = InterconnectPlanner()
    fleetp = ElasticFleetPlanner(fleet_from_params([dci_scenario()]))
    modes_a, modes_b = [], []
    for v in gb:
        modes_a.append(single.feed_hour(v * 1e9))
        modes_b.append(fleetp.feed_hour(np.array([v * 1e9]))[0])
    assert modes_a == modes_b
    ra, rb = single.report(), fleetp.report()
    assert ra.total_cost == pytest.approx(rb.total_cost, rel=1e-9)
    assert ra.cost_always_vpn == pytest.approx(rb.cost_always_vpn, rel=1e-9)
    assert ra.cost_always_cci == pytest.approx(rb.cost_always_cci, rel=1e-9)
    assert ra.on_fraction == pytest.approx(float(rb.on_fraction[0]))


def test_fleet_planner_factory():
    from repro.core.planner import fleet_planner

    pl = fleet_planner(_planner_fleet())
    assert isinstance(pl, ElasticFleetPlanner)


def test_elastic_planner_per_port_topology_mode():
    """Per-port actuation: feed per-PAIR bytes, get per-pair modes mapped
    through the routing; the report carries per-PORT lease occupancy and
    per-pair wire-byte savings instead of assuming one link per row."""
    from repro.core.pricing import flat_rate
    from repro.fleet.plan import PairSpec, PortSpec, TopologySpec

    mk_port = lambda n, f: PortSpec(
        name=n, facility=f, cloud="aws", L_cci=4.55, V_cci=0.1,
        c_cci=0.002, D=6, T_cci=12, h=12,
    )
    pairs = tuple(
        PairSpec(f"pr{i}", "gcp", "aws", 0.105, flat_rate(0.1),
                 candidates=(0, 1))
        for i in range(3)
    )
    topo = TopologySpec(ports=(mk_port("hub", "f0"), mk_port("idle", "f1")),
                        pairs=pairs)
    pl = ElasticFleetPlanner(topo, routing=topo.plan([0, 0, 1]))
    assert pl.topology
    np.testing.assert_array_equal(pl.sync_groups(), [0, 0, 1])
    traffic = np.array([5e12, 5e12, 1e9])  # two hot pairs share the hub
    modes = None
    for _ in range(200):
        modes = pl.feed_hour(traffic)
    assert modes == ["hierarchical", "hierarchical", "compressed"]
    rep = pl.report()
    np.testing.assert_array_equal(rep.port_occupancy, [2.0, 1.0])
    assert rep.on_fraction.shape == (2,)        # per PORT
    assert rep.pair_gb_saved.shape == (3,)      # per PAIR
    # The cold pair keeps compressing all 200 hours; the hot pairs only
    # during the provisioning window — per-GB savings must reflect that.
    frac_saved = rep.pair_gb_saved / (rep.pair_gb + rep.pair_gb_saved)
    assert frac_saved[2] > frac_saved[0]
    assert 0 < rep.wire_savings_fraction < 1
    # Shared lease: the hub port's CCI counterfactual charges ONE lease for
    # two pairs — L + 2V + c·(d1+d2) per hour, not 2L (the per-link view).
    gb = traffic / 1e9
    shared_hour = 4.55 + 2 * 0.1 + 0.002 * (gb[0] + gb[1])
    assert pl.cost_cci_only[0] == pytest.approx(rep.hours * shared_hour, rel=1e-9)
    # Re-routing re-targets actuation next tick.
    pl.runtime.reroute(topo.plan([0, 0, 0]))
    modes = pl.feed_hour(traffic)
    np.testing.assert_array_equal(pl.sync_groups(), [0, 0, 0])
    assert modes[2] == "hierarchical"  # now rides the (ON) hub port


# ---------------------------------------------------------------------------
# Collective actuation: link modes select the int8 vs hierarchical path
# ---------------------------------------------------------------------------


def test_sync_wire_bytes_compression_ratio():
    from repro.dist.collectives import sync_wire_bytes

    grads = {"w": jnp.zeros((256, 256), jnp.float32), "b": jnp.zeros((256,), jnp.float32)}
    full = sync_wire_bytes(grads, "hierarchical")
    comp = sync_wire_bytes(grads, "compressed")
    assert full == (256 * 256 + 256) * 4
    # int8 payload + one f32 scale per row: a hair under 4x.
    assert 3.5 < full / comp <= 4.0


def test_link_modes_actuate_sync_grads():
    """Two links on one mesh: the 'hierarchical' link syncs exactly like the
    full-precision path, the 'compressed' link goes through int8+error
    feedback (approximate, carries a residual, ~4x fewer billed bytes)."""
    script = """
        import numpy as np
        import jax, jax.numpy as jnp
        from repro.launch.mesh import make_host_mesh
        from repro.dist.collectives import fleet_sync_grads, sync_grads

        mesh = make_host_mesh(pod=2, data=2, model=2)
        rng = np.random.default_rng(0)
        grads = [
            {"w": jnp.asarray(rng.normal(size=(64, 32)), jnp.float32)}
            for _ in range(2)
        ]
        modes = ["hierarchical", "compressed"]
        synced, errs, billed = fleet_sync_grads(grads, mesh, modes)
        # Link 0: exact full-precision hierarchical sync, no residual.
        ref0, _ = sync_grads(grads[0], mesh, mode="hierarchical")
        np.testing.assert_array_equal(
            np.asarray(synced[0]["w"]), np.asarray(ref0["w"])
        )
        assert errs[0] is None
        # Link 1: int8 path — approximate, residual returned, ~4x fewer bytes.
        a = np.asarray(grads[1]["w"]); b = np.asarray(synced[1]["w"])
        assert np.max(np.abs(a - b)) < np.abs(a).max() / 32
        assert errs[1] is not None
        assert 3.0 < billed[0] / billed[1] <= 4.0

        # Shared sync domains (per-port topology actuation): pairs on one
        # leased port sync in ONE call — results and per-pair billed bytes
        # identical to the ungrouped path.
        grads4 = [
            {"w": jnp.asarray(rng.normal(size=(64, 32)), jnp.float32)}
            for _ in range(4)
        ]
        modes4 = ["hierarchical", "hierarchical", "compressed", "compressed"]
        groups = [7, 7, 7, 9]  # pairs 0+1 share port 7's leased domain
        gs, ge, gb = fleet_sync_grads(grads4, mesh, modes4, groups=groups)
        us, ue, ub = fleet_sync_grads(grads4, mesh, modes4)
        for i in range(4):
            np.testing.assert_array_equal(
                np.asarray(gs[i]["w"]), np.asarray(us[i]["w"])
            )
        assert gb == ub
        assert ge[0] is None and ge[2] is not None
        # Carried residuals survive a re-grouping (post-reroute step).
        gs2, ge2, _ = fleet_sync_grads(
            grads4, mesh, modes4, ge, groups=[7, 9, 9, 9]
        )
        us2, ue2, _ = fleet_sync_grads(grads4, mesh, modes4, ue)
        for i in range(4):
            np.testing.assert_array_equal(
                np.asarray(gs2[i]["w"]), np.asarray(us2[i]["w"])
            )
        print("OK")
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(script)],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr[-4000:]}"
    assert "OK" in out.stdout
