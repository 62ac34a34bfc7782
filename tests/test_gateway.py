"""Multi-tenant gateway contracts: pooled == standalone bit-for-bit, churn
never recompiles, admission backpressure is bounded and typed, and the
tenant-axis metrics path reconciles per tenant.

The core property is the streamed-vs-offline exactness guarantee lifted one
level: every tenant a gateway serves must step EXACTLY as its own standalone
``FleetRuntime`` would — same FSM decisions, same float64 costs, same window
sums — whatever its neighbors in the pool do (join, leave, re-route)."""
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

import jax
import jax.numpy as jnp

from repro.fleet.policy import (
    fit_cost_coef,
    forecast_gated_policy,
    hysteresis_policy,
    reactive_policy,
)
from repro.fleet.runtime import FleetRuntime, RuntimeConfig
from repro.fleet.scenario import (
    build_fleet_scenario,
    build_topology_scenario,
)
from repro.fleet.topology import optimize_routing
from repro.gateway import (
    AdmissionError,
    FleetGateway,
    GatewayConfig,
    TenantSLO,
    TenantSpec,
)

STEP_FIELDS = ("x", "state", "r_vpn", "r_cci", "vpn_cost", "cci_cost", "cost")


def _assert_step_equal(got, want, ctx):
    for f in STEP_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(got[f]), np.asarray(want[f]), err_msg=f"{ctx}:{f}"
        )


def _topology_tenant(n_pairs, horizon, seed, *, policy_kind="reactive", rng=None):
    """One topology tenant spec + its standalone reference runtime."""
    sc = build_topology_scenario(
        n_pairs, n_facilities=2, ports_per_facility=2,
        horizon=horizon, seed=seed,
    )
    routing = optimize_routing(sc.topo, sc.demand)
    policy = None
    if policy_kind != "reactive":
        with jax.enable_x64():
            arrays = sc.topo.stack(routing, jnp.float64)
            base = FleetRuntime(
                arrays, hours_per_month=sc.topo.hours_per_month
            ).run(sc.demand)
            tp = arrays.toggle
            if policy_kind == "hysteresis":
                policy = hysteresis_policy(
                    tp, up_hold=int(rng.integers(1, 6)),
                    down_hold=int(rng.integers(1, 6)),
                )
            else:
                pred = np.maximum(
                    base["r_vpn"][:, -1:] * 0 +
                    rng.uniform(0.3, 1.2) * np.asarray(base["vpn_cost"]), 0.0
                )
                coef = np.asarray(fit_cost_coef(
                    jnp.asarray(pred), jnp.asarray(base["vpn_cost"]),
                    jnp.asarray(base["cci_cost"]),
                ))
                policy = forecast_gated_policy(
                    tp, pred, margin=0.05, cost_coef=coef
                )
    cfg = RuntimeConfig(routing=routing, policy=policy)
    spec = TenantSpec(spec=sc.topo, demand=sc.demand, config=cfg)
    ref = FleetRuntime.from_config(sc.topo, cfg)
    return spec, ref, sc


def _alt_routing(topo, r0, rng):
    idx = np.asarray(r0.primary).copy()
    moved = 0
    for i, pr in enumerate(topo.pairs):
        others = [c for c in pr.candidates if c != idx[i]]
        if others and rng.random() < 0.8:
            idx[i] = int(rng.choice(others))
            moved += 1
    return topo.plan(idx), moved


# ---------------------------------------------------------------------------
# The tentpole property: pooled decisions == standalone, bit for bit
# ---------------------------------------------------------------------------


@given(seed=st.integers(0, 10_000))
@settings(max_examples=4, deadline=None)
def test_gateway_matches_standalone_bit_for_bit(seed):
    """Heterogeneous tenants across all three policies, sharing pools: every
    tick of every tenant equals its standalone FleetRuntime bit for bit —
    including one tenant re-routing mid-stream and one leaving mid-stream
    (its departure must not perturb its pool neighbors by one ulp)."""
    rng = np.random.default_rng(seed)
    T = int(rng.integers(60, 120))
    gw = FleetGateway(GatewayConfig(slots_per_bucket=4, cadence=16))

    tenants = {}
    for i, kind in enumerate(("reactive", "hysteresis", "forecast")):
        name = f"t{i}-{kind}"
        spec, ref, sc = _topology_tenant(
            int(rng.integers(3, 7)), T, seed + i, policy_kind=kind, rng=rng
        )
        gw.join(name, spec)
        tenants[name] = (spec, ref, sc)
    # Plus one fleet-mode tenant in its own bucket family.
    fsc = build_fleet_scenario(int(rng.integers(2, 5)), horizon=T, seed=seed)
    fcfg = RuntimeConfig()
    gw.join("fleet", TenantSpec(spec=fsc.fleet, demand=fsc.demand, config=fcfg))
    tenants["fleet"] = (
        TenantSpec(spec=fsc.fleet, demand=fsc.demand, config=fcfg),
        FleetRuntime.from_config(fsc.fleet, fcfg),
        fsc,
    )

    reroute_name = "t0-reactive"
    _, _, rsc = tenants[reroute_name]
    r1, moved = _alt_routing(
        rsc.topo, optimize_routing(rsc.topo, rsc.demand), rng
    )
    s_reroute = int(rng.integers(T // 4, T // 2))
    leaver = "t1-hysteresis"
    s_leave = int(rng.integers(T // 2, T - 10))

    compiles_after_first_tick = None
    for t in range(T):
        if t == s_reroute and moved:
            gw.reroute(reroute_name, r1)
            tenants[reroute_name][1].reroute(r1)
        if t == s_leave:
            gw.leave(leaver)
        outs = gw.tick()
        if compiles_after_first_tick is None:
            compiles_after_first_tick = gw.compiles
        for name, (spec, ref, sc) in tenants.items():
            if name == leaver and t >= s_leave:
                assert name not in outs
                continue
            ref_out = ref.step(sc.demand[:, t])
            _assert_step_equal(outs[name], ref_out, f"{name}@t{t}")
    # Membership churn (the departure) and the reroute never recompiled:
    # only the drain-variant tick may have joined after the first hour.
    assert gw.compiles <= compiles_after_first_tick + gw.n_buckets
    assert gw.check() == []


@given(seed=st.integers(0, 10_000))
@settings(max_examples=3, deadline=None)
def test_tick_many_matches_per_tick_bit_for_bit(seed):
    """The chunked mega-tick mirror of the standalone contract:
    ``tick_many(K)`` equals K sequential ``tick()`` calls bit for bit for
    every pooled tenant — stacked (rows, K) outputs, float64 billing
    totals, a reroute() applied at a chunk boundary, and a per-tick ragged
    tail interleaved after the chunks (drain cadence is a chunk multiple,
    so drains fire at the same hours on both sides)."""
    rng = np.random.default_rng(seed)
    K = int(rng.integers(2, 9))
    n_chunks = max(5, -(-28 // K))  # scenario builders need horizon >= 24
    tail = int(rng.integers(1, 4))
    T = K * n_chunks + tail

    tenants = {}
    for i, kind in enumerate(("reactive", "hysteresis", "forecast")):
        name = f"t{i}-{kind}"
        spec, _, sc = _topology_tenant(
            int(rng.integers(3, 7)), T, seed + i, policy_kind=kind, rng=rng
        )
        tenants[name] = (spec, sc)
    fsc = build_fleet_scenario(3, horizon=T, seed=seed)
    tenants["fleet"] = (
        TenantSpec(spec=fsc.fleet, demand=fsc.demand, config=RuntimeConfig()),
        fsc,
    )

    gw_a = FleetGateway(GatewayConfig(slots_per_bucket=4, cadence=2 * K))
    gw_b = FleetGateway(GatewayConfig(slots_per_bucket=4, cadence=2 * K))
    for name, (spec, _) in tenants.items():
        gw_a.join(name, spec)
        gw_b.join(name, spec)

    reroute_name = "t0-reactive"
    _, rsc = tenants[reroute_name]
    r1, moved = _alt_routing(
        rsc.topo, optimize_routing(rsc.topo, rsc.demand), rng
    )
    s = 2 * K  # a chunk boundary on the chunked side

    per_tick = {name: [] for name in tenants}
    for t in range(T):
        if t == s and moved:
            gw_a.reroute(reroute_name, r1)
        outs = gw_a.tick()
        for name in tenants:
            per_tick[name].append(outs[name])

    t = 0
    for _ in range(n_chunks):
        if t == s and moved:
            gw_b.reroute(reroute_name, r1)
        outs = gw_b.tick_many(K)
        for name in tenants:
            for k in range(K):
                got = {f: np.asarray(outs[name][f])[:, k]
                       for f in STEP_FIELDS}
                _assert_step_equal(
                    got, per_tick[name][t + k], f"{name}@chunk-hour{t + k}"
                )
        t += K
    while t < T:  # ragged tail: chunked and per-tick interleave freely
        outs = gw_b.tick()
        for name in tenants:
            _assert_step_equal(outs[name], per_tick[name][t],
                               f"{name}@tail-hour{t}")
        t += 1

    assert gw_b.hours == gw_a.hours == T
    for name in tenants:
        ba, bb = gw_a.billing(name), gw_b.billing(name)
        assert ba == bb, (name, ba, bb)
    assert gw_a.check() == [] and gw_b.check() == []


def test_mega_tick_steps_256_heterogeneous_tenants_bit_exact():
    """The acceptance bar: ONE bucket, ONE jitted mega-tick, >= 256
    heterogeneous tenants (distinct prices/thresholds/demands), every
    decision bit-exact vs 256 standalone runtimes."""
    from repro.fleet.runtime import resolve_runtime_operands
    from repro.gateway import bucket_key_for

    N, T = 256, 6
    gw = FleetGateway(GatewayConfig(slots_per_bucket=N, cadence=T, obs=True))
    refs = {}
    cfg = RuntimeConfig()
    want_key, i, seed = None, 0, 0
    # Heterogeneous = every tenant has its own sampled prices, thresholds,
    # calendars and demand; sharing a bucket only requires the same padded
    # SHAPES (tier-table depth varies across sampled cloud pairs, so filter
    # scenarios to the first key seen).
    while i < N:
        seed += 1
        sc = build_fleet_scenario(2, horizon=24, seed=7000 + seed)
        key = bucket_key_for(resolve_runtime_operands(sc.fleet, cfg))
        if want_key is None:
            want_key = key
        if key != want_key:
            continue
        gw.join(f"t{i}", TenantSpec(
            spec=sc.fleet, demand=sc.demand, config=cfg, horizon=T,
        ))
        refs[f"t{i}"] = (FleetRuntime.from_config(sc.fleet, cfg), sc)
        i += 1
    assert gw.n_buckets == 1 and gw.n_active == N
    for t in range(T):
        outs = gw.tick()
        for name, (ref, sc) in refs.items():
            _assert_step_equal(outs[name], ref.step(sc.demand[:, t]), name)
    # One pool, two compiled variants (plain + drain) — nothing else.
    assert gw.compiles == 2
    assert gw.check() == []


# ---------------------------------------------------------------------------
# Churn: join/leave/rejoin inside a bucket shape never recompiles
# ---------------------------------------------------------------------------


def test_churn_within_bucket_is_zero_recompiles():
    """After a bucket's tick variants exist, any amount of membership churn
    — leaves, re-joins into freed slots, a grow via resize() into an
    already-compiled shape — leaves the compile counter frozen."""
    T = 40
    gw = FleetGateway(GatewayConfig(slots_per_bucket=3, cadence=8))
    specs = {}
    for i in range(3):
        sc = build_fleet_scenario(2, horizon=T, seed=i)
        specs[f"t{i}"] = TenantSpec(spec=sc.fleet, demand=sc.demand)
        gw.join(f"t{i}", specs[f"t{i}"])
    for _ in range(10):
        gw.tick()
    frozen = gw.compiles
    gw.leave("t1")
    sc = build_fleet_scenario(2, horizon=T, seed=77)
    gw.join("t3", TenantSpec(spec=sc.fleet, demand=sc.demand))  # freed slot
    for _ in range(10):
        gw.tick()
    assert gw.compiles == frozen
    # Rejoin of a departed name into the same shape: still frozen.
    sc2 = build_fleet_scenario(2, horizon=T, seed=78)
    gw.leave("t0")
    gw.join("t0", TenantSpec(spec=sc2.fleet, demand=sc2.demand))
    for _ in range(10):
        gw.tick()
    assert gw.compiles == frozen


def test_resize_moves_buckets_and_carries_billing():
    """Grow a tenant across capacity buckets: billing totals accumulate
    across the incarnations, the new shape gets a fresh stream, and the old
    slot frees for the queue."""
    T = 30
    gw = FleetGateway(GatewayConfig(slots_per_bucket=2, cadence=8))
    small = build_fleet_scenario(2, horizon=T, seed=5)
    gw.join("acme", TenantSpec(spec=small.fleet, demand=small.demand))
    for _ in range(12):
        gw.tick()
    bill_before = gw.billing("acme")
    assert bill_before["realized"] > 0
    big = build_fleet_scenario(5, horizon=T, seed=6)
    h = gw.resize("acme", TenantSpec(spec=big.fleet, demand=big.demand))
    assert h.status == "active"
    assert h.key.rows_cap == 8  # 5 links -> pow2 bucket, distinct from 2
    ref = FleetRuntime(big.fleet)
    for t in range(10):
        out = gw.tick()["acme"]
        _assert_step_equal(out, ref.step(big.demand[:, t]), f"resized@t{t}")
    bill_after = gw.billing("acme")
    assert bill_after["realized"] > bill_before["realized"]
    assert gw.check() == []


# ---------------------------------------------------------------------------
# Admission control: bounded queue, typed rejection, no device work
# ---------------------------------------------------------------------------


def test_backpressure_bounded_queue_and_typed_rejection():
    """A join burst beyond pool headroom queues FIFO up to the limit, then
    rejects with AdmissionError(reason='queue_full') — and the rejection
    path never compiles anything. Departures drain the queue in order."""
    T = 24
    gw = FleetGateway(GatewayConfig(
        slots_per_bucket=2, max_buckets=1, queue_limit=2, cadence=8,
    ))
    base = build_fleet_scenario(2, horizon=T, seed=0)
    # Same shapes (one capacity bucket), distinct per-tenant demand streams.
    mk = lambda seed: TenantSpec(
        spec=base.fleet, demand=base.demand * (1.0 + 0.1 * seed),
    )
    assert gw.join("a", mk(0)).status == "active"
    assert gw.join("b", mk(1)).status == "active"
    assert gw.join("c", mk(2)).status == "queued"
    assert gw.join("d", mk(3)).status == "queued"
    compiles_before = gw.compiles
    with pytest.raises(AdmissionError) as ei:
        gw.join("e", mk(4))
    assert ei.value.reason == "queue_full"
    assert gw.compiles == compiles_before  # rejection touched no device pool
    assert gw.n_queued == 2
    gw.tick()
    gw.leave("a")
    assert gw.handle("c").status == "active"  # FIFO head took the slot
    assert gw.handle("d").status == "queued"
    gw.leave("b")
    assert gw.handle("d").status == "active"
    assert gw.n_queued == 0
    # Queued tenants start their OWN hour 0 on activation.
    ref = FleetRuntime(mk(2).spec)
    sc2 = mk(2)
    out = gw.tick()["c"]
    _assert_step_equal(out, ref.step(sc2.demand[:, 0]), "late-start")


def test_too_large_tenant_rejected_typed():
    gw = FleetGateway(GatewayConfig(max_rows=4))
    sc = build_fleet_scenario(6, horizon=24, seed=0)  # pads to 8 > 4
    with pytest.raises(AdmissionError) as ei:
        gw.join("huge", TenantSpec(spec=sc.fleet, demand=sc.demand))
    assert ei.value.reason == "too_large"
    assert gw.n_buckets == 0 and gw.compiles == 0


# ---------------------------------------------------------------------------
# Tenant-axis metrics: SLO breaches typed + attributed; honest runs silent
# ---------------------------------------------------------------------------


def test_tenant_slo_breach_is_typed_and_attributed():
    T = 24
    gw = FleetGateway(GatewayConfig(slots_per_bucket=2, cadence=8))
    sc = build_fleet_scenario(2, horizon=T, seed=3)
    gw.join("cheap", TenantSpec(
        spec=sc.fleet, demand=sc.demand,
        slo=TenantSLO(max_hourly_cost=1e-9),      # impossible budget
    ))
    sc2 = build_fleet_scenario(2, horizon=T, seed=4)
    gw.join("honest", TenantSpec(spec=sc2.fleet, demand=sc2.demand))
    for _ in range(T):
        gw.tick()
    violations = gw.check()
    assert violations, "impossible SLO must breach"
    assert all(v.monitor == "tenant_slo" for v in violations)
    assert {v.details["tenant"] for v in violations} == {"cheap"}
    # Billing reconciliation stayed clean for both (breaches are SLO-only).
    assert all("rate" in v.details for v in violations)
    # And the per-tenant drained windows carry real tick counts.
    assert sum(dm.ticks for dm in gw.metrics("cheap")) == T


def test_sync_groups_and_tenant_labels():
    """Per-tenant sync domains: routed-port group ids + the telemetry-safe
    tenant-tagged named_scope label."""
    from repro.dist.collectives import sync_domain_label
    from repro.dist.telemetry import _SYNCDOM_RE

    T = 24
    gw = FleetGateway(GatewayConfig(slots_per_bucket=2))
    sc = build_topology_scenario(
        4, n_facilities=2, ports_per_facility=2, horizon=T, seed=0
    )
    routing = optimize_routing(sc.topo, sc.demand)
    gw.join("acme", TenantSpec(
        spec=sc.topo, demand=sc.demand,
        config=RuntimeConfig(routing=routing),
    ))
    gw.tick()
    groups = gw.sync_groups("acme")
    assert groups == [int(g) for g in routing.primary]
    label = sync_domain_label(groups[0], "hierarchical", tenant="acme/eu?1")
    assert label == f"syncdom_t.acme-eu-1.g{groups[0]}_hierarchical"
    m = _SYNCDOM_RE.search(f"pad {label} pad")
    assert m is not None and m.group(0) == label
    # Untagged labels are unchanged (the pre-gateway format).
    assert sync_domain_label(3, "compressed") == "syncdom_g3_compressed"


@pytest.mark.parametrize("drain", [False, True], ids=["plain", "drain"])
def test_tick_many_fetches_two_arrays_a_bucket(drain):
    """A bucket's ``tick_many`` call brings home the runtime's two packed
    buffers (int8 decisions, one float64 vector), and the drained metrics
    ring as a third on a drain call: ``gateway.tick.d2h_arrays`` once a
    bucket a call."""
    import time

    from repro.obs import profile

    K = 6
    gw = FleetGateway(GatewayConfig(slots_per_bucket=4,
                                    cadence=K if drain else 2 * K))
    for i in range(2):
        sc = build_fleet_scenario(2, horizon=24, seed=i)
        gw.join(f"t{i}", TenantSpec(spec=sc.fleet, demand=sc.demand))
    profile.force_recording(True)
    try:
        t0 = time.perf_counter()
        gw.tick_many(K, collect=False)
        _, counts = profile.recorded(t0)
    finally:
        profile.force_recording(None)
    got = [n for name, _, n in counts if name == "gateway.tick.d2h_arrays"]
    assert got == [3 if drain else 2] * gw.n_buckets
