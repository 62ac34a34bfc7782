"""Routed execution core: ONE batched planning pipeline for fleets and
topologies.

Every planner runs the same three stages, entirely inside ONE jit call:

  pair stage   demand (P, T) --clip at pair/link capacity--> d
               d --monthly_cumsum + batched tiered tables--> per-pair VPN costs
  route stage  pairs fold onto decision rows through the one-hot routing
               matrix (a traceable operand — re-routing reuses the compiled
               program); identity routing (``plan_fleet``) skips the matmul
               but prices through the SAME formula, so the per-link planner
               is literally the identity-routing special case of the
               shared-port planner (bit-exact, property-tested)
  policy stage costs --vmap(policy_scan) over the row axis--> x, state, totals

The toggle decision is a pluggable *policy operand* (:mod:`repro.fleet.policy`):
the paper's reactive ToggleCCI by default, or SSM-forecast-gated /
hysteresis variants — all through the same compiled scan, the policy pytree
vmapped alongside the cost rows.

:func:`routed_cost_series` is the single pricing+aggregation entry point —
the offline planners, the forecast-policy factories and the streaming
runtime (:mod:`repro.fleet.runtime`) all consume it, so their cost series
cannot drift apart (the streaming-vs-offline bit-exactness contract).
:func:`replay_plan_topology` replays a PIECEWISE-CONSTANT routing schedule
offline — the oracle for :meth:`repro.fleet.runtime.FleetRuntime.reroute`'s
mid-stream routing swaps.

Precision: everything runs under ``jax.enable_x64`` so prefix
sums over year-long horizons accumulate in float64 — the batched decision
sequences ``x`` then match the float64 numpy references
(:func:`repro.core.togglecci.run_togglecci`) bit-for-bit
(property-tested in ``tests/test_fleet.py`` / ``tests/test_topology.py``).
On a TPU, float64 is a pair of float32s (~48 bits), so sums differ from
numpy's in the low bits; decisions still agree unless a window sum lands
within ~1e-14 of a threshold (checked at 2048 links x 8760 h by
``chip_smoke.py``).
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Optional, Sequence, Tuple, Union

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.costmodel import (
    monthly_cumsum,
    monthly_cumsum_np,
    tiered_marginal_cost_np,
    tiered_marginal_cost_tables,
)
from repro.core.togglecci import run_togglecci
from repro.kernels.tiered_cost import tiered_cost_batched
from repro.obs.profile import span

from .policy import make_policy, policy_scan
from .routing import RoutingOperand, RoutingPlan, as_routing_plan
from .spec import FleetArrays, FleetSpec
from .topology import TopologyArrays, TopologySpec, optimize_routing

_JIT_CACHE: dict = {}


def _run_policies(policy, demand_rows, vpn, cci):
    """THE single FSM call site: one :func:`policy_scan` vmapped over the
    link/port axis, the policy itself a mapped operand (every leaf carries
    the leading axis — per-row thresholds, windows, forecasts, flags)."""
    return jax.vmap(
        lambda p, dd, v, c: policy_scan(p, v, c, demand=dd)
    )(policy, demand_rows, vpn, cci)


def _plan_outputs(policy, d, vpn, cci) -> Dict[str, jax.Array]:
    """Shared tail of both planners: run the policies, add the static
    comparators. ALWAYS-CCI still pays the provisioning delay: the first D
    hours ride VPN (paper Fig. 11's "misses the first D")."""
    out = _run_policies(policy, d, vpn, cci)
    T = d.shape[1]
    with jax.named_scope("comparators"):
        cci_live = jnp.arange(T)[None, :] >= policy.toggle.D[:, None]
        static_cci = jnp.sum(jnp.where(cci_live, cci, vpn), axis=1)
        static_vpn = jnp.sum(vpn, axis=1)
    return {
        "x": out["x"],                     # (rows, T) 0/1 decision sequences
        "state": out["state"],             # (rows, T) FSM states
        "toggle_cost": out["total_cost"],  # (rows,)
        "static_vpn": static_vpn,
        "static_cci": static_cci,
        "vpn_hourly": vpn,
        "cci_hourly": cci,
    }


class RoutedSeries(NamedTuple):
    """The unified pricing+aggregation output both planners toggle on.

    ``pair_demand`` is per pair/link (P rows); everything else is per
    DECISION row (M ports in topology mode, M == P links in fleet mode —
    where ``row_demand is pair_demand`` and ``n_pairs`` is all-ones).
    """

    pair_demand: jax.Array  # (P, T) access/capacity-clipped demand
    row_demand: jax.Array   # (M, T) demand the decision rows see
    vpn: jax.Array          # (M, T) hourly VPN counterfactual
    cci: jax.Array          # (M, T) hourly CCI counterfactual
    n_pairs: jax.Array      # (M,) pairs attached per row


def _pair_stage(arrays, demand: jax.Array, *, hours_per_month: int,
                use_pallas: bool = False):
    """Per-pair clip + tiered VPN pricing — identical for both routings
    (a fleet's link IS a pair riding a private port)."""
    f = jnp.result_type(float)
    topology = isinstance(arrays, TopologyArrays)
    cap = arrays.pair_capacity if topology else arrays.capacity
    with jax.named_scope("pricing"):
        d = jnp.minimum(demand.astype(f), cap[:, None])               # (P, T)
    with jax.named_scope("calendar_scan"):
        month_cum = monthly_cumsum(d, hours_per_month)
    with jax.named_scope("pricing"):
        if use_pallas:
            # f32 kernel path (the kernel pads to whole blocks itself);
            # interpreted off-TPU.
            f32 = lambda a: a.astype(jnp.float32)
            vpn_transfer = tiered_cost_batched(
                f32(month_cum), f32(d),
                f32(arrays.tier_bounds), f32(arrays.tier_rates),
                interpret=jax.default_backend() != "tpu",
            ).astype(f)
        else:
            vpn_transfer = tiered_marginal_cost_tables(
                month_cum, d, arrays.tier_bounds, arrays.tier_rates
            )
        return d, arrays.L_vpn[:, None] + vpn_transfer


def _route_stage(arrays, routing, d_pair, vpn_pair):
    """Fold pairs onto decision rows and price the CCI counterfactual.

    ``routing=None`` is the identity fast path (fleet mode): no aggregation,
    one pair per row. The CCI formula ``L + V·n + c·d`` with ``n = 1`` is
    bit-identical to the historical per-link ``(L + V) + c·d`` — the
    refactor's safety net, asserted by the identity-routing property test.
    VPN rides the public internet, so only the CCI volume sees the port's
    hard capacity (linksim F1); the lease is paid once, attachments per pair.

    Topology mode consumes the padded :class:`RoutingOperand` LEG list:
    each leg attaches one demand row to one port, so a multi-hop path is
    just several legs of the same row (demand and attachment count at every
    hop; the VPN counterfactual split 1/n_hops so the row's tunnel is
    counted once across its ports) and a forwarding tree is one leg per
    shared edge. Aggregation is a ``segment_sum`` over legs in ROW-major
    leg order, NOT a dense matmul with a one-hot matrix: XLA's blocked f64
    dot reductions are shape-dependent (an (M,P)@(P,T) matmul and the
    streaming tick's matvec disagree in the last ulp past ~64 ports), while
    scatter-add accumulates sequentially in update order — bit-identical
    between the full-horizon offline plan, per-tick streaming columns, and
    the python float64 reference loop (measured across shapes up to
    2048x2048), and O(E·T) instead of O(M·P·T) on top. A 1-hop unicast
    operand has one identity-ordered leg per row with unit weights, so the
    gather is the identity and every weight multiply is ``x * 1.0`` —
    bit-for-bit the historical pair-indexed scatter (property-tested).
    Padding legs carry zero weights: exact ``+0.0`` contributions on the
    pad port, so growing the leg bound never changes a cost bit.
    """
    if routing is None:
        d_row, vpn = d_pair, vpn_pair
        n_pairs = jnp.ones_like(arrays.L_cci)
    else:
        lp, lm = routing.leg_pair, routing.leg_port                   # (E,)
        M = arrays.L_cci.shape[0]
        seg = lambda v: jax.ops.segment_sum(v, lm, num_segments=M)
        vpn = seg(vpn_pair[lp] * routing.vpn_w[:, None])              # (M, T)
        d_row = jnp.minimum(
            seg(d_pair[lp] * routing.attach_w[:, None]),
            arrays.port_capacity[:, None],
        )
        n_pairs = seg(routing.attach_w)                               # (M,)
    cci = (
        arrays.L_cci[:, None]
        + (arrays.V_cci * n_pairs)[:, None]
        + arrays.c_cci[:, None] * d_row
    )
    return d_row, vpn, cci, n_pairs


def routed_cost_series(
    arrays: Union[FleetArrays, TopologyArrays],
    demand: jax.Array,
    *,
    hours_per_month: int,
    use_pallas: bool = False,
) -> RoutedSeries:
    """THE pricing stage: pair costs folded through the routing.

    One function for both array kinds — :class:`FleetArrays` take the
    identity fast path, :class:`TopologyArrays` aggregate through their
    ``routing`` operand. Shared by the offline plan builder, the
    forecast-policy factories and the streaming runtime, so every consumer
    toggles on EXACTLY the same series (the bit-exactness contract).
    """
    d_pair, vpn_pair = _pair_stage(
        arrays, demand, hours_per_month=hours_per_month, use_pallas=use_pallas
    )
    routing = arrays.routing if isinstance(arrays, TopologyArrays) else None
    with jax.named_scope("route"):
        d_row, vpn, cci, n_pairs = _route_stage(
            arrays, routing, d_pair, vpn_pair
        )
    return RoutedSeries(d_pair, d_row, vpn, cci, n_pairs)


def _build_plan_fn(hours_per_month: int, use_pallas: bool):
    """The ONE shared plan builder: pricing + routing + policy scan.

    One function serves both array kinds (jax.jit caches per input
    structure); ``plan_fleet``/``plan_topology`` are thin wrappers that
    resolve specs/routings/policies and call this.
    """

    def plan(arrays, demand: jax.Array, policy) -> Dict[str, jax.Array]:
        s = routed_cost_series(
            arrays, demand, hours_per_month=hours_per_month,
            use_pallas=use_pallas,
        )
        return {
            **_plan_outputs(policy, s.row_demand, s.vpn, s.cci),
            "pair_demand": s.pair_demand,      # (P, T) access-clipped
            "port_demand": s.row_demand,       # (M, T) row aggregate
            "n_pairs": s.n_pairs,              # (M,) attached pairs
        }

    return plan


def _run_plan(arrays, demand, policy, hours_per_month: int,
              use_pallas: bool = False) -> Dict[str, jax.Array]:
    key = (hours_per_month, use_pallas)
    fn = _JIT_CACHE.get(key)
    if fn is None:
        fn = _JIT_CACHE.setdefault(key, jax.jit(_build_plan_fn(*key)))
    return fn(arrays, jnp.asarray(demand, jnp.float64), policy)


def plan_fleet(
    fleet: Union[FleetSpec, FleetArrays],
    demand,
    *,
    policy=None,
    hours_per_month: int = 730,
    renew_in_chunks: bool = False,
    use_pallas: bool = False,
) -> Dict[str, jax.Array]:
    """Plan the whole portfolio in one jitted vmapped scan.

    The identity-routing wrapper of the shared routed core: one link = one
    pair on a private port, no aggregation matmul, same pricing formula —
    bit-for-bit the historical per-link planner (property-tested against
    :func:`plan_fleet_reference`).

    Args:
      fleet: a :class:`FleetSpec` (stacked here, under x64) or pre-stacked
        :class:`FleetArrays`.
      demand: (N, T) hourly GB per link (clipped at per-link capacity).
      policy: a :mod:`repro.fleet.policy` pytree with per-link leading axes
        (e.g. :func:`~repro.fleet.policy.forecast_fleet_policy`). ``None``
        resolves the spec's ``policy`` kind (default ``"reactive"`` — the
        paper's ToggleCCI, bit-for-bit the pre-policy-layer behavior).
      hours_per_month: billing calendar (taken from the spec when given).
    Returns:
      dict of per-link arrays — see ``_build_plan_fn`` (plus ``demand``, an
      alias of ``pair_demand`` kept for the per-link view).
    """
    # Host work only: the plan runs on after the return, and the caller's
    # fetch of its outputs lies outside the ``fleet.plan`` span.
    with span("fleet.plan"), jax.enable_x64():
        kind = "reactive"
        if isinstance(fleet, FleetSpec):
            hours_per_month = fleet.hours_per_month
            kind = fleet.policy
            arrays = fleet.stack(jnp.float64)
        else:
            arrays = fleet
        if policy is None:
            with span("fleet.plan.policy"):
                policy = make_policy(
                    kind, arrays.toggle, renew_in_chunks=renew_in_chunks
                )
        with span("fleet.plan.dispatch"):
            out = dict(_run_plan(
                arrays, demand, policy, hours_per_month, use_pallas
            ))
        out["demand"] = out["pair_demand"]
        return out


def plan_fleet_reference(
    fleet: FleetSpec, demand, *, renew_in_chunks: bool = False
) -> Dict[str, np.ndarray]:
    """Per-link pure-Python reference (test oracle / bench verification).

    Runs :func:`run_togglecci` link by link on capacity-clipped demand —
    semantically what the batched engine computes, minus the batching.
    """
    demand = np.asarray(demand, dtype=np.float64)
    xs, states, totals = [], [], []
    for i, link in enumerate(fleet.links):
        d = np.minimum(demand[i], link.capacity_gb_hr)
        res = run_togglecci(link.params, d, renew_in_chunks=renew_in_chunks)
        xs.append(res.x)
        states.append(res.state)
        totals.append(res.total_cost)
    return {
        "x": np.stack(xs),
        "state": np.stack(states),
        "toggle_cost": np.array(totals),
    }


# ---------------------------------------------------------------------------
# Topology-aware planning: routing + leasing over shared ports
# ---------------------------------------------------------------------------


def plan_topology(
    topo: Union[TopologySpec, TopologyArrays],
    demand,
    *,
    routing=None,
    policy=None,
    hours_per_month: int = 730,
    renew_in_chunks: bool = False,
) -> Dict[str, jax.Array]:
    """Co-optimized routing + leasing plan in one jitted program.

    Args:
      topo: a :class:`TopologySpec` (stacked here under x64) or pre-stacked
        :class:`TopologyArrays` (then ``routing`` is already baked in).
      demand: (P, T) hourly GB per region pair / multicast group.
      routing: a :class:`repro.fleet.routing.RoutingPlan` (legacy (P,)
        indices / (M, P) one-hot matrices still work through the
        ``DeprecationWarning`` shim). ``None`` with a spec runs
        :func:`repro.fleet.topology.optimize_routing` on the demand first —
        that is the "co-optimize" entry point.
      policy: per-PORT policy pytree (e.g.
        :func:`~repro.fleet.policy.forecast_topology_policy` on the routed
        arrays). ``None`` resolves the spec's ``policy`` kind (default
        reactive — bit-for-bit the pre-policy-layer behavior).
    Returns:
      dict of per-port arrays — see ``_build_plan_fn``.
    """
    with jax.enable_x64():
        kind = "reactive"
        if isinstance(topo, TopologySpec):
            hours_per_month = topo.hours_per_month
            kind = topo.policy
            if routing is None:
                routing = optimize_routing(topo, np.asarray(demand))
            routing = as_routing_plan(
                routing, n_ports=topo.n_ports, context="plan_topology"
            )
            arrays = topo.stack(routing, jnp.float64)
        else:
            assert routing is None, "pre-stacked arrays already carry a routing"
            arrays = topo
        if policy is None:
            policy = make_policy(
                kind, arrays.toggle, renew_in_chunks=renew_in_chunks
            )
        return _run_plan(arrays, demand, policy, hours_per_month)


def replay_plan_topology(
    arrays: TopologyArrays,
    demand,
    schedule: Sequence[Tuple[int, object]],
    *,
    policy=None,
    hours_per_month: int = 730,
    renew_in_chunks: bool = False,
) -> Dict[str, jax.Array]:
    """Offline replay of a PIECEWISE-CONSTANT routing schedule.

    ``schedule`` is ``[(start_hour, routing), ...]`` with the first start at
    hour 0 and strictly increasing starts; each ``routing`` is a
    :class:`RoutingPlan` or an already-padded :class:`RoutingOperand`
    (legacy (P,) indices / (M, P) one-hot matrices go through the
    deprecation shim). The port cost/demand series are
    the hour-by-hour stitch of each segment's ``routed_cost_series`` (the
    pair stage is routing-independent, so this is exactly what a streaming
    run that swaps its routing operand at those hours prices), and ONE
    shared policy scan runs over the stitched series — which makes this the
    bit-exactness oracle for :meth:`repro.fleet.runtime.FleetRuntime.reroute`:
    window sums near a swap mix old- and new-routing hours through the same
    float64 prefixes, and the FSM carry rides across the swap uninterrupted.

    A single-segment schedule ``[(0, routing)]`` reproduces
    :func:`plan_topology` on that routing bit-for-bit.
    """
    assert isinstance(arrays, TopologyArrays), (
        "replay_plan_topology replays shared-port routings; fleet mode has "
        "no routing to swap"
    )
    starts = [int(s) for s, _ in schedule]
    assert starts and starts[0] == 0, "schedule must start at hour 0"
    assert all(a < b for a, b in zip(starts, starts[1:])), (
        "schedule starts must be strictly increasing"
    )
    with jax.enable_x64():
        demand = jnp.asarray(demand, jnp.float64)
        T = demand.shape[1]
        M = arrays.n_ports
        if policy is None:
            policy = make_policy(
                "reactive", arrays.toggle, renew_in_chunks=renew_in_chunks
            )
        E = arrays.routing.leg_pair.shape[-1]
        bounds = starts + [T]
        segs = []
        for (a, b), (_, r) in zip(zip(bounds[:-1], bounds[1:]), schedule):
            if isinstance(r, RoutingOperand):
                op = r
            else:
                plan = as_routing_plan(
                    r, n_ports=M, context="replay_plan_topology"
                )
                # Pad to the arrays' leg bound when it fits, so every
                # segment reuses the one compiled program shape.
                if plan.total_hops <= E:
                    plan = plan.pad_to(E)
                op = plan.operand(jnp.float64)
            # Full-horizon plan per routing through the SAME jitted builder
            # (identical op fusion → identical floats), stitched per hour.
            seg = _run_plan(
                arrays._replace(routing=op), demand, policy, hours_per_month
            )
            segs.append(
                {k: seg[k][:, a:b]
                 for k in ("port_demand", "vpn_hourly", "cci_hourly")}
            )
        d_row = jnp.concatenate([s["port_demand"] for s in segs], axis=1)
        vpn = jnp.concatenate([s["vpn_hourly"] for s in segs], axis=1)
        cci = jnp.concatenate([s["cci_hourly"] for s in segs], axis=1)
        key = "replay_outputs"
        fn = _JIT_CACHE.get(key)
        if fn is None:
            fn = _JIT_CACHE.setdefault(key, jax.jit(_plan_outputs))
        return fn(policy, d_row, vpn, cci)


def offline_stream_oracle(
    arrays: Union[FleetArrays, TopologyArrays],
    demand,
    *,
    policy=None,
    schedule: Optional[Sequence[Tuple[int, object]]] = None,
    hours_per_month: int = 730,
    renew_in_chunks: bool = False,
) -> Dict[str, jax.Array]:
    """The offline twin of a streamed prefix — the divergence monitor's oracle.

    Dispatches on the arrays: :class:`TopologyArrays` replay through
    :func:`replay_plan_topology` with the recorded routing ``schedule``
    (defaulting to one segment of the arrays' own baked-in routing — so a
    stream that never rerouted replays against exactly ``plan_topology``);
    :class:`FleetArrays` run straight through :func:`plan_fleet`
    (``schedule`` must be ``None`` — a fleet has no routing to swap).
    Decisions must match a :class:`repro.fleet.runtime.FleetRuntime` stream
    of the same demand prefix bit for bit.
    """
    if isinstance(arrays, TopologyArrays):
        if schedule is None:
            schedule = [(0, arrays.routing)]
        return replay_plan_topology(
            arrays, demand, schedule,
            policy=policy, hours_per_month=hours_per_month,
            renew_in_chunks=renew_in_chunks,
        )
    assert schedule is None, "fleet mode has no routing schedule"
    return plan_fleet(
        arrays, demand,
        policy=policy, hours_per_month=hours_per_month,
        renew_in_chunks=renew_in_chunks,
    )


def topology_port_costs_reference(
    topo: TopologySpec, demand, routing
) -> Dict[str, np.ndarray]:
    """Float64 numpy port-aggregated cost series (reference / oracle input).

    Returns ``vpn``/``cci`` (M, T) hourly counterfactuals plus the clipped
    ``pair_demand``/``port_demand`` — the exact quantities the jitted
    aggregation stage computes. ``routing`` is anything
    :meth:`TopologySpec.plan` normalizes (plans, indices, path lists);
    multi-hop rows contribute demand and an attachment at EVERY hop and a
    ``1/n_hops`` share of their VPN counterfactual (tunnels are priced once
    per row, not per hop).
    """
    plan = topo.plan(routing)
    demand = np.asarray(demand, dtype=np.float64)
    P, T = demand.shape
    assert P == topo.n_pairs
    d = np.minimum(demand, topo.row_capacities()[:, None])
    vpn_pair = np.zeros((P, T))
    for i in range(P):
        cum = monthly_cumsum_np(d[i], topo.hours_per_month)
        vpn_pair[i] = topo.row_vpn_lease(i) + tiered_marginal_cost_np(
            topo.row_vpn_tier(i), cum, d[i]
        )

    M = topo.n_ports
    vpn = np.zeros((M, T))
    cci = np.zeros((M, T))
    d_port = np.zeros((M, T))
    for m, po in enumerate(topo.ports):
        idx = [i for i, path in enumerate(plan.paths) if m in path]
        agg = d[idx].sum(axis=0) if idx else np.zeros(T)
        d_port[m] = np.minimum(agg, po.capacity_gb_hr)
        if idx:
            w = np.array([1.0 / len(plan.paths[i]) for i in idx])
            vpn[m] = (vpn_pair[idx] * w[:, None]).sum(axis=0)
        cci[m] = po.L_cci + po.V_cci * len(idx) + po.c_cci * d_port[m]
    return {"vpn": vpn, "cci": cci, "pair_demand": d, "port_demand": d_port}


def plan_topology_reference(
    topo: TopologySpec,
    demand,
    routing,
    *,
    renew_in_chunks: bool = False,
    port_costs: Optional[Dict[str, np.ndarray]] = None,
) -> Dict[str, np.ndarray]:
    """Per-port pure-Python reference (test oracle for :func:`plan_topology`).

    Aggregates pair costs onto ports in float64 numpy and runs the paper's
    reference FSM (:func:`repro.core.togglecci.run_togglecci`) port by port
    on the aggregated series.

    Exactness contract: the FSM is bit-exact GIVEN identical (M, T) port
    cost series. The independent numpy aggregation here reproduces the
    engine's matmul aggregation only to float64 ulp (summation order over
    routed pairs differs), so decisions agree bit-for-bit unless a window
    sum straddles a θ threshold within ~1e-15 relative — pass
    ``port_costs={"vpn": ..., "cci": ...}`` (e.g. the engine's own hourly
    outputs) to pin the series and assert the FSM property exactly; see
    ``benchmarks/bench_topology.py`` for the two-part verification.

    Policy contract: this reference implements the REACTIVE policy (the
    paper's FSM). It is the bit-exactness oracle for ``plan_topology`` with
    its default/``ReactivePolicy`` operand — the property that proves the
    policy-layer refactor behavior-preserving (``tests/test_policy.py``);
    forecast-gated and hysteresis plans are measured against it, not by it.
    """
    from repro.core.costmodel import HourlyCosts

    series = (
        port_costs
        if port_costs is not None
        else topology_port_costs_reference(topo, demand, routing)
    )
    T = series["vpn"].shape[1]
    zeros = np.zeros(T)
    xs, states, totals = [], [], []
    for m, po in enumerate(topo.ports):
        costs = HourlyCosts(
            vpn_lease=zeros,
            vpn_transfer=series["vpn"][m],
            cci_lease=zeros,
            cci_transfer=series["cci"][m],
        )
        res = run_togglecci(
            po.toggle_cost_params(topo.hours_per_month),
            None,
            costs=costs,
            renew_in_chunks=renew_in_chunks,
        )
        xs.append(res.x)
        states.append(res.state)
        totals.append(res.total_cost)
    return {
        "x": np.stack(xs),
        "state": np.stack(states),
        "toggle_cost": np.array(totals),
        "vpn_hourly": series["vpn"],
        "cci_hourly": series["cci"],
    }


def topology_oracle(topo: TopologySpec, demand, routing) -> np.ndarray:
    """Offline-optimal (DP) cost per port for a FIXED routing — the report's
    leasing-oracle column (routing itself is not oracle-optimized)."""
    from repro.core.costmodel import HourlyCosts
    from repro.core.oracle import offline_optimal

    series = topology_port_costs_reference(topo, demand, routing)
    T = series["vpn"].shape[1]
    zeros = np.zeros(T)
    out = []
    for m, po in enumerate(topo.ports):
        costs = HourlyCosts(
            vpn_lease=zeros,
            vpn_transfer=series["vpn"][m],
            cci_lease=zeros,
            cci_transfer=series["cci"][m],
        )
        out.append(
            offline_optimal(
                po.toggle_cost_params(topo.hours_per_month), costs=costs
            ).total_cost
        )
    return np.array(out)


def fleet_oracle(fleet: FleetSpec, demand) -> np.ndarray:
    """Offline-optimal (DP) total cost per link — the report's OPT column.

    O(T · (D + T_cci)) per link in numpy; meant for report-time subsets, not
    the planning hot path.
    """
    from repro.core.oracle import offline_optimal

    demand = np.asarray(demand, dtype=np.float64)
    out = []
    for i, link in enumerate(fleet.links):
        d = np.minimum(demand[i], link.capacity_gb_hr)
        out.append(offline_optimal(link.params, d).total_cost)
    return np.array(out)
