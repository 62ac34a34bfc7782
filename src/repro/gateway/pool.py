"""Tenant packing: capacity buckets, inert padding, pooled device operands.

A gateway pool is a stack of per-tenant runtime operands with one leading
SLOT axis, shaped so ONE jitted mega-tick (``jax.vmap`` of the standalone
tick over slots) can serve every tenant of the bucket — whatever each
tenant's real size — without ever recompiling for membership churn. Two
mechanisms make that work:

**Capacity bucketing.** Tenants are grouped by a :class:`BucketKey`: the
padded row/pair capacities (next power of two), the exact tier depth ``K``,
the policy treedef (kind + static knobs), and the forecast-replay column
capacity. Everything in the key is a COMPILED-SHAPE fact; everything not in
the key (thresholds, windows, prices, routings, calendars, demand) is a
traced operand or host state, so any two tenants sharing a key share one
compiled program and one pool. ``K`` is deliberately exact, not quantized:
:func:`repro.core.costmodel.tiered_marginal_cost_tables` reduces over the
tier axis, and padding it cross-tenant would change the reduction pairing —
the one place padding could break the bit-exactness contract.

**Inert padding.** Padded rows are *provably frozen* FSMs: ``θ₁ = θ₂ = 1``
with zero window costs makes the reactive/hysteresis gates compare
``0 < 0`` / ``0 > 0`` (both false), and a zero ``cost_coef`` with zero
margin makes the forecast gates compare ``exp(0)`` against itself — so
padded FSMs stay OFF forever, contribute zero to every cost/volume
reduction, and never pollute a real tenant's metrics counters (the one
exception, the realized-cost histogram's zero-bin, is corrected host-side
at drain — see :mod:`repro.gateway.gateway`). Padded routing LEGS point at
an inert (pad pair, pad port) slot with zero weights, and padded PAIRS
carry no legs at all, so ``segment_sum`` aggregation onto real ports sees
exactly the standalone leg list in the standalone (leg) order — the
property PR 5 established bitwise, generalized to weighted multi-hop legs.

Forecast ``pred_demand`` columns are padded by EDGE-REPLICATING the last
column, matching XLA's clamping ``dynamic_index_in_dim`` semantics in the
standalone runtime, so an over-long replay index reads the same value in
both worlds.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.togglecci import ToggleParams
from repro.fleet.policy import (
    ForecastGatedPolicy,
    HysteresisPolicy,
    ReactivePolicy,
)
from repro.fleet.routing import RoutingOperand, RoutingPlan, padded_operand_np
from repro.fleet.runtime import ResolvedRuntime
from repro.fleet.spec import PAD_BOUND, FleetArrays
from repro.fleet.topology import TopologyArrays


def ceil_pow2(n: int) -> int:
    """The smallest power of two ≥ n (≥ 1)."""
    n = int(n)
    assert n >= 1, n
    return 1 << (n - 1).bit_length()


# Minimum pooled prefix-ring depth (hours). A ring only costs host memory
# (rows_cap x hbuf float64 per slot), so quantizing every tenant up to one
# generous depth trades kilobytes for pool consolidation.
HBUF_FLOOR = 512


class BucketKey(NamedTuple):
    """Everything that determines a pool's compiled shapes + host layout.

    Two tenants share a bucket iff their keys are equal. ``policy_treedef``
    carries the policy kind AND its static aux (``renew_in_chunks``), so
    mixed-kind tenants never share a vmapped policy stack. ``hbuf_cap``
    (the prefix-ring depth, ``max(pow2(max(h)+1), HBUF_FLOOR)``) shapes
    only HOST state — it is excluded from :meth:`compile_key`, so buckets
    differing only in window depth still share one compiled mega-tick, and
    the floor keeps ordinary window-length spread (the paper's h ≈ 72–336h
    regime fits under one 512-deep ring) from fragmenting pools at all.
    """

    topology: bool
    rows_cap: int        # decision rows (ports/links), padded
    pairs_cap: int       # demand rows (pairs; == rows_cap in fleet mode)
    legs_cap: int        # padded routing-leg bound (0 in fleet mode) — a
                         # 1-hop tenant's tight bound pow2-pads to exactly
                         # pairs_cap, so plain tenants never fragment; a
                         # relay/multicast tenant with more legs buckets by
                         # its own leg capacity
    n_tiers: int         # EXACT tier depth K (never padded cross-tenant)
    policy_treedef: object
    pred_source: Optional[str]   # None | "replay" (live is not poolable)
    pred_cap: int        # replay pred_demand column capacity (0 when unused)
    hbuf_cap: int        # host prefix-ring depth (pow2)

    def compile_key(
        self, *, n_slots: int, obs: bool, drain: bool,
        chunk: Optional[int] = None,
    ) -> tuple:
        # ``chunk`` is the static K of a chunked mega-tick (tick_many);
        # ``None`` is the per-tick variant — distinct compiled programs.
        return (
            self.topology, self.rows_cap, self.pairs_cap, self.legs_cap,
            self.n_tiers, self.policy_treedef, self.pred_source,
            self.pred_cap, n_slots, obs, drain, chunk,
        )


@dataclasses.dataclass(frozen=True)
class PackedTenant:
    """One tenant's operands padded to bucket capacity, ready for a slot."""

    key: BucketKey
    arrays: object                    # padded FleetArrays / TopologyArrays
    policy: object                    # padded policy pytree (rows_cap leaves)
    routing: Optional[RoutingOperand] # numpy-field leg operand padded to
                                      # (legs_cap, pairs_cap), topology only
    h_np: np.ndarray                  # (rows_cap,) int64 padded window lengths
    hours_per_month: int
    n_rows: int                       # real decision rows
    n_pairs: int                      # real demand rows


def _pad_rows(x, cap: int, value) -> jnp.ndarray:
    """Pad the leading axis to ``cap`` with a constant fill."""
    x = jnp.asarray(x)
    n = x.shape[0]
    assert n <= cap, (n, cap)
    if n == cap:
        return x
    fill = jnp.full((cap - n,) + x.shape[1:], value, x.dtype)
    return jnp.concatenate([x, fill], axis=0)


def _pad_toggle(tp: ToggleParams, cap: int) -> ToggleParams:
    """Inert FSM rows: θ₁ = θ₂ = 1 over zero window costs never fires."""
    return ToggleParams(
        theta1=_pad_rows(tp.theta1, cap, 1.0),
        theta2=_pad_rows(tp.theta2, cap, 1.0),
        h=_pad_rows(tp.h, cap, 1),
        D=_pad_rows(tp.D, cap, 0),
        T_cci=_pad_rows(tp.T_cci, cap, 1),
    )


def _pad_pred(pred: jnp.ndarray, rows_cap: int, pred_cap: int) -> jnp.ndarray:
    """(rows, T) → (rows_cap, pred_cap): zero rows, edge-replicated columns
    (matching ``dynamic_index_in_dim``'s clamp in the standalone replay)."""
    pred = np.asarray(pred)
    t = pred.shape[1]
    assert 1 <= t <= pred_cap, (t, pred_cap)
    out = np.pad(pred, ((0, 0), (0, pred_cap - t)), mode="edge")
    return _pad_rows(jnp.asarray(out, jnp.asarray(pred).dtype), rows_cap, 0.0)


def _pad_policy(policy, rows_cap: int, pred_cap: int):
    """Pad a policy pytree's per-row leaves to bucket capacity with values
    that keep the padded FSMs provably inert (module docstring)."""
    if isinstance(policy, ReactivePolicy):
        return dataclasses.replace(policy, toggle=_pad_toggle(policy.toggle, rows_cap))
    if isinstance(policy, HysteresisPolicy):
        return dataclasses.replace(
            policy,
            toggle=_pad_toggle(policy.toggle, rows_cap),
            up_hold=_pad_rows(policy.up_hold, rows_cap, 1),
            down_hold=_pad_rows(policy.down_hold, rows_cap, 1),
        )
    if isinstance(policy, ForecastGatedPolicy):
        assert policy.cost_coef is not None
        return dataclasses.replace(
            policy,
            toggle=_pad_toggle(policy.toggle, rows_cap),
            margin=_pad_rows(policy.margin, rows_cap, 0.0),
            pred_demand=_pad_pred(policy.pred_demand, rows_cap, pred_cap),
            cost_coef=_pad_rows(policy.cost_coef, rows_cap, 0.0),
        )
    raise TypeError(
        f"cannot pool policy type {type(policy).__name__}: the gateway "
        "pads reactive/hysteresis/forecast policies only"
    )


def bucket_key_for(resolved: ResolvedRuntime) -> BucketKey:
    """Derive the capacity bucket of one resolved tenant runtime."""
    assert resolved.pred_source != "live", (
        "live SSM forecasting is not poolable (per-tenant carried forecaster "
        "state defeats the shared mega-tick); stream forecast tenants in "
        "replay mode, or standalone"
    )
    arrays = resolved.arrays
    if resolved.topology:
        m, p = arrays.n_ports, arrays.n_pairs
        k = arrays.tier_bounds.shape[1]
    else:
        m = p = arrays.n_links
        k = arrays.tier_bounds.shape[1]
    rows_cap = ceil_pow2(m)
    pairs_cap = ceil_pow2(p) if resolved.topology else rows_cap
    if resolved.topology and pairs_cap > p and rows_cap == m:
        # Padded pairs need a padded port to route to (a real port's
        # n_pairs count must not see them) — reserve one by doubling.
        rows_cap *= 2
    legs_cap = 0
    if resolved.topology:
        # The stacked operand's padded leg bound is the tenant's own swap
        # budget; every row carries >= 1 leg so the pow2 bound is at least
        # pairs_cap for plain 1-hop tenants (no fragmentation).
        legs_cap = ceil_pow2(int(arrays.routing.leg_pair.shape[-1]))
    pred_cap = 0
    if resolved.pred_source == "replay":
        pred_cap = ceil_pow2(resolved.policy.pred_demand.shape[1])
    hbuf = int(np.max(np.asarray(resolved.arrays.toggle.h))) + 1
    return BucketKey(
        topology=resolved.topology,
        rows_cap=rows_cap,
        pairs_cap=pairs_cap,
        legs_cap=legs_cap,
        n_tiers=int(k),
        policy_treedef=jax.tree.structure(resolved.policy),
        pred_source=resolved.pred_source,
        pred_cap=pred_cap,
        hbuf_cap=max(ceil_pow2(hbuf), HBUF_FLOOR),
    )


def pack_tenant(resolved: ResolvedRuntime, key: Optional[BucketKey] = None) -> PackedTenant:
    """Pad one resolved tenant to its bucket capacities. Runs under
    ``jax.enable_x64`` itself — the fills must concatenate at the operands'
    own float64, exactly as runtime construction does."""
    if key is None:
        key = bucket_key_for(resolved)
    with jax.enable_x64():
        return _pack_tenant(resolved, key)


def _pack_tenant(resolved: ResolvedRuntime, key: BucketKey) -> PackedTenant:
    arrays = resolved.arrays
    mc, pc = key.rows_cap, key.pairs_cap
    if resolved.topology:
        m, p = arrays.n_ports, arrays.n_pairs
        plan = resolved.routing_plan
        if plan is None:
            plan = RoutingPlan.from_operand(
                arrays.routing, m, provenance="from_operand:gateway"
            )
        # Padding legs point at the pool's inert (pad_pair, pad_port) slot
        # with zero weights (exact +0.0 in every segment sum), and padded
        # PAIRS carry no legs at all — real ports aggregate exactly the
        # standalone leg list in the standalone (leg) order. The padded
        # primary still maps padded pairs to the pad port for the obs ring.
        pad_port = mc - 1
        assert p == pc or pad_port >= m, (m, p, key)
        routing = padded_operand_np(
            plan, n_legs=key.legs_cap, n_rows=pc,
            pad_pair=pc - 1, pad_port=pad_port,
        )
        padded = TopologyArrays(
            L_cci=_pad_rows(arrays.L_cci, mc, 0.0),
            V_cci=_pad_rows(arrays.V_cci, mc, 0.0),
            c_cci=_pad_rows(arrays.c_cci, mc, 0.0),
            port_capacity=_pad_rows(arrays.port_capacity, mc, PAD_BOUND),
            toggle=_pad_toggle(arrays.toggle, mc),
            L_vpn=_pad_rows(arrays.L_vpn, pc, 0.0),
            tier_bounds=_pad_rows(arrays.tier_bounds, pc, PAD_BOUND),
            tier_rates=_pad_rows(arrays.tier_rates, pc, 0.0),
            pair_capacity=_pad_rows(arrays.pair_capacity, pc, PAD_BOUND),
            # The tick aggregates through the pooled leg operand, never
            # this field; pools keep a rank-preserving dummy rather than S
            # stacked operands (reroute() then swaps one slot's leg rows).
            routing=jnp.zeros((1, 1), arrays.routing.attach_w.dtype),
        )
    else:
        m = p = arrays.n_links
        routing = None
        padded = FleetArrays(
            L_cci=_pad_rows(arrays.L_cci, mc, 0.0),
            V_cci=_pad_rows(arrays.V_cci, mc, 0.0),
            c_cci=_pad_rows(arrays.c_cci, mc, 0.0),
            L_vpn=_pad_rows(arrays.L_vpn, mc, 0.0),
            tier_bounds=_pad_rows(arrays.tier_bounds, mc, PAD_BOUND),
            tier_rates=_pad_rows(arrays.tier_rates, mc, 0.0),
            toggle=_pad_toggle(arrays.toggle, mc),
            capacity=_pad_rows(arrays.capacity, mc, PAD_BOUND),
        )
    policy = _pad_policy(resolved.policy, mc, key.pred_cap)
    assert jax.tree.structure(policy) == key.policy_treedef, (
        "padding must not change the policy treedef"
    )
    return PackedTenant(
        key=key,
        arrays=padded,
        policy=policy,
        routing=routing,
        h_np=np.asarray(np.concatenate([
            np.asarray(arrays.toggle.h, np.int64),
            np.ones(mc - m, np.int64),
        ])),
        hours_per_month=resolved.hours_per_month,
        n_rows=m,
        n_pairs=p,
    )


def stack_slots(packed_list):
    """Stack per-slot pytrees (arrays/policies/fsm carries) along a new
    leading slot axis — the pool's device layout."""
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *packed_list)


def set_slot(pool, slot: int, value):
    """Write one slot of a pooled pytree (pure ``.at[slot].set`` per leaf —
    an operand update, never a shape change, so never a recompile)."""
    return jax.tree.map(lambda p, v: p.at[slot].set(v), pool, value)
