"""The paper's cost model — Eq. (1)/(2) of §V.

Given an hourly demand matrix ``d[t, p]`` (GB transferred by pair ``p`` during
hour ``t``) and a CCI-activation schedule ``x[t] ∈ {0, 1}``, the total cost is

    Σ_t [ x_t · ( L_CCI + Σ_p ( V_CCI + c_CCI · d_{p,t} ) )
        + (1-x_t) · Σ_p ( L_VPN + c_VPN(p,t) · d_{p,t} ) ]

where ``c_VPN(p, t)`` is the tiered per-GB rate given pair ``p``'s cumulative
volume since the start of the month (paper assumption: tiers accumulate
per-pair and reset every ``hours_per_month`` hours).

Tier-state convention (documented in DESIGN.md §6): the cumulative volume used
for the tier lookup is the *all-VPN counterfactual* volume — i.e. tiers advance
with total demand regardless of the schedule. This makes per-hour VPN cost an
exogenous series (exact when the schedule is all-VPN; the approximation is
conservative *against* VPN otherwise, since real mixed schedules would sit in
earlier, more expensive tiers) and is what both ToggleCCI's window costs and
the offline DP oracle consume.

Two implementations with identical semantics:

* numpy reference (clear, test oracle)   — :func:`hourly_cost_series`
* jax.numpy / jit-able                   — :func:`hourly_cost_series_jnp`
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

from .pricing import CostParams, TieredRate

# ---------------------------------------------------------------------------
# numpy reference
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class HourlyCosts:
    """Per-hour aggregate (summed over pairs) costs of each mode.

    ``vpn[t]``  — cost of serving hour ``t`` entirely over VPN
    ``cci[t]``  — cost of serving hour ``t`` entirely over CCI
    Components are split so benchmarks can reproduce the paper's
    leasing/transfer breakdowns (Figs. 7, 10b).
    """

    vpn_lease: np.ndarray
    vpn_transfer: np.ndarray
    cci_lease: np.ndarray
    cci_transfer: np.ndarray

    @property
    def vpn(self) -> np.ndarray:
        return self.vpn_lease + self.vpn_transfer

    @property
    def cci(self) -> np.ndarray:
        return self.cci_lease + self.cci_transfer


def tier_segment(lo, d, prev, bound, xp=jnp):
    """Volume of an hour's demand ``d`` that bills in the tier
    ``(prev, bound]`` when the month already stands at ``lo``:
    ``clip(min(lo + d, bound) - max(lo, prev), 0)``.

    Computed as ``min(d, bound - prev, bound - lo, d - (prev - lo))``, the
    same quantity without forming ``lo + d``: inside one tier it is ``d``
    itself, where ``(lo + d) - lo`` would lose the low bits of ``d`` to a
    month volume up to ~1e7 GB. That cancellation costs ~1e-12 relative in
    IEEE float64 and ~1e-10 in the TPU's float64, which is a pair of
    float32s (~48 significant bits). ``xp`` is ``jnp`` or ``np``; every
    tier fold of the repository (references, engines, kernels) prices
    through this one formula.
    """
    seg = xp.minimum(
        xp.minimum(d, bound - prev), xp.minimum(bound - lo, d - (prev - lo))
    )
    return xp.maximum(seg, 0.0)


def tiered_marginal_cost_np(
    tier: TieredRate, start_gb: np.ndarray, added_gb: np.ndarray
) -> np.ndarray:
    """Vectorized piecewise-linear marginal cost (numpy; broadcasts)."""
    bounds = np.array(
        [b if b != np.inf else 1e300 for b in tier.bounds_gb], dtype=np.float64
    )
    rates = np.array(tier.rates, dtype=np.float64)
    prev = np.concatenate([[0.0], bounds[:-1]])
    lo = np.asarray(start_gb, dtype=np.float64)[..., None]
    d = np.asarray(added_gb, dtype=np.float64)[..., None]
    seg = tier_segment(lo, d, prev, bounds, np)
    return np.sum(seg * rates, axis=-1)


def monthly_cumsum_np(demand: np.ndarray, hours_per_month: int) -> np.ndarray:
    """Numpy twin of :func:`monthly_cumsum`, along the LAST axis: the same
    adds in the same order (a fresh sequential sum from zero each month)."""
    d = np.asarray(demand, dtype=np.float64)
    out = np.zeros_like(d)
    for s in range(0, d.shape[-1], hours_per_month):
        e = min(s + hours_per_month, d.shape[-1])
        out[..., s + 1:e] = np.cumsum(d[..., s:e - 1], axis=-1)
    return out


def _as_2d(demand: np.ndarray) -> np.ndarray:
    demand = np.asarray(demand, dtype=np.float64)
    if demand.ndim == 1:
        demand = demand[:, None]
    assert demand.ndim == 2, "demand must be (T,) or (T, P)"
    assert (demand >= 0).all(), "negative demand"
    return demand


def hourly_cost_series(params: CostParams, demand: np.ndarray) -> HourlyCosts:
    """Compute the per-hour VPN and CCI cost series (numpy reference)."""
    d = _as_2d(demand)
    T, P = d.shape

    # Cumulative monthly volume per pair (all-VPN counterfactual), exclusive
    # of the current hour: tier position at the *start* of hour t.
    month_cum = monthly_cumsum_np(d.T, params.hours_per_month).T

    vpn_transfer = tiered_marginal_cost_np(params.vpn_tier, month_cum, d).sum(axis=1)
    vpn_lease = np.full(T, P * params.L_vpn)
    cci_lease = np.full(T, params.L_cci + P * params.V_cci)
    cci_transfer = params.c_cci * d.sum(axis=1)
    return HourlyCosts(vpn_lease, vpn_transfer, cci_lease, cci_transfer)


def evaluate_schedule(
    params: CostParams,
    demand: np.ndarray,
    x: np.ndarray,
    costs: Optional[HourlyCosts] = None,
) -> float:
    """Total cost of schedule ``x`` (Eq. 2). ``x[t]=1`` means CCI serves hour t."""
    costs = costs if costs is not None else hourly_cost_series(params, demand)
    x = np.asarray(x, dtype=np.float64)
    assert x.shape == costs.vpn.shape
    assert np.isin(x, (0.0, 1.0)).all()
    return float(np.sum(x * costs.cci + (1.0 - x) * costs.vpn))


def cost_breakdown(
    params: CostParams, demand: np.ndarray, x: np.ndarray
) -> dict:
    """Leasing/transfer decomposition of a schedule's cost (paper Figs. 7, 10b)."""
    c = hourly_cost_series(params, demand)
    x = np.asarray(x, dtype=np.float64)
    return {
        "lease": float(np.sum(x * c.cci_lease + (1 - x) * c.vpn_lease)),
        "transfer": float(np.sum(x * c.cci_transfer + (1 - x) * c.vpn_transfer)),
        "total": float(np.sum(x * c.cci + (1 - x) * c.vpn)),
    }


# ---------------------------------------------------------------------------
# jax implementation (vectorized / vmap-able over scenario batches)
# ---------------------------------------------------------------------------


def tiered_marginal_cost_jnp(
    tier: TieredRate, start_gb: jax.Array, added_gb: jax.Array
) -> jax.Array:
    """Vectorized piecewise-linear marginal cost. Broadcasts over inputs."""
    bounds = jnp.asarray(
        [b if b != np.inf else 1e30 for b in tier.bounds_gb], dtype=jnp.float32
    )
    rates = jnp.asarray(tier.rates, dtype=jnp.float32)
    prev = jnp.concatenate([jnp.zeros(1, dtype=bounds.dtype), bounds[:-1]])
    seg = tier_segment(start_gb[..., None], added_gb[..., None], prev, bounds)
    return jnp.sum(seg * rates, axis=-1)


def tiered_marginal_cost_tables(
    start_gb: jax.Array,   # (..., T)
    added_gb: jax.Array,   # (..., T)
    bounds: jax.Array,     # (..., K) — inf already mapped to a large finite cap
    rates: jax.Array,      # (..., K)
) -> jax.Array:
    """Piecewise-linear marginal cost with the tier tables as *array operands*.

    Unlike :func:`tiered_marginal_cost_jnp` (which closes over one static
    :class:`TieredRate`), this broadcasts ``(..., T)`` volumes against
    ``(..., K)`` tables — the batched path the fleet engine uses to price N
    heterogeneous links in one XLA op. Pad ragged tables with
    ``(bound=1e30, rate=0)`` rows: duplicate bounds make zero-width
    segments, so padding never contributes cost.

    The tier axis is unrolled as a left fold from zero (K is small and
    static) rather than broadcast to a ``(..., T, K)`` temp and reduced:
    the fold keeps every intermediate at the ``(..., T)`` operand shape —
    XLA:CPU fuses the whole chain where it leaves the 3-D broadcast temps
    materialized — and fixes the summation ASSOCIATION, so every caller
    (offline planners, the per-tick runtime, the chunked ``step_many``
    planes, which inline this same op chain in their own orientation)
    produces bit-identical f64 costs.
    """
    acc = jnp.result_type(start_gb.dtype, added_gb.dtype, jnp.result_type(float))
    bounds = bounds.astype(acc)
    rates = rates.astype(acc)
    lo = start_gb.astype(acc)
    d = added_gb.astype(acc)
    out = jnp.zeros((), acc)
    prev = jnp.zeros(bounds.shape[:-1] + (1,), acc)
    for j in range(bounds.shape[-1]):
        b_j = bounds[..., j:j + 1]                       # (..., 1) over T
        seg = tier_segment(lo, d, prev, b_j)
        # The where() keeps the product from feeding the fold add directly:
        # XLA:CPU emits mul-feeding-add as llvm.fmuladd, and LLVM then
        # contracts it to a real FMA in some fusion contexts and not others
        # — the last bit of the cost would differ between compiled variants
        # of this same formula (an optimization_barrier does NOT help; the
        # CPU backend expands it away before fusion). seg is clipped ≥ 0
        # and rates are finite, so the select is value-identical to the
        # plain product.
        out = out + jnp.where(seg > 0, seg * rates[..., j:j + 1], 0.0)
        prev = b_j
    return out


def prefix_sum(x: jax.Array) -> jax.Array:
    """Inclusive prefix sum along the LAST axis, added strictly left to right.

    ``jnp.cumsum`` lowers to a reduce-window that XLA may rewrite into a
    parallel prefix, which reassociates the adds; for TPU its float64 form
    also compiles for minutes at fleet sizes (2048 x 8760: ~4 min, against
    under a second for this scan). A ``lax.scan`` over the axis adds in
    exactly the order ``np.cumsum`` and the streaming runtime's carried
    accumulators do, on every backend.
    """
    xt = jnp.moveaxis(x, -1, 0)

    def body(c, v):
        c = c + v
        return c, c

    _, ys = jax.lax.scan(body, jnp.zeros(xt.shape[1:], x.dtype), xt)
    return jnp.moveaxis(ys, 0, -1)


def lag_rows(x: jax.Array, h, axis: int = 0) -> jax.Array:
    """``x`` read at ``max(0, t - h)`` along ``axis``, one lag ``h`` per row.

    ``h`` holds one lag for each position of the other axes (a scalar under
    ``vmap``, or ``(M,)`` for an hour-major ``(K, M)`` block). No gather:
    the TPU's gather emitter moves one element at a time (~86 M elements/s
    on v5e, four of them were 85% of the offline plan's device time), so
    the lag is composed from ``n.bit_length()`` static shifts by ``2**b``,
    each kept where bit ``b`` of ``h`` is set. A shift by ``s`` fills with
    the row's first element, which every shift keeps in place, so the
    composition of shifts ``a`` and ``b`` reads ``max(0, t - a - b)``.
    Only selects, no arithmetic: the values are ``x``'s own bits.
    """
    n = x.shape[axis]
    xt = jnp.moveaxis(x, axis, 0)
    h = jnp.clip(jnp.asarray(h), 0, n)
    first = xt[:1]
    for b in range(n.bit_length()):
        s = 1 << b
        fill = jnp.broadcast_to(first, (min(s, n),) + xt.shape[1:])
        lagged = jnp.concatenate([fill, xt[:n - s]]) if s < n else fill
        xt = jnp.where((h >> b) & 1 == 1, lagged, xt)
    return jnp.moveaxis(xt, 0, axis)


def monthly_cumsum(demand: jax.Array, hours_per_month: int) -> jax.Array:
    """Exclusive within-month cumulative volume along the LAST axis.

    ``demand``: (..., T). Returns the all-VPN-counterfactual tier position at
    the start of each hour (the tier-state convention above), vectorized over
    any leading batch axes. Summed hour by hour from zero at every month
    start — not as a difference of year-long prefixes — so its rounding
    stays at the month's magnitude (on a TPU, whose float64 carries ~48
    bits, a year-long prefix moved tier boundaries by ~1e-8 $/hour); the
    streaming runtime's carried month volume performs the same adds.
    """
    d = jnp.moveaxis(demand, -1, 0)

    def body(c, tv):
        t, v = tv
        c = jnp.where(t % hours_per_month == 0, jnp.zeros_like(c), c)
        return c + v, c

    _, out = jax.lax.scan(
        body, jnp.zeros(d.shape[1:], d.dtype), (jnp.arange(d.shape[0]), d)
    )
    return jnp.moveaxis(out, 0, -1)


def hourly_cost_series_jnp(params: CostParams, demand: jax.Array):
    """jnp version of :func:`hourly_cost_series`. demand: (T, P) -> dict of (T,)."""
    d = demand.astype(jnp.float32)
    if d.ndim == 1:
        d = d[:, None]
    T, P = d.shape
    month_cum = monthly_cumsum(d.T, params.hours_per_month).T
    vpn_transfer = jnp.sum(
        tiered_marginal_cost_jnp(params.vpn_tier, month_cum, d), axis=1
    )
    vpn_lease = jnp.full((T,), P * params.L_vpn, dtype=d.dtype)
    cci_lease = jnp.full((T,), params.L_cci + P * params.V_cci, dtype=d.dtype)
    cci_transfer = params.c_cci * jnp.sum(d, axis=1)
    return {
        "vpn_lease": vpn_lease,
        "vpn_transfer": vpn_transfer,
        "cci_lease": cci_lease,
        "cci_transfer": cci_transfer,
        "vpn": vpn_lease + vpn_transfer,
        "cci": cci_lease + cci_transfer,
    }
