"""Device-side metrics: a fixed-size ring pytree updated INSIDE the jitted tick.

The streaming runtime's hot path is one jitted dispatch per hour with one
packed H2D and one packed D2H transfer (~100µs each on CPU — see
:mod:`repro.fleet.runtime`). Naive metrics would double that: every counter
read is a transfer. Instead the :class:`MetricsRing` rides the device carry
like the FSM state does: :func:`update_ring` appends this tick's gauges in
slot ``ticks`` and bumps the transition counters as pure XLA ops on
intermediates the tick already computed (``x_t``/``state_t``/``vpn_t``/
``cci_t``/``d_pair``/``month_cum``), and at drain cadence
:func:`flatten_ring` hands the whole ring home as one flat vector, fetched
beside the tick's two packed buffers, and the step returns a zeroed ring
(:func:`reset_ring`) for the next window.

Bit-exactness contract: the ring only CONSUMES tick outputs, it never feeds
back into pricing or the FSM — decisions with observability on and off are
identical bit for bit (property-tested in ``tests/test_fleet_runtime.py``).

Host side, :meth:`DrainedMetrics.from_flat` unpacks the drained vector by
the shared :func:`ring_layout`; quantiles come from the in-jit histogram
(log-spaced edges, under/overflow clipped into the end bins).
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.togglecci import OFF, ON

# Flatten layout (order matters — host unpacking mirrors it).
SCALARS = ("ticks", "requests", "activations", "releases", "cci_gb")
GAUGES = (
    "lease_on",          # rows leased (serving CCI) this tick
    "realized_cost",     # fleet-wide realized $ this tick
    "vpn_cost",          # fleet-wide VPN counterfactual $
    "cci_cost",          # fleet-wide CCI counterfactual $
    "billed_gb",         # pair-level billed GB (all paths)
    "forecast_abs_err",  # sum |pred - realized row demand| (0 when no forecast)
    "pred_total",        # sum of per-row demand predictions
    "demand_total",      # sum of row-aggregated realized demand
)


class MetricsRing(NamedTuple):
    """Counters / per-tick gauge rings / histograms, all device-resident.

    Deliberately THREE leaves, batched by role: the tick is dispatch-bound
    on CPU (~60µs of XLA for the whole pricing+FSM step), so the ring must
    not re-introduce what the packed-transfer design removed. Every scalar
    accumulator — the 5 counters, the B histogram bins, the K tier buckets —
    lives in ONE ``small`` vector so the whole per-tick accumulation is a
    single vector add; the 8 per-tick gauges land as ONE
    dynamic-update-slice column write (vs eight separate slice updates); and
    both histograms are computed as cumulative compare-reductions
    (``sum(v > edges[e])`` per edge, differenced host-of... see
    :func:`update_ring`) rather than scatter-adds, which XLA CPU serializes
    per element (measured ~350µs/tick at 2048 rows, 5x the whole plain
    tick), or (rows × bins) one-hot materialization.

    ``prev_state`` is carried state, not a metric: the FSM state of the
    previous tick, kept across drains so lease transition edges never go
    missing at a drain boundary. Everything else zeroes on drain.

    The runtime DONATES the ring operand to the jitted tick (the caller
    never touches the pre-step ring again), so XLA updates these buffers in
    place — without donation the gauge ring alone would cost a fresh copy
    per tick.
    """

    small: jax.Array             # (5 + B + K,) f64 — [SCALARS | cost_hist |
                                 #   tier_gb]. SCALARS order: ticks since
                                 #   last drain (= gauge slot), OFF→{WAITING,
                                 #   ON} request edges, →ON activations,
                                 #   ON→OFF releases, GB billed while leased;
                                 #   then B per-row hourly realized-cost
                                 #   histogram counts; then K VPN-path billed
                                 #   GB tier buckets
    prev_state: jax.Array        # (M,) int32 — carried across drains
    gauges: jax.Array            # (8, cap) f64 — per-tick gauge rings, one
                                 #   row per GAUGES name, column = tick slot


def default_hist_edges(n_bins: int, lo: float = 1e-2, hi: float = 1e4) -> np.ndarray:
    """Log-spaced histogram edges for per-row hourly realized cost ($/h).

    ``n_bins`` buckets spanning [lo, hi]; values outside clip into the end
    bins (the first bin doubles as "≈ zero cost" — idle rows land there).
    """
    assert n_bins >= 2 and 0 < lo < hi
    return np.logspace(np.log10(lo), np.log10(hi), n_bins + 1)


def init_ring(
    n_rows: int, cap: int, n_bins: int, n_tiers: int, dtype=jnp.float64
) -> MetricsRing:
    assert cap >= 1 and n_bins >= 2 and n_tiers >= 1
    return MetricsRing(
        small=jnp.zeros((len(SCALARS) + n_bins + n_tiers,), dtype),
        prev_state=jnp.full((n_rows,), OFF, jnp.int32),
        gauges=jnp.zeros((len(GAUGES), cap), dtype),
    )


def reset_ring(ring: MetricsRing) -> MetricsRing:
    """Fresh window: zero everything EXCEPT the carried ``prev_state``."""
    zeroed = jax.tree.map(jnp.zeros_like, ring)
    return zeroed._replace(prev_state=ring.prev_state)


def init_tenant_ring(
    n_slots: int, n_rows: int, cap: int, n_bins: int, n_tiers: int,
    dtype=jnp.float64,
) -> MetricsRing:
    """A pool of ``n_slots`` per-tenant rings as ONE ring pytree with a
    leading tenant axis on every leaf — the vmapped mega-tick of the
    multi-tenant gateway updates all slots through the SAME
    :func:`update_ring` path the standalone runtime compiles (one metrics
    path, lifted one axis; see :mod:`repro.gateway`)."""
    one = init_ring(n_rows, cap, n_bins, n_tiers, dtype)
    return jax.tree.map(
        lambda x: jnp.tile(x, (n_slots,) + (1,) * x.ndim), one
    )


def reset_ring_slot(ring: MetricsRing, slot: int) -> MetricsRing:
    """Reset ONE tenant slot of a pooled ring to its initial state (zeros,
    ``prev_state`` back to OFF) — a tenant joining mid-window must not
    inherit the previous occupant's counters or FSM edge baseline."""
    return jax.tree.map(lambda p: p.at[slot].set(jnp.zeros_like(p[slot])), ring)._replace(
        prev_state=ring.prev_state.at[slot].set(OFF)
    )


def update_ring(
    ring: MetricsRing,
    hist_edges: jax.Array,
    *,
    x_t: jax.Array,
    state_t: jax.Array,
    vpn_t: jax.Array,
    cci_t: jax.Array,
    d_pair: jax.Array,
    d_row: jax.Array,
    month_cum: jax.Array,
    tier_bounds: jax.Array,
    routing_idx: Optional[jax.Array] = None,
    pred_t: Optional[jax.Array] = None,
) -> MetricsRing:
    """One tick of metrics, pure XLA — consumes only existing tick outputs.

    ``routing_idx`` maps the per-PAIR billed volume onto its serving port's
    decision in topology mode (``None`` in fleet mode, rows == pairs);
    ``pred_t`` is this tick's per-row demand forecast when the policy is
    forecast-gated (``None`` otherwise — the calibration gauges stay zero).
    Tier attribution uses the start-of-hour cumulative volume: an hour whose
    volume straddles a tier boundary is counted in its starting tier (the
    billing itself is exact; this is a metric, the monitors reconcile totals
    not tier splits).
    """
    f = ring.gauges.dtype
    B = hist_edges.shape[0] - 1
    K = tier_bounds.shape[1]
    i = ring.small[0].astype(jnp.int32)  # ticks = gauge slot
    st = state_t.astype(jnp.int32)
    prev = ring.prev_state
    on = (x_t == 1)
    realized = jnp.where(on, cci_t, vpn_t)

    # Lease lifecycle edges vs the previous tick's FSM state — one stacked
    # (M, 3) compare reduced in a single sum. Orientation matters on XLA
    # CPU: reducing axis=0 of a (rows, few) array is one streaming pass
    # with a register-resident accumulator vector, while the transposed
    # (few, rows) axis=1 form measured 5-10x slower (it defeats the
    # vectorizer); every reduction in this function uses the former.
    # Count-like reductions accumulate bool→int32 and convert the TINY
    # result: converting the (rows, few) compare to f64 first forces XLA to
    # materialize it (hundreds of KB per tick) before the reduce; the
    # predicate reduce fuses with the compare instead. Counts ≤ rows are
    # exact in int32.
    edges3 = jnp.stack([
        (prev == OFF) & (st != OFF),  # requests
        (prev != ON) & (st == ON),    # activations
        (prev == ON) & (st == OFF),   # releases
    ], axis=1)
    req_act_rel = jnp.sum(edges3, axis=0, dtype=jnp.int32).astype(f)

    # Billed volume split: VPN path per tier (start-of-hour tier index from
    # the month-cumulative volume), CCI path in one bucket. Both binnings
    # are CUMULATIVE compare-reductions differenced on the (bins,) vector —
    # never a scatter-add (XLA CPU serializes small scatters per element:
    # measured ~350µs/tick at 2048 rows, 7x the whole plain tick) and never
    # a (rows × bins) one-hot materialization (another ~40µs of unfused
    # compare/convert/reduce thunks). ``w[j] = Σ vol·[cum ≥ bound_j]`` is
    # one fused compare-multiply-reduce; bucket k of the clipped tier index
    # is then w[k-1] - w[k] with the end buckets absorbing the clip.
    on_pair = (on[routing_idx] if routing_idx is not None else on).astype(f)
    vpn_vol = d_pair * (1.0 - on_pair)
    w = jnp.sum(
        vpn_vol[:, None] * (month_cum[:, None] >= tier_bounds).astype(f),
        axis=0,
    )  # (K,)
    total_vol = jnp.sum(vpn_vol)
    if K == 1:
        tier_delta = total_vol[None]
    else:
        tier_delta = jnp.concatenate([
            (total_vol - w[0])[None], w[:-2] - w[1:-1], w[K - 2][None]
        ])
    cci_gb = jnp.sum(d_pair * on_pair)

    # Per-row realized-cost histogram, same trick: s[e] = #rows with value
    # strictly above edge e (identical tie semantics to the left-insertion
    # searchsorted binning: bin = clip(#edges < v − 1, 0, B−1)); interior
    # bins are s[k] − s[k+1], the end bins absorb under/overflow.
    s = jnp.sum(
        realized[:, None] > hist_edges[None, :], axis=0, dtype=jnp.int32
    ).astype(f)
    hist_delta = jnp.concatenate([
        (realized.shape[0] - s[1])[None], s[1:B - 1] - s[2:B], s[B - 1][None]
    ])

    # Per-row gauge reductions as ONE stacked sum; the forecast-calibration
    # rows join the stack only when a forecast exists (static shape switch).
    rows = [on.astype(f), realized, vpn_t, cci_t, d_row]
    if pred_t is not None:
        pred = pred_t.astype(f)
        rows += [jnp.abs(pred - d_row), pred]
    sums = jnp.sum(jnp.stack(rows, axis=1), axis=0)
    zero = jnp.zeros((1,), f)
    err, pred_sum = (sums[5:6], sums[6:7]) if pred_t is not None else (zero, zero)

    # All 8 gauges land as ONE column write at slot ``i`` (GAUGES order),
    # and every scalar accumulator as ONE vector add in ``small`` layout.
    gvec = jnp.concatenate([
        sums[:4],                  # lease_on, realized, vpn, cci
        jnp.sum(d_pair)[None],     # billed_gb (pair-level, (P,) in topology)
        err, pred_sum,
        sums[4:5],                 # demand_total
    ])
    gauges = jax.lax.dynamic_update_slice(
        ring.gauges, gvec[:, None], (jnp.int32(0), i)
    )
    small = ring.small + jnp.concatenate([
        jnp.ones((1,), f), req_act_rel, cci_gb[None], hist_delta, tier_delta
    ])
    return MetricsRing(small=small, prev_state=st, gauges=gauges)


def ring_layout(cap: int, n_bins: int, n_tiers: int) -> Tuple[Tuple[str, int], ...]:
    """(name, length) spec of the flattened drain vector — shared by the
    in-jit :func:`flatten_ring` and the host :meth:`DrainedMetrics.from_flat`."""
    return tuple(
        [(s, 1) for s in SCALARS]
        + [(g, cap) for g in GAUGES]
        + [("cost_hist", n_bins), ("tier_gb", n_tiers)]
    )


def ring_size(cap: int, n_bins: int, n_tiers: int) -> int:
    return sum(n for _, n in ring_layout(cap, n_bins, n_tiers))


def flatten_ring(ring: MetricsRing) -> jax.Array:
    """The drain payload: every drained field as one flat float64 vector, in
    :func:`ring_layout` order (``prev_state`` stays in the carry)."""
    # ``small`` is [SCALARS | hist | tier] and gauges reshapes row-major
    # into per-gauge contiguous blocks in GAUGES order — reordering two
    # slices of ``small`` around the gauge block reproduces the layout of
    # concatenating each field separately.
    n = len(SCALARS)
    return jnp.concatenate([
        ring.small[:n], jnp.reshape(ring.gauges, (-1,)), ring.small[n:],
    ])


@dataclasses.dataclass(frozen=True)
class DrainedMetrics:
    """One drained window, host-side. Gauge arrays carry ``ticks`` valid
    entries (a final partial drain can close a window early)."""

    hour: int  # stream hour at which the drain happened (exclusive end)
    ticks: int
    requests: int
    activations: int
    releases: int
    cci_gb: float
    lease_on: np.ndarray
    realized_cost: np.ndarray
    vpn_cost: np.ndarray
    cci_cost: np.ndarray
    billed_gb: np.ndarray
    forecast_abs_err: np.ndarray
    pred_total: np.ndarray
    demand_total: np.ndarray
    cost_hist: np.ndarray
    tier_gb: np.ndarray

    @classmethod
    def from_flat(
        cls, hour: int, vec, *, cap: int, n_bins: int, n_tiers: int
    ) -> "DrainedMetrics":
        vec = np.asarray(vec, np.float64)
        layout = ring_layout(cap, n_bins, n_tiers)
        assert vec.shape == (sum(n for _, n in layout),), (
            vec.shape, sum(n for _, n in layout),
        )
        fields = {}
        off = 0
        for name, n in layout:
            chunk = vec[off:off + n]
            off += n
            if name in SCALARS:
                fields[name] = (
                    float(chunk[0]) if name == "cci_gb" else int(chunk[0])
                )
            else:
                fields[name] = chunk.copy()
        ticks = fields["ticks"]
        for g in GAUGES:
            fields[g] = fields[g][:ticks]
        return cls(hour=hour, **fields)

    def to_json(self) -> dict:
        d = dataclasses.asdict(self)
        return {
            k: (v.tolist() if isinstance(v, np.ndarray) else v)
            for k, v in d.items()
        }

    def cost_quantiles(
        self, edges: np.ndarray, qs: Sequence[float] = (0.5, 0.95, 0.99)
    ) -> dict:
        """Per-row hourly realized-cost quantiles from the binned histogram
        (log-interpolated within the hit bin; exact to bin resolution)."""
        edges = np.asarray(edges, np.float64)
        counts = np.asarray(self.cost_hist, np.float64)
        total = counts.sum()
        out = {}
        if total <= 0:
            return {f"p{int(100 * q)}": float("nan") for q in qs}
        cum = np.cumsum(counts)
        lo, hi = np.log(edges[:-1]), np.log(edges[1:])
        for q in qs:
            target = q * total
            b = int(np.searchsorted(cum, target))
            b = min(b, counts.shape[0] - 1)
            prev = cum[b - 1] if b > 0 else 0.0
            frac = (target - prev) / counts[b] if counts[b] > 0 else 0.5
            out[f"p{int(100 * q)}"] = float(np.exp(lo[b] + frac * (hi[b] - lo[b])))
        return out
