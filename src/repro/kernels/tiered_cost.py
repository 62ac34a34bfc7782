"""Tiered VPN transfer-cost Pallas TPU kernel — the paper's Eq. (2) hot loop.

The planner and the sensitivity benchmarks evaluate the tiered cost over
(hours x pairs x tiers) grids thousands of times (vmapped parameter sweeps);
this kernel fuses the per-tier segment arithmetic

    cost[t, p] = Σ_i rate_i * clip(min(hi, b_i) - max(lo, b_{i-1}), 0)

into one VPU pass per (time x pair) tile. The monthly prefix sums (``lo``)
are computed outside (cumsum is a cheap XLA op); the kernel handles the
O(T·P·n_tiers) segmentation, which dominates.

Tier tables are compile-time constants (closure), matching how pricing
catalogs are static per scenario.

The *batched* variant (``tiered_cost_batched``) prices N heterogeneous links
at once: tier tables become ``(N, K)`` array operands (one padded table per
link) and the grid tiles the ``(N, T)`` volume plane. The fleet engine
(``repro.fleet.engine``) uses the pure-XLA twin
(``tiered_cost_batched_ref``) by default — it fuses fine and supports f64 —
and the Pallas path on TPU f32 runs where the segmentation loop dominates.

Every wrapper zero-pads its operands up to whole (8, 128)-aligned blocks and
slices the result back: zero volume against zero or positive bounds prices
to exactly zero, so padding never changes a cost. Tiles stay well under the
TPU's 16 MiB scoped-VMEM default at any problem size.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.costmodel import tier_segment

DEFAULT_BLOCK_T = 512
_BLOCK_P = 512          # pair-axis tile of ``tiered_cost`` (lanes)
_BLOCK_N = 8            # link-axis tile of ``tiered_cost_batched`` (sublanes)
DEFAULT_SCAN_BLOCK_N = 512  # link-axis tile of ``tiered_cost_scan`` (lanes)
_LANE = 128


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _pad2(x: jax.Array, rows: int, cols: int) -> jax.Array:
    r, c = x.shape
    if (r, c) == (rows, cols):
        return x
    return jnp.pad(x, ((0, rows - r), (0, cols - c)))


def _zero():
    """Block index 0 as int32: a Python ``0`` turns int64 under ``enable_x64``
    (the fleet engine traces its pricing there) and Mosaic rejects i64
    indices."""
    return jnp.int32(0)


def _lane_block(n: int, block: int) -> int:
    """Lane-axis tile: ``block`` (a multiple of 128) or, for a narrow axis,
    the axis rounded up to whole lanes."""
    return min(block, _round_up(n, _LANE))


def _tiered_kernel(cum_ref, d_ref, o_ref, *, bounds: tuple, rates: tuple):
    lo = cum_ref[...].astype(jnp.float32)
    d = d_ref[...].astype(jnp.float32)
    total = jnp.zeros_like(lo)
    prev = 0.0
    for b, r in zip(bounds, rates):
        total = total + tier_segment(lo, d, prev, b) * r
        prev = b
    o_ref[...] = total


def tiered_cost(
    month_cum: jax.Array,        # (T, P)
    demand: jax.Array,           # (T, P)
    bounds: Sequence[float],     # upper bounds; inf is mapped to 1e30
    rates: Sequence[float],
    *,
    block_t: int = DEFAULT_BLOCK_T,
    interpret: bool = False,
) -> jax.Array:
    """(T, P) tiered cost, tiled over hours and pairs."""
    T, P = month_cum.shape
    assert demand.shape == (T, P)
    bp = _lane_block(P, _BLOCK_P)
    Tp, Pp = _round_up(T, block_t), _round_up(P, bp)
    bounds = tuple(float(b) if np.isfinite(b) else 1e30 for b in bounds)
    rates = tuple(float(r) for r in rates)
    spec = pl.BlockSpec((block_t, bp), lambda i, j: (i, j))
    out = pl.pallas_call(
        functools.partial(_tiered_kernel, bounds=bounds, rates=rates),
        grid=(Tp // block_t, Pp // bp),
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((Tp, Pp), jnp.float32),
        interpret=interpret,
    )(_pad2(month_cum, Tp, Pp), _pad2(demand, Tp, Pp))
    return out[:T, :P]


# ---------------------------------------------------------------------------
# Batched (N links, T hours) path — tier tables as per-link array operands
# ---------------------------------------------------------------------------


def _tiered_batched_kernel(cum_ref, d_ref, bounds_ref, rates_ref, o_ref):
    lo = cum_ref[...].astype(jnp.float32)          # (block_n, block_t)
    d = d_ref[...].astype(jnp.float32)
    bounds = bounds_ref[...].astype(jnp.float32)   # (block_n, K)
    rates = rates_ref[...].astype(jnp.float32)
    total = jnp.zeros_like(lo)
    prev = jnp.zeros_like(bounds[:, :1])
    for j in range(bounds.shape[-1]):              # K is small and static
        b_j = bounds[:, j:j + 1]                   # (block_n, 1) over hours
        total = total + tier_segment(lo, d, prev, b_j) * rates[:, j:j + 1]
        prev = b_j
    o_ref[...] = total


def tiered_cost_batched(
    month_cum: jax.Array,        # (N, T) per-link exclusive monthly volume
    demand: jax.Array,           # (N, T)
    bounds: jax.Array,           # (N, K) padded per-link tier bounds (finite)
    rates: jax.Array,            # (N, K) per-link marginal rates (0 on padding)
    *,
    block_t: int = DEFAULT_BLOCK_T,
    interpret: bool = False,
) -> jax.Array:
    """Per-hour tiered transfer cost for N heterogeneous links at once."""
    N, T = month_cum.shape
    K = bounds.shape[-1]
    assert demand.shape == (N, T) and bounds.shape == rates.shape == (N, K)
    bn, bt = _BLOCK_N, _lane_block(T, block_t)
    Np, Tp = _round_up(N, bn), _round_up(T, bt)
    plane = pl.BlockSpec((bn, bt), lambda n, i: (n, i))
    table = pl.BlockSpec((bn, K), lambda n, i: (n, _zero()))
    out = pl.pallas_call(
        _tiered_batched_kernel,
        grid=(Np // bn, Tp // bt),
        in_specs=[plane, plane, table, table],
        out_specs=plane,
        out_shape=jax.ShapeDtypeStruct((Np, Tp), jnp.float32),
        interpret=interpret,
    )(
        _pad2(month_cum, Np, Tp), _pad2(demand, Np, Tp),
        _pad2(bounds, Np, K), _pad2(rates, Np, K),
    )
    return out[:N, :T]


def tiered_cost_batched_ref(
    month_cum: jax.Array, demand: jax.Array, bounds: jax.Array, rates: jax.Array
) -> jax.Array:
    """Pure-XLA oracle for :func:`tiered_cost_batched` (any float dtype)."""
    from repro.core.costmodel import tiered_marginal_cost_tables

    return tiered_marginal_cost_tables(month_cum, demand, bounds, rates)


# ---------------------------------------------------------------------------
# Chunked streaming path — K hours per link with the tier carry in VMEM
# ---------------------------------------------------------------------------


def _tiered_scan_kernel(
    reset_ref, cum_ref, d_ref, bounds_ref, rates_ref, o_ref, cum_out_ref
):
    # Hour-major tile: links on lanes, the chunk's K hours on sublanes, so
    # the per-hour read/write is a dynamic SUBLANE row and the month-reset
    # flag a scalar from SMEM — Mosaic needs lane offsets it can prove are
    # multiples of 128, which a per-hour column index never is.
    K = d_ref.shape[0]
    bounds = bounds_ref[...].astype(jnp.float32)     # (Kt, block_n)
    rates = rates_ref[...].astype(jnp.float32)
    Kt = bounds.shape[0]

    def body(k, cum):
        # ``cum`` is the month-to-date volume carried ACROSS the K inner
        # hours — it lives in VMEM/registers for the whole chunk; only the
        # K cost rows and the final carry ever leave the tile.
        cum = jnp.where(reset_ref[k] != 0, 0.0, cum)      # month boundary
        d = d_ref[pl.ds(k, 1), :].astype(jnp.float32)
        total = jnp.zeros_like(cum)
        prev = jnp.zeros_like(cum)
        for j in range(Kt):
            b_j = bounds[j:j + 1, :]
            total = total + tier_segment(cum, d, prev, b_j) * rates[j:j + 1, :]
            prev = b_j
        o_ref[pl.ds(k, 1), :] = total
        return cum + d

    cum_out_ref[...] = jax.lax.fori_loop(
        jnp.int32(0), jnp.int32(K), body, cum_ref[...].astype(jnp.float32)
    )


def tiered_cost_scan(
    cum0: jax.Array,             # (N,) month-to-date volume at chunk start
    demand: jax.Array,           # (N, K) billed volume per inner hour
    bounds: jax.Array,           # (N, Kt) padded per-link tier bounds (finite)
    rates: jax.Array,            # (N, Kt) per-link marginal rates (0 padding)
    reset: jax.Array,            # (K,) int/bool — hour k starts a new month
    *,
    block_n: int = DEFAULT_SCAN_BLOCK_N,
    interpret: bool = False,
):
    """K-hour chunked tiered pricing with the tier carry resident in VMEM.

    The fused-chunk twin of :func:`tiered_cost_batched` for the streaming
    runtime's ``step_many`` path: instead of taking precomputed monthly
    prefix sums per hour, each grid tile carries the month-to-date volume
    through a ``fori_loop`` over the chunk's K inner hours (zeroed where
    ``reset`` marks a billing-month boundary), so on a TPU the tier state
    never leaves the device — or even VMEM — between chunk boundaries.
    Returns ``(costs (N, K) f32, cum_out (N,) f32)``; feeding ``cum_out``
    back as the next chunk's ``cum0`` chains chunks exactly.

    The kernel runs hour-major (operands transposed to ``(K, N)``, links on
    lanes in ``block_n`` tiles); the transposes are XLA ops around the call.

    f32 like the other Pallas kernels — this is the TPU throughput path;
    the runtime's jitted scan keeps XLA float64 pricing as the
    bit-exactness path (``tests/test_kernels.py`` sweeps this kernel
    against :func:`tiered_cost_scan_ref` in CPU interpret mode).
    """
    N, K = demand.shape
    Kt = bounds.shape[-1]
    assert cum0.shape == (N,) and bounds.shape == rates.shape == (N, Kt)
    assert reset.shape == (K,), (reset.shape, K)
    bn = _lane_block(N, block_n)
    Np = _round_up(N, bn)
    lanes = lambda a, rows: _pad2(a.T, rows, Np)
    costs, cum_out = pl.pallas_call(
        _tiered_scan_kernel,
        grid=(Np // bn,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, bn), lambda n: (_zero(), n)),
            pl.BlockSpec((K, bn), lambda n: (_zero(), n)),
            pl.BlockSpec((Kt, bn), lambda n: (_zero(), n)),
            pl.BlockSpec((Kt, bn), lambda n: (_zero(), n)),
        ],
        out_specs=[
            pl.BlockSpec((K, bn), lambda n: (_zero(), n)),
            pl.BlockSpec((1, bn), lambda n: (_zero(), n)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((K, Np), jnp.float32),
            jax.ShapeDtypeStruct((1, Np), jnp.float32),
        ],
        interpret=interpret,
    )(
        jnp.asarray(reset, jnp.int32),
        lanes(cum0[:, None], 1), lanes(demand, K),
        lanes(bounds, Kt), lanes(rates, Kt),
    )
    return costs[:, :N].T, cum_out[0, :N]


def tiered_cost_scan_ref(cum0, demand, bounds, rates, reset):
    """Pure-XLA oracle for :func:`tiered_cost_scan`: a ``lax.scan`` over the
    chunk's hour columns carrying the month-to-date volume (any float
    dtype — the fleet runtime uses exactly this formulation in f64)."""
    from repro.core.costmodel import tiered_marginal_cost_tables

    def body(cum, dr):
        d, rs = dr
        cum = jnp.where(rs != 0, jnp.zeros_like(cum), cum)
        cost = tiered_marginal_cost_tables(
            cum[:, None], d[:, None], bounds, rates
        )[:, 0]
        return cum + d, cost

    cum, costs = jax.lax.scan(
        body, cum0, (demand.T, jnp.asarray(reset, jnp.int32))
    )
    return costs.T, cum
