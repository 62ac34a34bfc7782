"""The multi-tenant fleet gateway: N runtimes behind one jitted mega-tick.

One :class:`FleetGateway` serves many independent tenants — each with its
own :class:`~repro.fleet.topology.TopologySpec`/routing (or fleet spec),
policy pytree, billing calendar, horizon, and demand stream — from shared
capacity-bucketed state pools. Per gateway chunk, each non-empty bucket
costs exactly ONE jitted dispatch: the runtime's packed chunk
(:func:`repro.fleet.runtime._build_step_many`, then
:func:`~repro.fleet.runtime._pack_chunk`), ``jax.vmap``-ed over the pool's
leading slot axis and masked by an alive bitmap, so a bucket's call brings
home two arrays (three on a drain call), as ``FleetRuntime.step_many``
does. The host side of the round trip (ring reads into the flat H2D block,
fetch, ring and accumulator mirror, output dict) is the runtime's own
helpers over that slot axis. Membership churn (join,
leave, grow/shrink across buckets, re-route) is pure operand traffic —
``.at[slot].set`` writes into fixed-shape pools — so a bucket shape
compiles once, ever.

The contract is the streamed-vs-offline exactness guarantee lifted one
level: a pooled tenant's per-hour decisions are BIT-EXACT vs its own
standalone :class:`~repro.fleet.runtime.FleetRuntime` fed the same demand
(property-tested across all three policies, including mid-stream
``reroute()`` and departures). That holds because (a) tenant operands
resolve through the same :func:`~repro.fleet.runtime.resolve_runtime_operands`
path, (b) padding is provably inert (:mod:`repro.gateway.pool`), and
(c) the sequential reductions (prefixes, month boundaries, tier state)
run on the device in the standalone order, with the prefix rings mirrored
host-side by the standalone runtime's own mirror.

Billing stays host-side per tenant (float64 accumulators, surviving
bucket moves via a carry), metrics ride the PR-6 device ring with a tenant
axis (one metrics path; per-tenant windows drained on the gateway cadence
and reconciled + SLO-checked by
:class:`~repro.obs.monitors.TenantSLOMonitor`, breaches surfaced as typed
:class:`~repro.obs.ContractViolation`\\ s), and admission control bounds
bursty arrival: a FIFO join queue with a hard limit, and typed
:class:`AdmissionError` rejections that never touch the device.
"""
from __future__ import annotations

import collections
import dataclasses
from typing import Dict, List, Optional

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.planner import collective_mode
from repro.fleet.routing import as_routing_plan, padded_operand_np
from repro.fleet import runtime
from repro.fleet.runtime import (
    RuntimeConfig,
    _fetch,
    _pack_chunk,
    _packed_planes,
    _ring_commit,
    _ring_reads,
    _step_out,
    _unpack_chunk,
    resolve_runtime_operands,
)
from repro.obs.metrics import (
    DrainedMetrics,
    default_hist_edges,
    init_tenant_ring,
    reset_ring_slot,
)
from repro.obs.monitors import ContractViolation, TenantSLOMonitor
from repro.obs.profile import count

from .pool import BucketKey, bucket_key_for, pack_tenant, set_slot


# The float64 planes a bucket brings home: every plane the host reads, with
# d_pair for per-tenant billed GB (no pooled tenant has a live forecaster).
_PLANES = _packed_planes(None, d_pair=True)


class AdmissionError(RuntimeError):
    """A typed join rejection — the gateway's backpressure signal.

    ``reason`` is machine-readable: ``"queue_full"`` (burst exceeded the
    bounded join queue) or ``"too_large"`` (the tenant's padded capacities
    exceed the gateway's pool ceiling). Rejections are decided entirely
    host-side — no pool is allocated, nothing compiles.
    """

    def __init__(self, reason: str, message: str):
        super().__init__(message)
        self.reason = reason


@dataclasses.dataclass(frozen=True)
class TenantSLO:
    """What the tenant was sold: a realized-cost budget checked per drained
    window (``None`` disables the check; billing reconciliation always runs)."""

    max_hourly_cost: Optional[float] = None


@dataclasses.dataclass(frozen=True)
class TenantSpec:
    """One tenant's admission request: spec + config + demand + contract.

    ``config`` is the SAME frozen :class:`~repro.fleet.runtime.RuntimeConfig`
    that drives ``FleetRuntime.from_config`` — one validation path for
    standalone and pooled construction. ``demand`` is the tenant's
    (rows, T) GB/hour stream; ``horizon`` defaults to its full length.
    """

    spec: object
    demand: np.ndarray
    config: RuntimeConfig = RuntimeConfig()
    horizon: Optional[int] = None
    slo: Optional[TenantSLO] = None

    def resolved_horizon(self) -> int:
        h = self.horizon
        if h is None:
            h = int(np.asarray(self.demand).shape[1])
        assert h >= 1, h
        return int(h)


@dataclasses.dataclass
class TenantHandle:
    """The gateway's view of one tenant: where it lives and how far it is."""

    name: str
    status: str                     # "queued" | "active" | "done" | "left"
    key: Optional[BucketKey] = None
    bucket: Optional[int] = None    # index within the key's bucket list
    slot: Optional[int] = None
    joined_at: int = 0              # gateway hour of activation

    @property
    def placed(self) -> bool:
        return self.status == "active"


@dataclasses.dataclass(frozen=True)
class GatewayConfig:
    """Gateway-level knobs (tenant-level ones live in the TenantSpec)."""

    slots_per_bucket: int = 8
    max_buckets: Optional[int] = None   # pool-count ceiling (None: unbounded)
    queue_limit: int = 16               # bounded join queue (backpressure)
    max_rows: int = 4096                # per-tenant padded-capacity ceiling
    obs: bool = True                    # tenant-axis metrics ring + monitors
    cadence: int = 64                   # gateway drain cadence (hours)
    hist_bins: int = 8

    def __post_init__(self):
        assert self.slots_per_bucket >= 1
        assert self.queue_limit >= 0
        assert self.cadence >= 1 and self.hist_bins >= 2


class _Bucket:
    """One capacity bucket: fixed-shape device pools + vectorized host state.

    Device pools carry one leading slot axis over the standalone tick's
    operands (padded arrays/policy stacks, FSM carries, tick counters,
    routing index rows, the tenant-axis metrics ring, the alive bitmap).
    Host state is the standalone :class:`~repro.fleet.runtime.RuntimeState`
    numpy block, one row per slot — float64, elementwise identical math.
    """

    def __init__(self, key: BucketKey, n_slots: int, packed, obs_dims):
        self.key = key
        self.n_slots = n_slots
        m, p, hb = key.rows_cap, key.pairs_cap, key.hbuf_cap
        tile = lambda x: jnp.tile(
            x, (n_slots,) + (1,) * getattr(x, "ndim", 0)
        )
        with jax.enable_x64():
            # Seed every slot from the first joiner's padded operands —
            # placeholder values for not-yet-allocated slots (their outputs
            # are alive-masked and their FSMs start OFF on zero demand).
            self.arrays = jax.tree.map(tile, packed.arrays)
            self.policy = jax.tree.map(tile, packed.policy)
            fsm_one = jax.vmap(lambda q: q.init_carry())(packed.policy)
            self.fsm = jax.tree.map(tile, fsm_one)
            self.t_dev = jnp.zeros((n_slots,), jnp.int32)
            self.ssm_h = jnp.zeros((n_slots, m, 0), jnp.float32)
            # The pooled routing operand: each RoutingOperand field tiled
            # with a leading slot axis ((S, legs_cap) legs, (S, pairs_cap)
            # primary) — reroute() swaps ONE slot's rows, never the stack.
            self.routing = (
                jax.tree.map(lambda x: tile(jnp.asarray(x)), packed.routing)
                if key.topology else None
            )
            self.alive_dev = jnp.zeros((n_slots,), jnp.float64)
            self.ring = None
            if obs_dims is not None:
                cadence, n_bins = obs_dims
                self.ring = init_tenant_ring(
                    n_slots, m, cadence, n_bins, key.n_tiers
                )
        z = lambda *s: np.zeros((n_slots,) + s, np.float64)
        self.alive = np.zeros(n_slots, bool)
        self.t = np.zeros(n_slots, np.int64)
        self.hpm = np.ones(n_slots, np.int64)
        self.horizon = np.zeros(n_slots, np.int64)
        self.m = np.zeros(n_slots, np.int64)      # real decision rows
        self.p = np.zeros(n_slots, np.int64)      # real demand rows
        self.h_np = np.ones((n_slots, m), np.int64)
        self.dcum, self.month_vol = z(p), z(p)
        self.vpn_pref, self.cci_pref = z(m), z(m)
        self.ring_vpn, self.ring_cci = z(hb, m), z(hb, m)  # hour-major
        self.bill_real, self.bill_vpn, self.bill_cci = z(m), z(m), z(m)
        self.gb = z(p)
        self.demand = np.zeros((n_slots, p, 1), np.float64)
        self.routing_idx_np = np.zeros((n_slots, p), np.int64)
        self.slots: List[Optional[str]] = [None] * n_slots
        self.free: List[int] = list(range(n_slots))[::-1]
        # Device-resident float64 sequential block, kept across ticks and
        # mirrored host-side; re-seeded from the host copy after slot writes.
        self._dev_seq = None

    @property
    def occupied(self) -> int:
        return self.n_slots - len(self.free)

    def device_seq(self):
        # The (slots, Hbuf, M) window rings stay host-only: the gateway runs
        # the runtime's packed chunk over its slot axis (runtime._ring_reads).
        if self._dev_seq is None:
            with jax.enable_x64():
                self._dev_seq = (
                    jnp.asarray(self.hpm, jnp.int32),
                    jax.device_put((
                        self.dcum, self.month_vol, self.vpn_pref,
                        self.cci_pref,
                        np.zeros(self.vpn_pref.shape, np.float64),  # pred_live
                    )),
                )
        return self._dev_seq

    def totals(self, s: int) -> Dict[str, np.float64]:
        """Slot ``s``'s host float64 billing: realized $, VPN/CCI
        counterfactual $, billed GB."""
        return {"realized": self.bill_real[s].sum(), "vpn": self.bill_vpn[s].sum(),
                "cci": self.bill_cci[s].sum(), "gb": self.gb[s].sum()}

    def ensure_T(self, T: int) -> None:
        cur = self.demand.shape[2]
        if T > cur:
            self.demand = np.pad(self.demand, ((0, 0), (0, 0), (0, T - cur)))

    def write_slot(self, s: int, name: str, packed, demand, horizon) -> None:
        """Allocate slot ``s``: pure per-slot operand writes, fixed shapes."""
        with jax.enable_x64():
            self.arrays = set_slot(self.arrays, s, packed.arrays)
            self.policy = set_slot(self.policy, s, packed.policy)
            fsm_one = jax.vmap(lambda q: q.init_carry())(packed.policy)
            self.fsm = set_slot(self.fsm, s, fsm_one)
            self.t_dev = self.t_dev.at[s].set(0)
            if self.routing is not None:
                self.routing = set_slot(
                    self.routing,
                    s,
                    jax.tree.map(jnp.asarray, packed.routing),
                )
            self.alive_dev = self.alive_dev.at[s].set(1.0)
            if self.ring is not None:
                self.ring = reset_ring_slot(self.ring, s)
        self.alive[s] = True
        self.t[s] = 0
        self.hpm[s] = packed.hours_per_month
        self.horizon[s] = horizon
        self.m[s], self.p[s] = packed.n_rows, packed.n_pairs
        self.h_np[s] = packed.h_np
        for a in (self.dcum, self.month_vol, self.vpn_pref, self.cci_pref,
                  self.ring_vpn, self.ring_cci, self.bill_real,
                  self.bill_vpn, self.bill_cci, self.gb):
            a[s] = 0.0
        d = np.asarray(demand, np.float64)
        self.ensure_T(d.shape[1])
        self.demand[s] = 0.0
        self.demand[s, : d.shape[0], : d.shape[1]] = d
        if packed.routing is not None:
            self.routing_idx_np[s] = packed.routing.primary
        self.slots[s] = name
        self._dev_seq = None

    def clear_slot(self, s: int) -> None:
        with jax.enable_x64():
            self.alive_dev = self.alive_dev.at[s].set(0.0)
        self.alive[s] = False
        self.demand[s] = 0.0
        self.slots[s] = None
        self.free.append(s)
        self._dev_seq = None


class FleetGateway:
    """Admit, pool, and step many tenant runtimes — one dispatch per bucket.

    See the module docstring for the architecture;
    :mod:`repro.gateway`'s package docstring has a quickstart.
    """

    def __init__(self, config: GatewayConfig = GatewayConfig()):
        self.config = config
        self.cadence = int(config.cadence)
        self.hist_bins = int(config.hist_bins)
        self._obs = bool(config.obs)
        with jax.enable_x64():
            self._edges = (
                jnp.asarray(default_hist_edges(self.hist_bins), jnp.float64)
                if self._obs else None
            )
        self._buckets: Dict[BucketKey, List[_Bucket]] = {}
        self._tenants: Dict[str, TenantHandle] = {}
        self._specs: Dict[str, TenantSpec] = {}
        self._resolved: Dict[str, object] = {}
        self._monitors: Dict[str, TenantSLOMonitor] = {}
        self._billing_carry: Dict[str, Dict[str, np.ndarray]] = {}
        self._drained: Dict[str, List[DrainedMetrics]] = {}
        self._queue: collections.deque = collections.deque()
        self._compiled: dict = {}
        self.compiles = 0               # jitted mega-tick variants built
        self.violations: List[ContractViolation] = []
        self.hours = 0                  # the gateway clock

    # --- admission ---------------------------------------------------------

    def join(self, name: str, tenant: TenantSpec) -> TenantHandle:
        """Admit a tenant: place it in a pool slot now, or queue it (FIFO,
        bounded), or reject with a typed :class:`AdmissionError`."""
        assert name not in self._tenants or self._tenants[name].status in (
            "done", "left"
        ), f"tenant {name!r} already admitted"
        resolved = resolve_runtime_operands(tenant.spec, tenant.config)
        key = bucket_key_for(resolved)
        if max(key.rows_cap, key.pairs_cap) > self.config.max_rows:
            raise AdmissionError(
                "too_large",
                f"tenant {name!r} pads to {key.rows_cap} rows x "
                f"{key.pairs_cap} pairs, over the gateway ceiling "
                f"{self.config.max_rows}",
            )
        packed = pack_tenant(resolved, key)
        handle = TenantHandle(name=name, status="queued", key=key)
        self._tenants[name] = handle
        self._specs[name] = tenant
        self._resolved[name] = resolved
        self._billing_carry.setdefault(name, self._zero_totals())
        if not self._try_place(handle, packed, tenant):
            if len(self._queue) >= self.config.queue_limit:
                del self._tenants[name], self._specs[name], self._resolved[name]
                raise AdmissionError(
                    "queue_full",
                    f"no bucket has headroom for tenant {name!r} and the "
                    f"join queue is at its limit "
                    f"({self.config.queue_limit})",
                )
            self._queue.append((name, packed, tenant))
        return handle

    def _zero_totals(self) -> Dict[str, float]:
        return {"realized": 0.0, "vpn": 0.0, "cci": 0.0, "gb": 0.0}

    def _try_place(self, handle, packed, tenant: TenantSpec) -> bool:
        key = packed.key
        buckets = self._buckets.setdefault(key, [])
        for bi, b in enumerate(buckets):
            if b.free:
                self._activate(handle, packed, tenant, bi, b)
                return True
        if not self._may_create_bucket():
            return False
        b = _Bucket(
            key, self.config.slots_per_bucket, packed,
            (self.cadence, self.hist_bins) if self._obs else None,
        )
        buckets.append(b)
        self._activate(handle, packed, tenant, len(buckets) - 1, b)
        return True

    def _may_create_bucket(self) -> bool:
        if self.config.max_buckets is None:
            return True
        total = sum(len(v) for v in self._buckets.values())
        if total < self.config.max_buckets:
            return True
        # GC one fully-empty pool to make room (its compiled tick stays
        # cached — re-creating the same key later costs zero recompiles).
        for key, lst in self._buckets.items():
            for i, b in enumerate(lst):
                if b.occupied == 0:
                    del lst[i]
                    return True
        return False

    def _activate(self, handle, packed, tenant: TenantSpec, bi, bucket) -> None:
        s = bucket.free.pop()
        bucket.write_slot(
            s, handle.name, packed, tenant.demand, tenant.resolved_horizon()
        )
        handle.status, handle.bucket, handle.slot = "active", bi, s
        handle.joined_at = self.hours
        slo = tenant.slo or TenantSLO()
        self._monitors[handle.name] = TenantSLOMonitor(
            handle.name, max_hourly_cost=slo.max_hourly_cost
        )
        self._drained.setdefault(handle.name, [])

    def _drain_admission_queue(self) -> None:
        still = collections.deque()
        while self._queue:
            name, packed, tenant = self._queue.popleft()
            if not self._try_place(self._tenants[name], packed, tenant):
                still.append((name, packed, tenant))
        self._queue = still

    # --- the mega-tick -----------------------------------------------------

    def tick(self, *, collect: bool = True) -> Dict[str, Dict[str, np.ndarray]]:
        """Advance EVERY active tenant one hour: :meth:`tick_many` on one
        hour (one stepping path, as in the standalone runtime). Returns
        per-tenant step outputs (the standalone ``FleetRuntime.step`` dict,
        sliced to real rows) when ``collect``; pass ``collect=False`` on the
        hot path to skip building them."""
        outs = self.tick_many(1, collect=collect)
        return {
            name: {k: v[:, 0] for k, v in out.items()}
            for name, out in outs.items()
        }

    # --- the chunked mega-tick (tick_many) ---------------------------------

    def _mega_many_fn(self, key: BucketKey, n_slots: int, drain: bool, K: int):
        ck = key.compile_key(
            n_slots=n_slots, obs=self._obs, drain=drain, chunk=K
        )
        fn = self._compiled.get(ck)
        if fn is None:
            # Looked up through the module at build time, as the runtime's
            # _step_many_fn does.
            chunk = runtime._build_step_many(
                key.topology, key.pred_source, False, self._obs, drain, K
            )
            built = runtime._chunk_planes(key.pred_source)
            edges = self._edges

            def mega(arrays, policy, fsm, ssm_h, t, routing, ring,
                     alive, hpm, seq, blocks):
                def one(a, q, f, s, tt, ri, rg, hp, sq, bk):
                    return chunk(a, q, None, f, s, tt, ri, rg, edges,
                                 hp, sq, bk)

                fsm, ssm_h, t1, ring, seq, ys, dv = jax.vmap(one)(
                    arrays, policy, fsm, ssm_h, t, routing, ring,
                    hpm, seq, blocks
                )
                # Alive-bitmap mask over each (n_slots, K, rows) plane,
                # before packing; the accumulators stay unmasked.
                ys = {n: p * alive[:, None, None] for n, p in zip(built, ys)}
                dec, vals = jax.vmap(
                    lambda pl, sq: _pack_chunk(pl, sq, _PLANES)
                )(ys, seq)
                return fsm, ssm_h, t1, ring, seq, dec, vals, dv

            fn = jax.jit(
                mega, donate_argnums=(6, 9) if self._obs else (9,)
            )
            self._compiled[ck] = fn
            self.compiles += 1
        return fn

    def tick_many(
        self, K: int, *, collect: bool = True
    ) -> Dict[str, Dict[str, np.ndarray]]:
        """Advance EVERY active tenant K hours — one chunked dispatch per
        non-empty bucket (the :meth:`repro.fleet.runtime.FleetRuntime.step_many`
        scan, vmapped over pool slots). Decisions and host float64 billing
        are bit-exact vs K sequential :meth:`tick` calls; per-tenant outputs
        come back stacked ``(rows, K)`` when ``collect``.

        Chunk-boundary semantics: lifecycle resolves at chunk ends — queued
        joins admit after the chunk, and every active tenant must have at
        least K hours of horizon left (asserted; finish a ragged tail with
        smaller chunks or per-tick :meth:`tick`). With obs on, the drain
        cadence must not fall strictly inside the chunk (pick K dividing
        the cadence); drains then fire at the same hours as per-tick
        stepping with bit-identical windows.
        """
        K = int(K)
        assert K >= 1, K
        hour = self.hours
        drain = False
        if self._obs:
            boundary = ((hour // self.cadence) + 1) * self.cadence
            assert boundary >= hour + K, (
                f"gateway drain cadence {self.cadence} falls mid-chunk "
                f"(hour {boundary} inside ({hour}, {hour + K})): pick K "
                f"dividing the cadence, or tick() across the boundary"
            )
            drain = boundary == hour + K
        outs: Dict[str, Dict[str, np.ndarray]] = {}
        finished: List[str] = []
        for key, buckets in self._buckets.items():
            for b in buckets:
                if b.occupied == 0:
                    continue
                remaining = b.horizon[b.alive] - b.t[b.alive]
                assert int(remaining.min()) >= K, (
                    f"tick_many({K}) would overrun a tenant's horizon "
                    f"(min remaining {int(remaining.min())}h): chunk the "
                    f"tail with a smaller K or finish it with tick()"
                )
                self._tick_bucket_many(key, b, K, drain, collect, outs,
                                       finished)
        self.hours = hour + K
        for name in finished:
            self._finish(name, "done")
        self._drain_admission_queue()
        return outs

    def _tick_bucket_many(self, key, b, K, drain, collect, outs,
                          finished) -> None:
        M, P = key.rows_cap, key.pairs_cap
        cols = np.minimum(
            b.t[:, None] + np.arange(K)[None, :], b.demand.shape[2] - 1
        )
        # The runtime's flat H2D block, one row per slot: demand, then the
        # pre-chunk window reads gathered from the host ring twins.
        nd = K * P
        blocks = np.empty((b.n_slots, nd + 2 * K * M))
        blocks[:, :nd] = np.take_along_axis(
            b.demand, cols[:, None, :], axis=2
        ).reshape(b.n_slots, nd)                        # native (P, K) order
        pre = blocks[:, nd:].reshape(b.n_slots, 2, K, M)
        _ring_reads(b.t, b.h_np, (b.ring_vpn, b.ring_cci),
                    (pre[:, 0], pre[:, 1]))
        blocks *= b.alive[:, None]

        fn = self._mega_many_fn(key, b.n_slots, drain, K)
        hpm_dev, seq = b.device_seq()
        with jax.enable_x64():
            b.fsm, b.ssm_h, b.t_dev, b.ring, seq, dec, vals, dv = fn(
                b.arrays, b.policy, b.fsm, b.ssm_h, b.t_dev,
                b.routing, b.ring, b.alive_dev, hpm_dev, seq,
                jax.device_put(blocks),
            )
        b._dev_seq = (hpm_dev, seq)
        fetched = _fetch((dec, vals, dv) if drain else (dec, vals))
        count("gateway.tick.d2h_arrays", len(fetched))
        p, accs = _unpack_chunk(fetched[0], fetched[1], _PLANES, K, M, P)

        # Mirror the device's sequential carry as the standalone runtime
        # does (dead slots' snapshots are alive-masked to zero).
        _ring_commit(b.t, (b.ring_vpn, b.ring_cci), (p["snap_v"], p["snap_c"]),
                     (b.dcum, b.month_vol, b.vpn_pref, b.cci_pref), accs)
        x, vpn_t, cci_t = p["x"], p["vpn_t"], p["cci_t"]
        # Billing accumulates hour by hour on the host: np.add.accumulate
        # is a strictly sequential left fold, so seeding it with the carry
        # gives the same bits for any chunking.
        seeded = lambda carry, cols: np.add.accumulate(
            np.concatenate([carry[:, None], cols], axis=1), axis=1
        )
        b.bill_real[...] = seeded(
            b.bill_real, np.where(x == 1, cci_t, vpn_t)
        )[:, K]
        b.bill_vpn[...] = seeded(b.bill_vpn, vpn_t)[:, K]
        b.bill_cci[...] = seeded(b.bill_cci, cci_t)[:, K]
        b.gb[...] = seeded(b.gb, p["d_pair"])[:, K]

        out = _step_out(p) if collect else None      # (n_slots, rows, K)
        for s, name in enumerate(b.slots):
            if name is None:
                continue
            if collect:
                m = int(b.m[s])
                outs[name] = {k: v[s, :m] for k, v in out.items()}
            if drain:
                self._drain_slot(name, b, s, fetched[2][s].copy(),
                                 int(b.t[s]) + K)
            if b.t[s] + K >= b.horizon[s]:
                finished.append(name)
        b.t += K

    # --- metrics / SLO -----------------------------------------------------

    def _drain_slot(self, name, b, s, vec, hour) -> None:
        ticks = vec[0]
        if ticks <= 0:
            return
        # Pad correction: the realized-cost histogram's zero-bin counted
        # every padded row (cost exactly 0.0) on every tick.
        vec[5 + 8 * self.cadence] -= ticks * (b.key.rows_cap - int(b.m[s]))
        dm = DrainedMetrics.from_flat(
            hour, vec, cap=self.cadence,
            n_bins=self.hist_bins, n_tiers=b.key.n_tiers,
        )
        self._drained[name].append(dm)
        self.violations.extend(
            self._monitors[name].on_drain(hour, dm, host_totals=b.totals(s))
        )

    def _flush_slot(self, name, b, s) -> None:
        """Host-side partial-window drain (leave/check time — never on the
        per-tick hot path)."""
        if b.ring is None:
            return
        small = np.asarray(b.ring.small[s], np.float64)
        gauges = np.asarray(b.ring.gauges[s], np.float64)
        vec = np.concatenate([small[:5], gauges.reshape(-1), small[5:]])
        self._drain_slot(name, b, s, vec, int(b.t[s]))
        with jax.enable_x64():
            b.ring = reset_ring_slot(b.ring, s)

    # --- lifecycle ---------------------------------------------------------

    def _bucket_of(self, handle) -> _Bucket:
        return self._buckets[handle.key][handle.bucket]

    def _finish(self, name: str, status: str) -> None:
        handle = self._tenants[name]
        assert handle.status == "active", (name, handle.status)
        b = self._bucket_of(handle)
        s = handle.slot
        self._flush_slot(name, b, s)
        carry = self._billing_carry[name]
        for k, v in b.totals(s).items():
            carry[k] += v
        b.clear_slot(s)
        handle.status, handle.bucket, handle.slot = status, None, None
        self._drain_admission_queue()

    def leave(self, name: str) -> None:
        """Remove an active tenant mid-stream: drain its metrics window,
        bank its billing, free the slot, and admit from the queue — all
        operand traffic, zero recompiles."""
        self._finish(name, "left")

    def resize(self, name: str, tenant: TenantSpec) -> TenantHandle:
        """Grow/shrink a tenant across capacity buckets: admit the NEW shape
        first (so a rejection leaves the tenant untouched), then retire the
        old slot. Billing totals carry across; the stream restarts at the
        new spec's hour 0 with fresh windows (a reshaped WAN is a new
        planning problem — the carried prefix rings would be shape-nonsense).
        """
        handle = self._tenants.get(name)
        assert handle is not None and handle.status == "active", name
        old_key, old_bucket, old_slot = handle.key, handle.bucket, handle.slot
        resolved = resolve_runtime_operands(tenant.spec, tenant.config)
        key = bucket_key_for(resolved)
        if max(key.rows_cap, key.pairs_cap) > self.config.max_rows:
            raise AdmissionError(
                "too_large",
                f"tenant {name!r} resize pads over the gateway ceiling",
            )
        packed = pack_tenant(resolved, key)
        # Flush the old incarnation's partial metrics window NOW, while its
        # monitor is still registered (placement installs the new one); the
        # later _finish re-flush then sees an empty ring and no-ops.
        self._flush_slot(name, self._bucket_of(handle), old_slot)
        # Reserve the new slot BEFORE freeing the old one.
        probe = TenantHandle(name=name, status="queued", key=key)
        if not self._try_place(probe, packed, tenant):
            raise AdmissionError(
                "queue_full",
                f"no bucket has headroom to resize tenant {name!r}",
            )
        # Retire the old incarnation (banks billing, frees the slot).
        handle.key, handle.bucket, handle.slot = old_key, old_bucket, old_slot
        self._finish(name, "left")
        self._tenants[name] = probe
        self._specs[name] = tenant
        self._resolved[name] = resolved
        return probe

    def reroute(self, name: str, routing) -> None:
        """Swap one tenant's row→port routing mid-stream — the standalone
        :meth:`FleetRuntime.reroute` contract, as one ``.at[slot]`` operand
        write into the pooled leg stack (never a recompile). ``routing`` is
        a :class:`~repro.fleet.routing.RoutingPlan` whose legs fit the
        tenant's bucketed leg capacity; legacy bare index vectors and
        one-hot matrices keep working through the deprecation shim."""
        handle = self._tenants[name]
        assert handle.status == "active", (name, handle.status)
        assert handle.key.topology, (
            "reroute() applies to topology (shared-port) tenants"
        )
        b = self._bucket_of(handle)
        s = handle.slot
        resolved = self._resolved[name]
        m, p = int(b.m[s]), int(b.p[s])
        with jax.enable_x64():
            plan = as_routing_plan(
                routing, n_ports=m, context="FleetGateway.reroute"
            )
            assert plan.n_rows == p, (
                f"plan routes {plan.n_rows} rows, tenant carries {p}"
            )
            if resolved.spec is not None:
                resolved.spec.validate_plan(plan)
            if plan.total_hops > b.key.legs_cap:
                raise ValueError(
                    f"plan needs {plan.total_hops} legs but tenant "
                    f"{name!r} is bucketed at legs_cap={b.key.legs_cap} — "
                    "a deeper swap budget needs a resize() into a larger "
                    "bucket"
                )
            op = padded_operand_np(
                plan, n_legs=b.key.legs_cap, n_rows=b.key.pairs_cap,
                pad_pair=b.key.pairs_cap - 1, pad_port=b.key.rows_cap - 1,
            )
            b.routing = set_slot(
                b.routing, s, jax.tree.map(jnp.asarray, op)
            )
        b.routing_idx_np[s] = op.primary

    # --- queries -----------------------------------------------------------

    def handle(self, name: str) -> TenantHandle:
        return self._tenants[name]

    @property
    def n_active(self) -> int:
        return sum(1 for h in self._tenants.values() if h.status == "active")

    @property
    def n_queued(self) -> int:
        return len(self._queue)

    @property
    def n_buckets(self) -> int:
        return sum(len(v) for v in self._buckets.values())

    def billing(self, name: str) -> Dict[str, float]:
        """Lifetime host-side float64 totals (across resizes and departure):
        realized $, VPN/CCI counterfactual $, billed GB."""
        totals = dict(self._billing_carry[name])
        handle = self._tenants[name]
        if handle.status == "active":
            b, s = self._bucket_of(handle), handle.slot
            for k, v in b.totals(s).items():
                totals[k] += v
        return {k: float(v) for k, v in totals.items()}

    def metrics(self, name: str) -> List[DrainedMetrics]:
        """The tenant's drained metrics windows (current incarnation)."""
        return list(self._drained.get(name, []))

    def check(self, *, final: bool = True) -> List[ContractViolation]:
        """Flush every active tenant's partial metrics window through its
        :class:`~repro.obs.monitors.TenantSLOMonitor` and return ALL
        violations recorded so far (typed, tenant-attributed). The gateway
        records rather than raises — one tenant's breach must not stall the
        others' streams."""
        if final and self._obs:
            for handle in self._tenants.values():
                if handle.status == "active":
                    self._flush_slot(
                        handle.name, self._bucket_of(handle), handle.slot
                    )
        return list(self.violations)

    def sync_groups(self, name: str, out=None) -> List[int]:
        """Per-job sync-domain ids for
        :func:`repro.dist.collectives.fleet_sync_grads` (pass
        ``tenant=name`` there so the HLO labels attribute bytes per tenant):
        routed-port ids in topology mode, row ids in fleet mode."""
        handle = self._tenants[name]
        assert handle.status == "active", (name, handle.status)
        b, s = self._bucket_of(handle), handle.slot
        p = int(b.p[s])
        if not handle.key.topology:
            return list(range(int(b.m[s])))
        return [int(g) for g in b.routing_idx_np[s, :p]]

    def modes(self, name: str, out, *, mode_fn=None) -> List[str]:
        """Map one tenant's step output to per-actuator collective modes
        (the standalone :meth:`FleetRuntime.modes` contract)."""
        if mode_fn is None:
            mode_fn = collective_mode
        handle = self._tenants[name]
        states = np.asarray(out["state"])
        if handle.key.topology:
            b, s = self._bucket_of(handle), handle.slot
            states = states[b.routing_idx_np[s, : int(b.p[s])]]
        return [mode_fn(int(v)) for v in states]


__all__ = [
    "AdmissionError",
    "FleetGateway",
    "GatewayConfig",
    "TenantHandle",
    "TenantSLO",
    "TenantSpec",
]
