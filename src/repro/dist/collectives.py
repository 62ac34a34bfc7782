"""Gradient synchronization modes over the (pod, data, model) mesh.

``sync_grads`` is the cross-pod actuator the interconnect planners drive
(:class:`repro.core.planner.InterconnectPlanner` for one link,
:class:`repro.fleet.runtime.ElasticFleetPlanner` for a fleet — each link's
FSM mode selects this module's path per tick):

* ``direct``        one flat mean over every data-parallel axis;
* ``hierarchical``  mean within each pod (cheap ICI), then across pods — the
                    full-precision mode used when the leased DCI is ON;
* ``compressed``    intra-pod mean in full precision, then int8 per-row
                    quantization with error feedback for the pod hop only —
                    ~4x fewer wire (billed) bytes on the pay-per-GB path.

All modes run under ``shard_map`` so the collectives are explicit in compiled
HLO (the telemetry tests meter them there). :func:`sync_wire_bytes` prices a
sync's cross-pod bytes under each mode — the demand model the planners feed
back into the next hour's toggle decision (endogenous demand).
"""
from __future__ import annotations

import functools
import math
import re

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

INT8_MAX = 127.0


def _dp_axes(mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def init_error_state(grads, mesh):
    """Zero error-feedback residuals (one per gradient leaf)."""
    del mesh
    return jax.tree.map(lambda g: jnp.zeros(g.shape, jnp.float32), grads)


def _quantize(v):
    """Per-row symmetric int8: scale over the last dim."""
    scale = jnp.max(jnp.abs(v), axis=-1, keepdims=True) / INT8_MAX
    scale = jnp.maximum(scale, 1e-30)
    q = jnp.round(v / scale).astype(jnp.int8)
    return q, scale


def _sync_leaf(g, err, *, mode: str, dp, has_pod: bool):
    intra = tuple(a for a in dp if a != "pod")
    if mode == "direct":
        return jax.lax.pmean(g, dp) if dp else g, None
    if mode == "hierarchical":
        out = jax.lax.pmean(g, intra) if intra else g
        if has_pod:
            out = jax.lax.pmean(out, "pod")
        return out, None
    # compressed: full precision inside the pod, int8 + error feedback across.
    out = jax.lax.pmean(g, intra) if intra else g
    if not has_pod:
        return out, jnp.zeros_like(out) if err is not None else None
    u = out + (err if err is not None else 0.0)
    q, scale = _quantize(u)
    deq = q.astype(jnp.float32) * scale
    new_err = u - deq
    qs = jax.lax.all_gather(q, "pod")          # int8 on the wire
    ss = jax.lax.all_gather(scale, "pod")      # tiny f32 sidecar
    avg = jnp.mean(qs.astype(jnp.float32) * ss, axis=0)
    return avg.astype(g.dtype), new_err


def sync_grads(grads, mesh, *, mode: str = "direct", err_state=None):
    """Average a gradient pytree over the mesh's data-parallel axes.

    Returns ``(synced_grads, err_state)``; ``err_state`` is the updated
    error-feedback residual pytree for ``mode='compressed'`` (else ``None``).
    Inputs may be host arrays (replicated on entry).
    """
    assert mode in ("direct", "hierarchical", "compressed"), mode
    dp = _dp_axes(mesh)
    has_pod = "pod" in mesh.shape
    if err_state is None and mode == "compressed":
        err_state = init_error_state(grads, mesh)
    use_err = mode == "compressed"

    leaf = functools.partial(_sync_leaf, mode=mode, dp=dp, has_pod=has_pod)

    def fn(g, e):
        pairs = jax.tree.map(leaf, g, e)
        outs = jax.tree.map(lambda p: p[0], pairs, is_leaf=lambda p: isinstance(p, tuple))
        errs = jax.tree.map(lambda p: p[1], pairs, is_leaf=lambda p: isinstance(p, tuple))
        return outs, errs

    err_in = err_state if use_err else jax.tree.map(lambda g: jnp.zeros((), jnp.float32), grads)
    mapped = jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(P(), P()),
        out_specs=(P(), P()),
        check_vma=False,
    )
    outs, errs = mapped(grads, err_in)
    return outs, (errs if use_err else None)


def sync_wire_bytes(grads, mode: str) -> int:
    """Cross-pod wire (billed) bytes of ONE ``sync_grads`` call under ``mode``.

    The planners' demand model: ``hierarchical``/``direct`` move every leaf
    at its own precision; ``compressed`` moves int8 payload plus one f32
    scale per quantization row (last-dim rows) — the ~4x shrink that makes
    the pay-per-GB path cheap (cf. ``COMPRESS_RATIO`` in
    :mod:`repro.core.planner`).
    """
    assert mode in ("direct", "hierarchical", "compressed"), mode
    total = 0
    for g in jax.tree.leaves(grads):
        n = int(math.prod(g.shape)) if g.shape else 1
        if mode == "compressed":
            rows = n // (g.shape[-1] if getattr(g, "ndim", 0) else 1)
            total += n + max(rows, 1) * 4        # int8 payload + f32 scales
        else:
            total += n * jnp.dtype(g.dtype).itemsize
    return total


def sync_domain_label(gid, mode: str, *, tenant=None) -> str:
    """The ``named_scope`` label of one leased sync domain.

    Must stay parseable by :data:`repro.dist.telemetry._SYNCDOM_RE`
    (``syncdom[\\w.-]*`` — a single ``[\\w.-]`` token prefixed ``syncdom``),
    so the optional multi-tenant gateway attribution rides INSIDE the token:
    ``syncdom_t.<tenant>.g{gid}_{mode}`` — telemetry built before tenants
    existed keeps attributing bytes per domain, and per-tenant breakdowns
    fall out of the same label. Tenant names are sanitized to the telemetry
    charset (anything else becomes ``-``).
    """
    t = ""
    if tenant is not None:
        t = "t." + re.sub(r"[^\w.-]", "-", str(tenant)) + "."
    return f"syncdom_{t}g{gid}_{mode}"


def fleet_sync_grads(
    grads_per_link, mesh, modes, err_states=None, *, groups=None, tenant=None
):
    """Actuate a fleet plan: job ``i``'s gradients sync under ``modes[i]``.

    The bridge between :class:`repro.fleet.runtime.ElasticFleetPlanner` and
    the collective layer: each training job (one per interconnect link, or
    one per region PAIR in per-port topology mode) syncs hierarchically at
    full precision while its leased link is ON, and int8-compressed over the
    pay-per-GB path otherwise. Returns ``(synced, err_states, billed_bytes)``
    lists; feed ``billed_bytes`` (x steps/hour) back as the planner's
    next-hour demand to close the endogenous loop.

    ``groups`` (optional, one hashable id per job — e.g.
    ``ElasticFleetPlanner.sync_groups()``'s routed-port indices) declares
    leased sync DOMAINS: jobs sharing a group id and mode are synced in ONE
    ``sync_grads`` call (their pytrees batched into a list), so pairs
    attached to the same leased CCI port share one collective launch over
    the shared physical link instead of one per pair. Results are
    numerically identical to the ungrouped path (the mesh average is per
    leaf), and wire bytes stay metered PER JOB via :func:`sync_wire_bytes`
    — the per-pair billing the topology pricing model needs.

    Each domain's sync runs under a ``jax.named_scope`` of
    :func:`sync_domain_label` (``syncdom_g{group}_{mode}``, with an optional
    ``tenant=`` owner embedded as ``syncdom_t.<tenant>.g{group}_{mode}`` —
    the multi-tenant gateway labels each tenant's actuation this way), which
    lands in the compiled HLO as op metadata —
    :func:`repro.dist.telemetry.collective_bytes` parses it back out,
    attributing collective bytes per sync domain (the observability layer's
    device-side counterpart of the runtime's port tracks).
    """
    n = len(grads_per_link)
    assert n == len(modes), (n, len(modes))
    err_states = err_states or [None] * n
    if groups is None:
        domains = [(i,) for i in range(n)]
    else:
        assert len(groups) == n, (len(groups), n)
        by_key: dict = {}
        for i, (g, m) in enumerate(zip(groups, modes)):
            by_key.setdefault((g, m), []).append(i)
        domains = [tuple(v) for v in by_key.values()]
    synced = [None] * n
    errs = [None] * n
    billed = [None] * n
    for idx in domains:
        mode = modes[idx[0]]
        dom_errs = [err_states[i] for i in idx]
        if all(e is None for e in dom_errs):
            dom_errs = None
        else:
            # A domain can mix carried and fresh jobs after a re-route:
            # fresh jobs start from zero residuals, carried ones keep theirs.
            dom_errs = [
                e if e is not None else init_error_state(grads_per_link[i], mesh)
                for e, i in zip(dom_errs, idx)
            ]
        gid = groups[idx[0]] if groups is not None else idx[0]
        with jax.named_scope(sync_domain_label(gid, mode, tenant=tenant)):
            out, new_err = sync_grads(
                [grads_per_link[i] for i in idx], mesh, mode=mode,
                err_state=dom_errs,
            )
        for k, i in enumerate(idx):
            synced[i] = out[k]
            errs[i] = new_err[k] if new_err is not None else None
            billed[i] = sync_wire_bytes(grads_per_link[i], mode)
    return synced, errs, billed
