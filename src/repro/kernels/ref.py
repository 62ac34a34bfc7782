"""Pure-jnp oracles for every Pallas kernel (the ``ref.py`` contract).

These are the ground truth for the per-kernel allclose sweeps in
``tests/test_kernels.py`` and the CPU execution path selected by ``ops.py``
(Pallas-TPU kernels cannot lower on the CPU backend used for dry-runs).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------


def attention(
    q: jax.Array,  # (B, Hq, Sq, D)
    k: jax.Array,  # (B, Hkv, Skv, D)
    v: jax.Array,  # (B, Hkv, Skv, D)
    *,
    causal: bool = True,
    window: int = 0,          # sliding-window size; 0 = unlimited
    q_offset: int = 0,        # global position of q[0] (decode: cache length)
    scale: Optional[float] = None,
) -> jax.Array:
    """Naive full-softmax attention with GQA + causal/sliding-window masking.

    The small-shape oracle: materializes the (Sq, Skv) score matrix.
    """
    B, Hq, Sq, D = q.shape
    _, Hkv, Skv, _ = k.shape
    assert Hq % Hkv == 0
    group = Hq // Hkv
    scale = (D ** -0.5) if scale is None else scale
    # v may have a different head dim than q/k (MLA).
    kr = jnp.repeat(k, group, axis=1)
    vr = jnp.repeat(v, group, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), kr.astype(jnp.float32))
    s = s * scale
    rows = q_offset + jnp.arange(Sq)[:, None]
    cols = jnp.arange(Skv)[None, :]
    mask = jnp.ones((Sq, Skv), dtype=bool)
    if causal:
        mask &= cols <= rows
    if window > 0:
        mask &= cols > rows - window
    s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    # Fully-masked rows (can happen with tiny windows) produce NaN -> zero them.
    p = jnp.where(jnp.any(mask, -1)[None, None, :, None], p, 0.0)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, vr.astype(jnp.float32))
    return out.astype(q.dtype)


def attention_xla_chunked(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    q_offset: int = 0,
    scale: Optional[float] = None,
    chunk: int = 512,
) -> jax.Array:
    """Online-softmax attention chunked over KV via ``lax.scan`` — the
    XLA-native "flash" used on non-TPU backends (peak memory O(Sq * chunk)
    instead of O(Sq * Skv)). Mathematically identical to :func:`attention`.
    """
    B, Hq, Sq, D = q.shape
    _, Hkv, Skv, _ = k.shape
    Dv = v.shape[-1]
    group = Hq // Hkv
    scale = (D ** -0.5) if scale is None else scale
    if Skv % chunk != 0:
        pad = chunk - Skv % chunk
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        valid_len = Skv
        Skv = Skv + pad
    else:
        valid_len = Skv
    n_chunks = Skv // chunk

    qf = q.astype(jnp.float32)
    rows = q_offset + jnp.arange(Sq)[:, None]  # (Sq, 1)

    def body(carry, j):
        acc, m, l = carry
        ks = jax.lax.dynamic_slice_in_dim(k, j * chunk, chunk, axis=2)
        vs = jax.lax.dynamic_slice_in_dim(v, j * chunk, chunk, axis=2)
        ks = jnp.repeat(ks, group, axis=1).astype(jnp.float32)
        vs = jnp.repeat(vs, group, axis=1).astype(jnp.float32)
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, ks) * scale
        cols = j * chunk + jnp.arange(chunk)[None, :]
        mask = cols < valid_len
        if causal:
            mask &= cols <= rows
        if window > 0:
            mask &= cols > rows - window
        s = jnp.where(mask[None, None], s, -jnp.inf)
        m_new = jnp.maximum(m, s.max(axis=-1))
        # Guard fully-masked-so-far rows (m == -inf).
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(mask[None, None], p, 0.0)
        alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        acc = acc * alpha[..., None] + jnp.einsum("bhqk,bhkd->bhqd", p, vs)
        l = l * alpha + p.sum(axis=-1)
        return (acc, m_new, l), None

    acc0 = jnp.zeros((B, Hq, Sq, Dv), jnp.float32)
    m0 = jnp.full((B, Hq, Sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, Hq, Sq), jnp.float32)
    (acc, m, l), _ = jax.lax.scan(body, (acc0, m0, l0), jnp.arange(n_chunks))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


def _flash_fwd_chunked(q, k, v, *, causal, window, q_offset, scale, chunk):
    """Chunked online-softmax forward that also returns the row logsumexp L
    (needed by the flash backward). Shapes as attention_xla_chunked."""
    B, Hq, Sq, D = q.shape
    _, Hkv, Skv, _ = k.shape
    Dv = v.shape[-1]
    group = Hq // Hkv
    if Skv % chunk != 0:
        pad = chunk - Skv % chunk
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    valid_len = Skv  # pre-padding length
    n_chunks = k.shape[2] // chunk
    qf = q.astype(jnp.float32)
    rows = q_offset + jnp.arange(Sq)[:, None]

    def body(carry, j):
        acc, m, l = carry
        ks = jax.lax.dynamic_slice_in_dim(k, j * chunk, chunk, axis=2)
        vs = jax.lax.dynamic_slice_in_dim(v, j * chunk, chunk, axis=2)
        ks = jnp.repeat(ks, group, axis=1).astype(jnp.float32)
        vs = jnp.repeat(vs, group, axis=1).astype(jnp.float32)
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, ks) * scale
        cols = j * chunk + jnp.arange(chunk)[None, :]
        mask = cols < valid_len
        if causal:
            mask &= cols <= rows
        if window > 0:
            mask &= cols > rows - window
        s = jnp.where(mask[None, None], s, -jnp.inf)
        m_new = jnp.maximum(m, s.max(axis=-1))
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(mask[None, None], p, 0.0)
        alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        acc = acc * alpha[..., None] + jnp.einsum("bhqk,bhkd->bhqd", p, vs)
        l = l * alpha + p.sum(axis=-1)
        return (acc, m_new, l), None

    acc0 = jnp.zeros((B, Hq, Sq, Dv), jnp.float32)
    m0 = jnp.full((B, Hq, Sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, Hq, Sq), jnp.float32)
    (acc, m, l), _ = jax.lax.scan(body, (acc0, m0, l0), jnp.arange(n_chunks))
    out = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
    lse = jnp.where(l > 0, m + jnp.log(jnp.maximum(l, 1e-30)), -jnp.inf)
    return out, lse


def flash_attention_xla(
    q, k, v, *, causal=True, window=0, q_offset=0, scale=None, chunk=512
):
    """Flash attention with a custom-VJP chunked backward — the XLA-native
    equivalent of the Pallas kernel pair. The backward recomputes softmax
    weights per KV chunk from the saved (q, k, v, out, lse) instead of letting
    autodiff checkpoint the online-softmax scan carries (which costs
    O(n_chunks · B·H·Sq·D) HBM — the dominant training-memory term before
    this existed; see EXPERIMENTS.md §Perf)."""
    D = q.shape[-1]
    scale = (D ** -0.5) if scale is None else scale

    @functools.partial(jax.custom_vjp, nondiff_argnums=())
    def _attn(q, k, v):
        out, _ = _flash_fwd_chunked(
            q, k, v, causal=causal, window=window, q_offset=q_offset,
            scale=scale, chunk=chunk,
        )
        return out

    def fwd(q, k, v):
        out, lse = _flash_fwd_chunked(
            q, k, v, causal=causal, window=window, q_offset=q_offset,
            scale=scale, chunk=chunk,
        )
        return out, (q, k, v, out, lse)

    def bwd(res, dout):
        q, k, v, out, lse = res
        B, Hq, Sq, D = q.shape
        _, Hkv, Skv, Dv = v.shape
        group = Hq // Hkv
        pad = (-Skv) % chunk
        kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0))) if pad else k
        vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0))) if pad else v
        n_chunks = kp.shape[2] // chunk
        qf = q.astype(jnp.float32)
        dof = dout.astype(jnp.float32)
        of = out.astype(jnp.float32)
        rows = q_offset + jnp.arange(Sq)[:, None]
        delta = jnp.sum(dof * of, axis=-1)                       # (B,Hq,Sq)
        lse_safe = jnp.where(jnp.isfinite(lse), lse, 0.0)

        def body(dq, j):
            ks = jax.lax.dynamic_slice_in_dim(kp, j * chunk, chunk, axis=2)
            vs = jax.lax.dynamic_slice_in_dim(vp, j * chunk, chunk, axis=2)
            ksr = jnp.repeat(ks, group, axis=1).astype(jnp.float32)
            vsr = jnp.repeat(vs, group, axis=1).astype(jnp.float32)
            s = jnp.einsum("bhqd,bhkd->bhqk", qf, ksr) * scale
            cols = j * chunk + jnp.arange(chunk)[None, :]
            mask = cols < Skv
            if causal:
                mask &= cols <= rows
            if window > 0:
                mask &= cols > rows - window
            p = jnp.where(mask[None, None], jnp.exp(s - lse_safe[..., None]), 0.0)
            dp = jnp.einsum("bhqd,bhkd->bhqk", dof, vsr)
            ds = p * (dp - delta[..., None]) * scale
            dq = dq + jnp.einsum("bhqk,bhkd->bhqd", ds, ksr)
            dk_j = jnp.einsum("bhqk,bhqd->bhkd", ds, qf)
            dv_j = jnp.einsum("bhqk,bhqd->bhkd", p, dof)
            # Sum GQA group members back into the Hkv heads.
            dk_j = dk_j.reshape(B, Hkv, group, chunk, D).sum(axis=2)
            dv_j = dv_j.reshape(B, Hkv, group, chunk, Dv).sum(axis=2)
            return dq, (dk_j, dv_j)

        dq0 = jnp.zeros((B, Hq, Sq, D), jnp.float32)
        dq, (dks, dvs) = jax.lax.scan(body, dq0, jnp.arange(n_chunks))
        dk = jnp.moveaxis(dks, 0, 2).reshape(B, Hkv, n_chunks * chunk, D)[:, :, :Skv]
        dv = jnp.moveaxis(dvs, 0, 2).reshape(B, Hkv, n_chunks * chunk, Dv)[:, :, :Skv]
        return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)

    _attn.defvjp(fwd, bwd)
    return _attn(q, k, v)


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------


def rmsnorm(x: jax.Array, w: jax.Array, *, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# int8 per-row symmetric quantization (gradient compression)
# ---------------------------------------------------------------------------


def int8_quantize(x: jax.Array):
    """Per-row symmetric int8: returns (q int8 (N, d), scale f32 (N, 1))."""
    assert x.ndim == 2
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-30) / 127.0
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, scale


def int8_dequantize(q: jax.Array, scale: jax.Array, dtype=jnp.float32) -> jax.Array:
    return (q.astype(jnp.float32) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# Tiered VPN transfer cost (the paper's Eq. 2 hot loop)
# ---------------------------------------------------------------------------


def tiered_cost(
    month_cum: jax.Array,  # (T, P) cumulative monthly GB at hour start
    demand: jax.Array,     # (T, P) GB added during the hour
    bounds: jax.Array,     # (n_tiers,) upper bounds (inf -> big finite)
    rates: jax.Array,      # (n_tiers,)
) -> jax.Array:
    """(T, P) marginal tiered cost — oracle for the ``tiered_cost`` kernel."""
    from repro.core.costmodel import tier_segment

    lo = month_cum.astype(jnp.float32)[..., None]
    d = demand.astype(jnp.float32)[..., None]
    prev = jnp.concatenate([jnp.zeros((1,), bounds.dtype), bounds[:-1]])
    return jnp.sum(tier_segment(lo, d, prev, bounds) * rates, axis=-1)
