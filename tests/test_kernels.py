"""Per-kernel allclose validation against the pure-jnp oracles (ref.py).

Every Pallas kernel is executed in interpret mode (kernel body runs on CPU)
and swept over shapes/dtypes per the deliverable contract.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.int8_quant import int8_dequantize, int8_quantize
from repro.kernels.rmsnorm import rmsnorm as rmsnorm_kernel
from repro.kernels.tiered_cost import tiered_cost as tiered_cost_kernel


def _rand(rng, shape, dtype):
    x = rng.standard_normal(shape).astype(np.float32)
    return jnp.asarray(x, dtype=dtype)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

ATT_SHAPES = [
    # (B, Hq, Hkv, Sq, Skv, D)
    (1, 2, 2, 128, 128, 64),     # MHA square
    (2, 4, 2, 128, 256, 64),     # GQA, rectangular
    (1, 8, 1, 256, 256, 128),    # MQA
    (1, 2, 2, 384, 384, 32),     # 3-block
]


@pytest.mark.parametrize("shape", ATT_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_matches_ref(shape, dtype, causal):
    B, Hq, Hkv, Sq, Skv, D = shape
    if causal and Sq > Skv:
        pytest.skip("causal requires Sq <= Skv here")
    rng = np.random.default_rng(0)
    q = _rand(rng, (B, Hq, Sq, D), dtype)
    k = _rand(rng, (B, Hkv, Skv, D), dtype)
    v = _rand(rng, (B, Hkv, Skv, D), dtype)
    q_offset = Skv - Sq if causal else 0
    out = flash_attention(q, k, v, causal=causal, q_offset=q_offset, interpret=True)
    want = ref.attention(q, k, v, causal=causal, q_offset=q_offset)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32), atol=tol, rtol=tol
    )


@pytest.mark.parametrize("window", [128, 256])
def test_flash_attention_sliding_window(window):
    rng = np.random.default_rng(1)
    q = _rand(rng, (1, 2, 384, 64), jnp.float32)
    k = _rand(rng, (1, 2, 384, 64), jnp.float32)
    v = _rand(rng, (1, 2, 384, 64), jnp.float32)
    out = flash_attention(q, k, v, causal=True, window=window, interpret=True)
    want = ref.attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5, rtol=2e-5)


def test_flash_attention_decode_offset():
    """Prefill/decode equivalence: last-token attention with q_offset equals
    the last row of full attention."""
    rng = np.random.default_rng(2)
    S = 256
    q = _rand(rng, (1, 4, S, 64), jnp.float32)
    k = _rand(rng, (1, 4, S, 64), jnp.float32)
    v = _rand(rng, (1, 4, S, 64), jnp.float32)
    full = ref.attention(q, k, v, causal=True)
    last_q = q[:, :, S - 128 :, :]
    out = flash_attention(last_q, k, v, causal=True, q_offset=S - 128, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(full[:, :, S - 128 :]), atol=2e-5, rtol=2e-5
    )


def test_chunked_xla_matches_naive():
    """The non-TPU production path is itself validated against the oracle."""
    rng = np.random.default_rng(3)
    q = _rand(rng, (2, 4, 100, 64), jnp.float32)
    k = _rand(rng, (2, 2, 260, 64), jnp.float32)
    v = _rand(rng, (2, 2, 260, 64), jnp.float32)
    for window in (0, 64):
        out = ref.attention_xla_chunked(
            q, k, v, causal=True, window=window, q_offset=160, chunk=64
        )
        want = ref.attention(q, k, v, causal=True, window=window, q_offset=160)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5, rtol=1e-5)


def test_ops_attention_dispatch_cpu():
    rng = np.random.default_rng(4)
    q = _rand(rng, (1, 2, 64, 32), jnp.float32)
    k = _rand(rng, (1, 2, 64, 32), jnp.float32)
    v = _rand(rng, (1, 2, 64, 32), jnp.float32)
    out = ops.attention(q, k, v, causal=True)
    want = ref.attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5, rtol=1e-5)


def test_ops_attention_interpret_pad_path():
    """force_interpret routes through the Pallas kernel with q padding."""
    rng = np.random.default_rng(5)
    q = _rand(rng, (1, 2, 200, 64), jnp.float32)   # 200 % 128 != 0
    k = _rand(rng, (1, 2, 256, 64), jnp.float32)
    v = _rand(rng, (1, 2, 256, 64), jnp.float32)
    with ops.force_interpret():
        out = ops.attention(q, k, v, causal=True, q_offset=56)
    want = ref.attention(q, k, v, causal=True, q_offset=56)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# rmsnorm
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape", [(128, 512), (256, 1024), (2, 128, 384)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_matches_ref(shape, dtype):
    rng = np.random.default_rng(6)
    x = _rand(rng, shape, dtype)
    w = _rand(rng, shape[-1:], dtype)
    out = rmsnorm_kernel(x, w, interpret=True)
    want = ref.rmsnorm(x, w)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32), atol=tol, rtol=tol
    )


# ---------------------------------------------------------------------------
# int8 quant
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape", [(256, 128), (512, 1024)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_int8_roundtrip_matches_ref(shape, dtype):
    rng = np.random.default_rng(7)
    x = _rand(rng, shape, dtype) * 3.0
    q, s = int8_quantize(x, interpret=True)
    qr, sr = ref.int8_quantize(x)
    # Exact equality up to rounding ties: a 1-ULP scale difference can flip
    # values sitting exactly at x/scale = n + 0.5, so allow |Δq| <= 1 on a
    # vanishing fraction of entries.
    dq = np.abs(np.asarray(q, np.int32) - np.asarray(qr, np.int32))
    assert dq.max() <= 1
    assert (dq != 0).mean() < 1e-3
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-6)
    y = int8_dequantize(q, s, interpret=True)
    yr = ref.int8_dequantize(qr, sr)
    # Tie-flipped entries differ by exactly one quantization step.
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(yr), atol=float(np.asarray(s).max()) * 1.01
    )
    # Quantization error bound: |x - deq| <= scale/2 per element.
    err = np.abs(np.asarray(x, np.float32) - np.asarray(y))
    bound = np.asarray(s) * 0.5 + 1e-6
    assert (err <= bound).all()


def test_int8_quant_zero_rows():
    x = jnp.zeros((256, 64), jnp.float32)
    q, s = int8_quantize(x, interpret=True)
    assert not np.isnan(np.asarray(s)).any()
    np.testing.assert_array_equal(np.asarray(q), 0)


# ---------------------------------------------------------------------------
# tiered cost
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("T,P", [(512, 1), (1024, 4), (8704, 8)])
def test_tiered_cost_matches_ref_and_core(T, P):
    from repro.core.costmodel import tiered_marginal_cost_np
    from repro.core.pricing import AWS_EGRESS_INTERNET as tier

    rng = np.random.default_rng(8)
    d = rng.uniform(0, 500, size=(T, P)).astype(np.float32)
    cum = (np.cumsum(d, axis=0) - d).astype(np.float32)
    out = tiered_cost_kernel(
        jnp.asarray(cum), jnp.asarray(d), tier.bounds_gb, tier.rates, interpret=True
    )
    # Tight against the same-precision (f32) jnp oracle...
    want32 = ref.tiered_cost(
        jnp.asarray(cum), jnp.asarray(d),
        jnp.asarray([b if np.isfinite(b) else 1e30 for b in tier.bounds_gb], jnp.float32),
        jnp.asarray(tier.rates, jnp.float32),
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(want32), rtol=1e-6, atol=1e-6)
    # ...and loose against the float64 core reference (f32 resolution at
    # month-cumulative volumes ~2e6 GB is ~0.25 GB -> cents-level cost noise).
    want64 = tiered_marginal_cost_np(tier, cum, d)
    np.testing.assert_allclose(np.asarray(out), want64, atol=2e-2)


def test_ops_tiered_cost_dispatch():
    from repro.core.pricing import GCP_EGRESS_PREMIUM as tier

    rng = np.random.default_rng(9)
    d = jnp.asarray(rng.uniform(0, 100, size=(300, 2)), jnp.float32)  # 300 % 512 != 0
    cum = jnp.cumsum(d, axis=0) - d
    out = ops.tiered_cost(cum, d, tier.bounds_gb, tier.rates)
    want = ref.tiered_cost(
        cum, d,
        jnp.asarray([b if np.isfinite(b) else 1e30 for b in tier.bounds_gb], jnp.float32),
        jnp.asarray(tier.rates, jnp.float32),
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-6)


def test_tiered_cost_batched_matches_ref():
    """Batched (N, T) path with PER-LINK tier tables as array operands."""
    from repro.core.pricing import (
        AWS_EGRESS_INTERNET,
        AZURE_EGRESS_INTERNET,
        GCP_EGRESS_PREMIUM,
    )
    from repro.kernels.tiered_cost import tiered_cost_batched, tiered_cost_batched_ref

    tiers = [GCP_EGRESS_PREMIUM, AWS_EGRESS_INTERNET, AZURE_EGRESS_INTERNET]
    K = max(len(t.bounds_gb) for t in tiers)
    bounds = np.full((3, K), 1e30, np.float32)
    rates = np.zeros((3, K), np.float32)
    for i, t in enumerate(tiers):
        bounds[i, : len(t.bounds_gb)] = [
            b if np.isfinite(b) else 1e30 for b in t.bounds_gb
        ]
        rates[i, : len(t.rates)] = t.rates

    rng = np.random.default_rng(4)
    d = rng.uniform(0, 200, size=(3, 256)).astype(np.float32)
    cum = (np.cumsum(d, axis=1) - d).astype(np.float32)
    out = tiered_cost_batched(
        jnp.asarray(cum), jnp.asarray(d), jnp.asarray(bounds), jnp.asarray(rates),
        block_t=128, interpret=True,
    )
    want = tiered_cost_batched_ref(
        jnp.asarray(cum), jnp.asarray(d), jnp.asarray(bounds), jnp.asarray(rates)
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-6, atol=1e-6)
    # Cross-check one row against the scalar float64 tier engine.
    from repro.core.costmodel import tiered_marginal_cost_np

    want64 = tiered_marginal_cost_np(tiers[1], cum[1], d[1])
    np.testing.assert_allclose(np.asarray(out)[1], want64, atol=2e-2)


@pytest.mark.parametrize("K", [1, 7, 24])
def test_tiered_cost_scan_matches_ref(K):
    """Chunked K-hour kernel: VMEM tier carry vs the lax.scan oracle."""
    from repro.kernels.tiered_cost import (
        tiered_cost_batched_ref,
        tiered_cost_scan,
        tiered_cost_scan_ref,
    )

    rng = np.random.default_rng(11)
    N, Kt = 16, 4
    cum0 = jnp.asarray(rng.uniform(0, 5e4, N), jnp.float32)
    d = jnp.asarray(rng.uniform(0, 200, (N, K)), jnp.float32)
    b = np.sort(rng.uniform(1e3, 2e5, (N, Kt)), axis=1)
    b[:, -1] = 1e30
    bounds = jnp.asarray(b, jnp.float32)
    rates = jnp.asarray(rng.uniform(0.01, 0.2, (N, Kt)), jnp.float32)
    reset = np.zeros(K, np.int32)
    reset[K // 2] = 1  # billing-month boundary inside the chunk
    reset = jnp.asarray(reset)

    out, cum_out = tiered_cost_scan(cum0, d, bounds, rates, reset, interpret=True)
    want, cum_want = tiered_cost_scan_ref(cum0, d, bounds, rates, reset)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(cum_out), np.asarray(cum_want), rtol=1e-6)

    # Chaining two half-chunks reproduces the full chunk bit-for-bit.
    if K > 1:
        h = K // 2
        cA, cumA = tiered_cost_scan(cum0, d[:, :h], bounds, rates, reset[:h], interpret=True)
        cB, _ = tiered_cost_scan(cumA, d[:, h:], bounds, rates, reset[h:], interpret=True)
        np.testing.assert_array_equal(
            np.concatenate([np.asarray(cA), np.asarray(cB)], axis=1), np.asarray(out)
        )

    # With no resets, the scan path equals the prefix-sum batched oracle.
    zero = jnp.zeros(K, jnp.int32)
    out0, _ = tiered_cost_scan(cum0, d, bounds, rates, zero, interpret=True)
    pref = cum0[:, None] + jnp.concatenate(
        [jnp.zeros((N, 1), jnp.float32), jnp.cumsum(d, axis=1)[:, :-1]], axis=1
    )
    want0 = tiered_cost_batched_ref(pref, d, bounds, rates)
    # Looser: the batched oracle's f32 cumsum reassociates the prefix adds.
    np.testing.assert_allclose(np.asarray(out0), np.asarray(want0), rtol=1e-4, atol=1e-3)


def test_ops_tiered_cost_scan_dispatch():
    """Off-TPU and outside ``force_interpret`` the ops wrapper takes the XLA
    twin."""
    from repro.kernels.tiered_cost import tiered_cost_scan_ref

    rng = np.random.default_rng(12)
    N, K, Kt = 5, 6, 3
    cum0 = jnp.asarray(rng.uniform(0, 100, N), jnp.float32)
    d = jnp.asarray(rng.uniform(0, 50, (N, K)), jnp.float32)
    b = np.sort(rng.uniform(50, 500, (N, Kt)), axis=1)
    b[:, -1] = 1e30
    bounds = jnp.asarray(b, jnp.float32)
    rates = jnp.asarray(rng.uniform(0.01, 0.2, (N, Kt)), jnp.float32)
    reset = jnp.zeros(K, jnp.int32)
    out, cum_out = ops.tiered_cost_scan(cum0, d, bounds, rates, reset)
    want, cum_want = tiered_cost_scan_ref(cum0, d, bounds, rates, reset)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(cum_out), np.asarray(cum_want), rtol=1e-6)


def test_ops_tiered_cost_kernels_pad_unaligned_shapes():
    """Shapes that fit no block still take the kernels (padded to whole
    blocks), and agree with the XLA twins."""
    from repro.core.pricing import GCP_EGRESS_PREMIUM as tier
    from repro.kernels.tiered_cost import tiered_cost_scan_ref

    rng = np.random.default_rng(13)
    d = jnp.asarray(rng.uniform(0, 100, size=(300, 3)), jnp.float32)
    cum = jnp.cumsum(d, axis=0) - d
    N, K, Kt = 5, 6, 3
    cum0 = jnp.asarray(rng.uniform(0, 100, N), jnp.float32)
    dd = jnp.asarray(rng.uniform(0, 50, (N, K)), jnp.float32)
    b = np.sort(rng.uniform(50, 500, (N, Kt)), axis=1)
    b[:, -1] = 1e30
    bounds = jnp.asarray(b, jnp.float32)
    rates = jnp.asarray(rng.uniform(0.01, 0.2, (N, Kt)), jnp.float32)
    reset = jnp.asarray([0, 0, 1, 0, 0, 0], jnp.int32)
    with ops.force_interpret():
        out = ops.tiered_cost(cum, d, tier.bounds_gb, tier.rates)
        sc, sc_cum = ops.tiered_cost_scan(cum0, dd, bounds, rates, reset)
    want = ref.tiered_cost(
        cum, d,
        jnp.asarray([b if np.isfinite(b) else 1e30 for b in tier.bounds_gb], jnp.float32),
        jnp.asarray(tier.rates, jnp.float32),
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-6)
    want_sc, want_cum = tiered_cost_scan_ref(cum0, dd, bounds, rates, reset)
    np.testing.assert_allclose(np.asarray(sc), np.asarray(want_sc), rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(sc_cum), np.asarray(want_cum), rtol=1e-6)
