"""Backend-dispatching jit'd wrappers for every kernel.

Dispatch policy (DESIGN.md §3):

* ``tpu`` backend        -> compiled Pallas kernel (the production path).
* anything else          -> pure-jnp reference (XLA-native; the dry-run path —
                            Pallas-TPU cannot lower on the CPU host devices).
* ``force_interpret()``  -> Pallas kernel in interpret mode (CPU execution of
                            the *kernel body*; used by tests to validate the
                            kernel logic itself without a TPU).

For attention the non-TPU path is :func:`ref.attention_xla_chunked` (online
softmax via lax.scan) rather than the naive oracle, so compiled dry-run HLO
keeps flash-attention's O(S·chunk) memory shape — crucial for the 32k/500k
shape cells.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional

import jax
import jax.numpy as jnp

from . import flash_attention as _fa
from . import int8_quant as _i8
from . import ref
from . import rmsnorm as _rn
from . import tiered_cost as _tc

_state = threading.local()


def _interpret_forced() -> bool:
    return getattr(_state, "force_interpret", False)


@contextlib.contextmanager
def force_interpret():
    """Context manager: route ops through Pallas interpret mode (tests)."""
    prev = _interpret_forced()
    _state.force_interpret = True
    try:
        yield
    finally:
        _state.force_interpret = prev


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pad_to(x: jax.Array, axis: int, multiple: int):
    size = x.shape[axis]
    rem = size % multiple
    if rem == 0:
        return x, 0
    pad = multiple - rem
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), pad


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------


def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    q_offset: int = 0,
    scale: Optional[float] = None,
) -> jax.Array:
    """Dispatching attention: Pallas flash on TPU, chunked-XLA elsewhere.

    Decode steps (Sq small, e.g. 1) always use the XLA path — a (1, Skv)
    score row is a matvec, where a blocked kernel only adds overhead.
    """
    Sq = q.shape[2]
    if _interpret_forced() or (_on_tpu() and Sq >= _fa.DEFAULT_BLOCK_Q):
        interpret = not _on_tpu()
        bq = min(_fa.DEFAULT_BLOCK_Q, Sq)
        qp, pad_q = _pad_to(q, 2, bq)
        kp, pad_k = _pad_to(k, 2, _fa.DEFAULT_BLOCK_K)
        vp, _ = _pad_to(v, 2, _fa.DEFAULT_BLOCK_K)
        if pad_k:
            # Padded KV columns must be masked out: with causal masking any
            # padded col > valid rows is masked iff rows < Skv; enforce via
            # an explicit window-free guard by masking padded keys to -inf
            # through a huge negative bias on k... simplest: rely on causal
            # (rows < Skv_valid <= padded col). Non-causal calls require
            # divisible Skv.
            assert causal, "non-causal flash path requires Skv % block_k == 0"
            assert q_offset + q.shape[2] <= k.shape[2]
        out = _fa.flash_attention(
            qp, kp, vp,
            causal=causal, window=window, q_offset=q_offset, scale=scale,
            block_q=bq, interpret=interpret,
        )
        return out[:, :, :Sq] if pad_q else out
    return ref.flash_attention_xla(
        q, k, v, causal=causal, window=window, q_offset=q_offset, scale=scale
    )


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------


def rmsnorm(x: jax.Array, w: jax.Array, *, eps: float = 1e-6) -> jax.Array:
    n_rows = 1
    for s in x.shape[:-1]:
        n_rows *= s
    usable = _interpret_forced() or _on_tpu()
    if usable and n_rows % _rn.DEFAULT_BLOCK_ROWS == 0:
        return _rn.rmsnorm(x, w, eps=eps, interpret=not _on_tpu())
    return ref.rmsnorm(x, w, eps=eps)


# ---------------------------------------------------------------------------
# int8 quantization
# ---------------------------------------------------------------------------


def int8_quantize(x: jax.Array):
    usable = _interpret_forced() or _on_tpu()
    if usable and x.ndim == 2 and x.shape[0] % _i8.DEFAULT_BLOCK_ROWS == 0:
        return _i8.int8_quantize(x, interpret=not _on_tpu())
    return ref.int8_quantize(x)


def int8_dequantize(q: jax.Array, scale: jax.Array, dtype=jnp.float32):
    usable = _interpret_forced() or _on_tpu()
    if usable and q.ndim == 2 and q.shape[0] % _i8.DEFAULT_BLOCK_ROWS == 0:
        return _i8.int8_dequantize(q, scale, dtype=dtype, interpret=not _on_tpu())
    return ref.int8_dequantize(q, scale, dtype=dtype)


# ---------------------------------------------------------------------------
# Tiered cost
# ---------------------------------------------------------------------------


def tiered_cost(month_cum, demand, bounds, rates):
    """(T, P) tiered cost. Any shape takes the kernel wherever it is usable
    (the kernel pads to whole blocks itself), so a TPU never drops to the
    reference for an unaligned shape."""
    if _interpret_forced() or _on_tpu():
        return _tc.tiered_cost(
            month_cum, demand, tuple(bounds), tuple(rates), interpret=not _on_tpu()
        )
    import numpy as np

    b = jnp.asarray([x if np.isfinite(x) else 1e30 for x in bounds], jnp.float32)
    r = jnp.asarray(list(rates), jnp.float32)
    return ref.tiered_cost(month_cum, demand, b, r)


def tiered_cost_scan(cum0, demand, bounds, rates, reset):
    """Chunked K-hour tiered pricing; returns ``(costs (N, K), cum_out (N,))``."""
    if _interpret_forced() or _on_tpu():
        return _tc.tiered_cost_scan(
            cum0, demand, bounds, rates, reset, interpret=not _on_tpu()
        )
    return _tc.tiered_cost_scan_ref(cum0, demand, bounds, rates, reset)
