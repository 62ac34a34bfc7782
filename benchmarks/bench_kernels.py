"""Tiered-cost kernel benchmark: the Pallas batched path vs the XLA twin.

`repro.kernels.tiered_cost.tiered_cost_batched` prices N heterogeneous
links' tiered VPN transfer over (N, T) volume planes with per-link padded
tier tables as array operands — the fleet engine's pricing hot loop. This
bench times it against the pure-XLA path
(`repro.core.costmodel.tiered_marginal_cost_tables`, what `plan_fleet`
compiles by default) on identical f32 operands and verifies they agree.

Off-TPU the kernel runs in INTERPRET mode (the kernel body is evaluated op
by op on CPU) — that measures correctness and gives an honest "what CPU
interpretation costs" number, NOT kernel performance; the CI gate therefore
rides on the XLA-path throughput (`xla_link_hours_per_s`), which is a real
regression signal on every backend, while the Pallas timing and the
XLA/Pallas agreement ride along in the artifact. On a TPU backend the same
CLI times the compiled kernel on real VMEM tiles (the ROADMAP "TPU batched
tiers" item; this file is its CPU-measurable half).

CLI:
  python -m benchmarks.bench_kernels           # 128 links x 8704 h
  python -m benchmarks.bench_kernels --smoke   # CI: 8 x 1024, artifact
"""
from __future__ import annotations

import argparse
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.costmodel import monthly_cumsum, tiered_marginal_cost_tables
from repro.kernels.tiered_cost import (
    DEFAULT_BLOCK_T,
    tiered_cost_batched,
    tiered_cost_scan,
    tiered_cost_scan_ref,
)

from ._util import save_rows, use_compile_cache, write_bench_artifact


def _operands(n_links: int, horizon: int, seed: int):
    """Synthetic f32 pricing operands: log-normal demand, ragged-ish padded
    tier tables (same structure the fleet stacker emits)."""
    rng = np.random.default_rng(seed)
    demand = rng.lognormal(4.0, 1.0, size=(n_links, horizon))
    K = 4
    bounds = np.sort(rng.uniform(1e3, 5e5, size=(n_links, K)), axis=1)
    bounds[:, -1] = 1e30  # top tier unbounded (PAD_BOUND convention)
    rates = np.sort(rng.uniform(0.01, 0.12, size=(n_links, K)), axis=1)[:, ::-1]
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    d = f32(demand)
    cum = monthly_cumsum(d, 730)
    return cum, d, f32(bounds), f32(np.ascontiguousarray(rates))


def _time(fn, *args, repeats: int) -> float:
    out = jax.block_until_ready(fn(*args))
    del out
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return min(times)


def run(n_links: int = 128, horizon: int = 8704, *, repeats: int = 5, seed: int = 0):
    assert horizon % DEFAULT_BLOCK_T == 0, (
        f"horizon must be a multiple of the kernel block ({DEFAULT_BLOCK_T})"
    )
    cum, d, bounds, rates = _operands(n_links, horizon, seed)
    interpret = jax.default_backend() != "tpu"

    xla = jax.jit(tiered_marginal_cost_tables)
    pallas = jax.jit(
        lambda c, dd, b, r: tiered_cost_batched(c, dd, b, r, interpret=interpret)
    )

    ref = np.asarray(xla(cum, d, bounds, rates))
    got = np.asarray(pallas(cum, d, bounds, rates))
    scale = max(float(np.abs(ref).max()), 1e-6)
    max_rel_err = float(np.abs(got - ref).max() / scale)
    assert max_rel_err < 1e-5, (
        f"Pallas kernel diverged from the XLA path: max rel err {max_rel_err:.2e}"
    )

    xla_s = _time(xla, cum, d, bounds, rates, repeats=repeats)
    pallas_s = _time(pallas, cum, d, bounds, rates, repeats=repeats)

    # Chunked streaming variant: K=24 inner hours, tier carry in VMEM.
    # The 730 h billing month never resets inside a 24 h chunk here; the
    # kernel's reset lane is exercised by tests/test_kernels.py.
    chunk_k = 24
    cum0 = cum[:, 0]
    d_chunk = jax.lax.slice(d, (0, 0), (n_links, chunk_k))
    reset = jnp.zeros(chunk_k, jnp.int32)
    scan_pallas = jax.jit(
        lambda c0, dd, b, r, rs: tiered_cost_scan(
            c0, dd, b, r, rs, interpret=interpret
        )
    )
    scan_xla = jax.jit(tiered_cost_scan_ref)
    sc_got, _ = scan_pallas(cum0, d_chunk, bounds, rates, reset)
    sc_ref, _ = scan_xla(cum0, d_chunk, bounds, rates, reset)
    scan_rel_err = float(
        np.abs(np.asarray(sc_got) - np.asarray(sc_ref)).max()
        / max(float(np.abs(np.asarray(sc_ref)).max()), 1e-6)
    )
    assert scan_rel_err < 1e-5, (
        f"scan kernel diverged from the XLA scan twin: {scan_rel_err:.2e}"
    )
    scan_pallas_s = _time(scan_pallas, cum0, d_chunk, bounds, rates, reset,
                          repeats=repeats)
    scan_xla_s = _time(scan_xla, cum0, d_chunk, bounds, rates, reset,
                       repeats=repeats)

    link_hours = n_links * horizon
    scan_link_hours = n_links * chunk_k
    rows = [{
        "links": n_links,
        "horizon": horizon,
        "backend": jax.default_backend(),
        "pallas_interpret": interpret,
        "xla_s": xla_s,
        "pallas_s": pallas_s,
        "xla_link_hours_per_s": link_hours / xla_s,
        "pallas_link_hours_per_s": link_hours / pallas_s,
        "pallas_vs_xla_speedup": xla_s / pallas_s,
        "max_rel_err": max_rel_err,
        "scan_chunk_k": chunk_k,
        "scan_xla_s": scan_xla_s,
        "scan_pallas_s": scan_pallas_s,
        "scan_xla_link_hours_per_s": scan_link_hours / scan_xla_s,
        "scan_pallas_link_hours_per_s": scan_link_hours / scan_pallas_s,
        "scan_max_rel_err": scan_rel_err,
    }]
    save_rows("kernels", rows)
    r = rows[0]
    derived = (
        f"xla={r['xla_link_hours_per_s']:.3g} lh/s "
        f"pallas={r['pallas_link_hours_per_s']:.3g} lh/s "
        f"(interpret={interpret}) err={max_rel_err:.1e}"
    )
    return rows, derived


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--links", type=int, default=128)
    ap.add_argument("--horizon", type=int, default=8704)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--smoke", action="store_true",
        help="CI mode: 8 links x 1024 h (interpret-mode kernel), artifact",
    )
    args = ap.parse_args()
    if args.smoke:
        args.links, args.horizon, args.repeats = 8, 1024, 3
    rows, derived = run(
        args.links, args.horizon, repeats=args.repeats, seed=args.seed
    )
    r = rows[0]
    print(
        f"kernels: {r['links']} links x {r['horizon']} h tiered pricing -> "
        f"XLA {r['xla_s'] * 1e3:.2f} ms ({r['xla_link_hours_per_s']:.3g} "
        f"link-hours/s), Pallas {r['pallas_s'] * 1e3:.2f} ms "
        f"({'interpret' if r['pallas_interpret'] else 'compiled'}), "
        f"max rel err {r['max_rel_err']:.1e}"
    )
    print(
        f"kernels: K={r['scan_chunk_k']} chunked scan -> "
        f"XLA {r['scan_xla_s'] * 1e3:.2f} ms, Pallas "
        f"{r['scan_pallas_s'] * 1e3:.2f} ms, "
        f"max rel err {r['scan_max_rel_err']:.1e}"
    )
    print(derived)
    if args.smoke:
        print("artifact:", write_bench_artifact("kernels", rows))


if __name__ == "__main__":
    main()
