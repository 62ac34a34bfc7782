"""Benchmark driver — one module per paper table/figure (DESIGN.md §5).

Prints ``name,us_per_call,derived`` CSV per module. Heavy sweeps accept a
REPRO_BENCH_FAST=1 env to shrink horizons (CI smoke); the full run matches
the paper's settings.
"""
from __future__ import annotations

import os
import sys
import traceback

from . import (
    bench_azure_intercont,
    bench_bursty,
    bench_constant,
    bench_fleet,
    bench_gateway,
    bench_kernels,
    bench_measurements,
    bench_mirage,
    bench_planner,
    bench_policy,
    bench_puffer,
    bench_roofline,
    bench_runtime,
    bench_sensitivity,
    bench_topology,
)
from ._util import fmt_csv, timed, use_compile_cache

FAST = os.environ.get("REPRO_BENCH_FAST", "0") == "1"

BENCHES = [
    ("measurements_fig2_3_4", lambda: bench_measurements.run(repeats=3 if FAST else 10)),
    ("mirage_fig6_7", lambda: bench_mirage.run(horizon_days=60 if FAST else 730)),
    ("azure_intercont_fig8_9", lambda: bench_azure_intercont.run(horizon_days=60 if FAST else 365)),
    ("puffer_fig10", lambda: bench_puffer.run(horizon_days=60 if FAST else 365)),
    ("constant_fig11", lambda: bench_constant.run(horizon=2000 if FAST else 8760)),
    ("bursty_fig12", lambda: bench_bursty.run(horizon=2000 if FAST else 8760)),
    ("sensitivity_fig13_14", lambda: bench_sensitivity.run(horizon=2000 if FAST else 8760)),
    ("planner_e12", lambda: bench_planner.run(hours=2000 if FAST else 8760)),
    ("fleet_portfolio", lambda: bench_fleet.run(
        16 if FAST else 128, 2000 if FAST else 8760,
        repeats=2 if FAST else 5, verify_links=None if FAST else 16,
    )),
    ("topology_multipair", lambda: bench_topology.run(
        16 if FAST else 96, 2000 if FAST else 8760,
        n_facilities=3 if FAST else 4, repeats=2 if FAST else 5,
    )),
    ("policy_compare", lambda: bench_policy.run(
        8 if FAST else 48, 1200 if FAST else 8760,
        repeats=2 if FAST else 3, train_steps=120 if FAST else 300,
    )),
    ("runtime_streaming", lambda: bench_runtime.run(
        512 if FAST else 2048, 600 if FAST else 3000,
        history=300 if FAST else 600,
    )),
    ("gateway_multitenant", lambda: bench_gateway.run(
        64 if FAST else 256, 16 if FAST else 32, 160 if FAST else 400,
    )),
    ("kernels_tiered_cost", lambda: bench_kernels.run(
        8 if FAST else 128, 1024 if FAST else 8704,
        repeats=2 if FAST else 5,
    )),
    ("roofline_e10", lambda: bench_roofline.run()),
]


def main() -> None:
    use_compile_cache()
    print("name,us_per_call,derived")
    failures = 0
    for name, fn in BENCHES:
        try:
            (rows, derived), us = timed(fn)
            print(fmt_csv(name, us, derived), flush=True)
        except Exception as e:
            failures += 1
            print(f"{name},0,ERROR:{type(e).__name__}:{e}", flush=True)
            traceback.print_exc(file=sys.stderr)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
