"""Run one cell of the benchmark once, and print its result as one JSON line.

    python3 bench/run.py --workload fleet2048.stream_k24 --seed 7 --seconds 20 --trace 0

From the root of a checkout. The run builds its scenario from ``--seed``,
sets up and warms the program, measures for ``--seconds``, compares what the
window produced with the benchmark's own numpy reference, and prints the
cell's end-to-end metrics (``--trace 0``) or, from a profiler trace of the
window, its per-layer metrics (``--trace 1``). The last lines of standard
error give each number compared beside its limit. Without an accelerator,
or with fewer chips than the cell asks for, it exits 3 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

    from bench.harness import NoAccelerator, Registry, report_checks, run_cell

    try:
        result = run_cell(Registry(ROOT), args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start=T_START)
    except NoAccelerator as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    report_checks(result)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
