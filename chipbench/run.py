"""Run one benchmark cell and print its result line.

    python3 chipbench/run.py --workload NAME --seed N --seconds S --trace 0|1

The cell, its configuration, traffic mix and per-layer metrics are found
by name from ``BENCHMARK.json`` at the root of the checkout. Set-up
(start-up, weights, warm-up of every shape the window uses) is timed as
``setup_s``; the window then runs for ``--seconds`` with nothing left to
compile; what the window produced is then compared with the plain
reference. The numbers compared are printed, each beside its limit, as
the last lines of standard error and under ``checks`` in the result,
which is the last line of standard output. ``--trace 1`` reports the
per-layer metrics from a profiler trace of the window instead of the
end-to-end ones. Without a TPU, or with fewer chips than the cell asks
for, the run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench import harness as H
    try:
        cell = H.find_cell(args.workload)
        H.log(f"[setup] compile cache: {H.configure_jax()}")
        devs = H.require_device(cell.chips)
        H.log(f"[device] {devs[0].device_kind} x{len(devs)} "
              f"(platform {devs[0].platform})")
        ctx = H.RunContext(cell, args.seed, args.seconds, bool(args.trace),
                           T_START, devs)
        out = cell.driver.run(ctx)
        line = H.result_line(cell, out, bool(args.trace))
    except H.BenchError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    H.print_checks(out.checks)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
