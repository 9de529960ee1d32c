"""Where a sim cell's grid calls spend their host time, by the program's
own phases (``repro.tracing`` spans), on the chip, in one process.

    python3 chipbench/phases.py --workload NAME --seeds N1,N2,... \\
        [--seconds S] [--trace-calls K] [--out FILE]

For each seed, a whole run of the cell (``--seconds`` of window, as the
benchmark runs it, untraced) and then, from the program's ring of spans,
the warm-up calls' ``sim.dispatch`` seconds (their compile or
compile-cache load) and each window call's split into ``sim.lower``,
``sim.dispatch``, ``sim.wait``, ``sim.summarize`` and the rest of
``sim.grid``: the median and the three slowest calls. Then, with
``--trace-calls``, that many further calls under the profiler: the
device's idle time inside each program phase of each call, and its idle
gaps of a millisecond or more, each named by the innermost program span
around its middle. Prints one JSON line per run and per traced call,
and the whole list to ``--out``. Needs a TPU.
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

PHASES = ("sim.lower", "sim.dispatch", "sim.wait", "sim.summarize")


def split(ring: list) -> list:
    """Per call (root ``sim.grid``) in order: phase -> ms, and ``other``
    for the root's own time outside its phases."""
    calls = {}
    for r in ring:
        calls.setdefault(r.call, []).append(r)
    out = []
    for spans in calls.values():
        root = next((r for r in spans if r.parent is None
                     and r.name == "sim.grid"), None)
        if root is None:
            continue
        row = {p: 1e3 * sum(r.end_s - r.start_s for r in spans
                            if r.name == p) for p in PHASES}
        row["call"] = 1e3 * (root.end_s - root.start_s)
        row["other"] = row["call"] - sum(row[p] for p in PHASES)
        out.append((root.start_s, row))
    return [row for _, row in sorted(out, key=lambda x: x[0])]


def one_run(cell, seed: int, seconds: float, devs) -> dict:
    from chipbench import harness as H
    from repro import tracing
    mark = max((r.id for r in tracing.snapshot()["ring"]), default=0)
    ctx = H.RunContext(cell, seed, seconds, False, time.perf_counter(), devs)
    out = cell.driver.run(ctx)
    calls = split([r for r in tracing.snapshot()["ring"] if r.call > mark])
    n = out.record["counters"]["grid_calls"]
    warm, window = calls[:-n], calls[-n:]
    slow = sorted(window, key=lambda c: -c["call"])[:3]
    return {
        "seed": seed, "correct": out.correct, **out.end_to_end,
        "warmup_dispatch_s": [round(c["sim.dispatch"] / 1e3, 3)
                              for c in warm],
        "median_ms": {k: statistics.median(c[k] for c in window)
                      for k in window[0]},
        "slowest_ms": [{k: round(v, 2) for k, v in c.items()}
                       for c in slow],
    }


def load(path: str):
    """Device operation intervals and the host's ``repro.*`` spans of a
    trace, in seconds."""
    from jax.profiler import ProfileData

    from chipbench import trace as T
    devices, spans = {}, []
    for plane in ProfileData.from_file(path).planes:
        lines = {ln.name: ln for ln in plane.lines}
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            line = next((lines[n] for n in T.OP_LINES if n in lines), None)
            if line is not None:
                devices[plane.name] = [
                    (ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns)
                     * 1e-9) for ev in line.events if ev.duration_ns > 0]
        elif plane.name.startswith("/host:"):
            spans += [(ev.name, ev.start_ns * 1e-9,
                       (ev.start_ns + ev.duration_ns) * 1e-9)
                      for ln in lines.values() for ev in ln.events
                      if ev.name.startswith("repro.")]
    return devices, spans


def idle_by_phase(devices: dict, spans: list, min_gap_s: float = 1e-3):
    """Per traced call: its busy and idle ms, the idle ms inside each
    phase span, and the gaps of ``min_gap_s`` or more, each with the
    innermost span around its middle."""
    from chipbench import trace as T
    merged = T.union(iv for evs in devices.values() for iv in evs)
    cov = T.Covered(merged)
    rows = []
    for root in sorted(s for s in spans if s[0] == "repro.sim.grid"):
        _, a, b = root
        inside = [s for s in spans if a <= s[1] and s[2] <= b]
        idle = {}
        for name, x, y in inside:
            idle[name] = idle.get(name, 0.0) + 1e3 * ((y - x) - cov(x, y))
        gaps = []
        for x, y in T.gaps(merged, a, b):
            if y - x >= min_gap_s:
                mid = (x + y) / 2
                around = min((s for s in inside if s[1] <= mid <= s[2]),
                             key=lambda s: s[2] - s[1])
                gaps.append([around[0], round(1e3 * (y - x), 3)])
        rows.append({"call_ms": 1e3 * (b - a), "busy_ms": 1e3 * cov(a, b),
                     "idle_ms": idle,
                     "gaps_ms": sorted(gaps, key=lambda g: -g[1])})
    return rows


def traced_calls(cell, seed: int, k: int) -> list:
    import shutil
    import tempfile

    import jax

    from chipbench import trace as T
    from repro.core.sim.engine import Workload
    drv, cfg = cell.driver, cell.config
    topos = drv.topologies(cfg)
    wl = Workload(cfg["ncs_max"], cfg["cs"], cfg["steps_per_call"])
    lock = cfg["locks"][0]
    eng = drv.program_engine(lock, cfg)
    seeds = drv.seed_stream(seed, 3, cfg["seeds_per_topology"])

    def call():
        eng.grid(seeds=next(seeds), topologies=topos, workloads=[wl],
                 threads=[cfg["threads"]])
    call()                                  # compile or cache load
    d = tempfile.mkdtemp(prefix="chipbench_phases_")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            for _ in range(k):
                call()
        finally:
            jax.profiler.stop_trace()
        devices, spans = load(T.find_xplane(d))
    finally:
        shutil.rmtree(d, ignore_errors=True)
    return [{"lock": lock, **row} for row in idle_by_phase(devices, spans)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--trace-calls", type=int, default=0)
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    from chipbench import harness as H
    cell = H.find_cell(args.workload)
    H.configure_jax()
    devs = H.require_device(cell.chips)
    seeds = [int(s) for s in args.seeds.split(",")]
    rows = []
    for seed in seeds:
        rows.append(one_run(cell, seed, args.seconds, devs))
        print(json.dumps(rows[-1]), flush=True)
    if args.trace_calls:
        for row in traced_calls(cell, seeds[0], args.trace_calls):
            rows.append(row)
            print(json.dumps(row), flush=True)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(rows, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
