"""Readings behind a cell's limits and rate, on the chip, in one process.

    python3 chipbench/readings.py control --workload NAME --seeds N \\
        [--seconds S] [--quant int8,fp8] [--out FILE]
    python3 chipbench/readings.py knee --workload NAME --rates R1,R2,... \\
        [--seconds S] [--out FILE]

``--config C --traffic T`` names a cell that is not in
``BENCHMARK.json`` yet, by its configuration and traffic files.

``control`` reads, for each of ``N`` seeds drawn from the workload's
name, the numbers a run compares and the control's: for a cell of a
serve kind, a short window at the cell's own load, then over the same
sample of finished requests the program's logit error against the
configuration's reference (and its served-token gaps), and the same
readings of the reference computed with lower-precision weights; for a
``lock_sim`` cell, whole runs with the control (the reference with a
reciprocating lock whose doorway lets a second thread in) in the
program's place, and the numbers they compare. The benchmark's own runs
never run the control.

``knee`` serves the cell's traffic at each offered rate in turn, on one
engine, and reports per rate the requests, tokens per second, tails and
how the queue of waiting requests moved through the window: the knee is
the highest rate whose queue does not grow.

Both print one JSON line per seed or rate, and the whole list to
``--out``. Like a run, they need a TPU, except a ``lock_sim`` control,
which is host Python alone.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import zlib
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def seeds_for(name: str, n: int) -> list:
    import numpy as np
    rng = np.random.default_rng(zlib.crc32(name.encode()))
    return [int(s) for s in rng.integers(2**31, 2**33, size=n)]


def serve_control(cell, seeds, seconds, quants) -> list:
    from chipbench import gen
    from chipbench import harness as H
    drv = cell.driver
    cfg, tr = cell.config, cell.traffic
    srv, rows = None, []
    for seed in seeds:
        if srv is None:
            srv = drv.Server(cfg, tr, seed, H.Spans(), block_admits=False)
        else:
            srv.reset(seed)
        window = gen.schedule(tr, cfg["vocab_size"], seed, seconds)
        srv.set_up(seed, window)
        w = srv.serve(window, seconds)
        e = drv.summarize(w)
        done = [g for r, g in w["reqs"].items() if r not in e["unfinished"]]
        row = {"seed": seed, "requests": len(w["reqs"]),
               "unfinished": len(e["unfinished"]),
               "compiles_in_window": w["counters"]["compiles_in_window"]}
        for q in quants:
            got = drv.compare(srv.ref, srv.params, cfg, tr, done, seed,
                              srv.meter, q)
            row.update(tokens=got["tokens"],
                       served_error=got["served_error"],
                       served_gap=got["served_gap"],
                       served_gap_mean=got["served_gap_mean"])
            row[f"control_error.{q}"] = got["control_error"]
            row[f"control_gap.{q}"] = got["control_gap"]
            row[f"control_gap_mean.{q}"] = got["control_gap_mean"]
        print(json.dumps(row), flush=True)
        rows.append(row)
    return rows


def sim_control(cell, seeds, seconds) -> list:
    """Whole runs of a ``lock_sim`` cell with the control in the
    program's place, reciprocating only, at the cell's own sizes; pure
    host Python, so it needs no chip."""
    from chipbench import harness as H
    cell.config["locks"] = ["reciprocating"]
    rows = []
    for seed in seeds:
        ctx = H.RunContext(cell, seed, seconds, False, time.perf_counter(),
                           None)
        out = cell.driver.run(ctx, engine=cell.driver.ControlEngine)
        row = {"seed": seed, "correct": out.correct,
               **{c.name: c.value for c in out.checks}}
        print(json.dumps(row), flush=True)
        rows.append(row)
    return rows


def knee(cell, rates, seconds) -> list:
    import numpy as np

    from chipbench import gen
    from chipbench import harness as H
    drv = cell.driver
    cfg, tr = cell.config, cell.traffic
    seed = seeds_for(cell.name, 1)[0]
    srv, rows = None, []
    for rate in rates:
        t = dict(tr, rate_per_s=rate)
        window = gen.schedule(t, cfg["vocab_size"], seed, seconds)
        if srv is None:
            srv = drv.Server(cfg, t, seed, H.Spans(), block_admits=False)
        srv.tr = t
        t0 = time.perf_counter()
        srv.set_up(seed, window)
        setup_s = time.perf_counter() - t0
        w = srv.serve(window, seconds)
        e = drv.summarize(w)
        q = np.asarray(w["queue"], float)
        quarter = max(len(q) // 4, 1)
        row = {"rate_per_s": rate, "setup_s": setup_s,
               "steps": w["counters"]["steps"], "requests": len(w["reqs"]),
               "tokens_per_s": e["tokens_in_window"] / e["window_s"],
               "ttft_p90_ms": drv.percentile(e["ttft_ms"], 90),
               "ttft_p95_ms": drv.percentile(e["ttft_ms"], 95),
               "itl_p95_ms": drv.percentile(e["itl_ms"], 95),
               "queue_first_quarter": float(q[:quarter].mean()),
               "queue_last_quarter": float(q[-quarter:].mean()),
               "queue_max": float(q.max()) if len(q) else 0.0,
               "unfinished": len(e["unfinished"])}
        print(json.dumps(row), flush=True)
        rows.append(row)
        srv.reset(seed)
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("what", choices=("control", "knee"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--config", default="",
                    help="with --traffic: a cell not yet in BENCHMARK.json")
    ap.add_argument("--traffic", default="")
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--quant", default="int8,fp8")
    ap.add_argument("--rates", default="")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    from chipbench import harness as H
    cell = (H.cell_from_files(args.workload, args.config, args.traffic)
            if args.config else H.find_cell(args.workload))
    H.configure_jax()
    sim = cell.config["kind"] == "lock_sim"
    if not sim:
        H.require_device(cell.chips)
    t0 = time.perf_counter()
    seeds = seeds_for(cell.name, args.seeds)
    if args.what == "knee":
        rows = knee(cell, [float(r) for r in args.rates.split(",")],
                    args.seconds)
    elif sim:
        rows = sim_control(cell, seeds, args.seconds)
    else:
        rows = serve_control(cell, seeds, args.seconds,
                             args.quant.split(","))
    H.log(f"[readings] {args.what} {cell.name}: {len(rows)} rows in "
          f"{time.perf_counter() - t0:.1f} s")
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(rows, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
