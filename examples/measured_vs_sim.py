"""Measured vs modeled: the same lock, both backends, side by side.

One ``LockSpec`` lowers to one ``LockIR`` (DESIGN.md §L2 "one IR, two
backends") and runs twice here:

* **sim** — the discrete-time coherence machine prices every micro-op
  with a ``CostModel`` and reports episodes per kilocycle (model time);
* **measured** — the same IR as a Pallas kernel over the device atomics
  layer reports episodes per wall-second and per kilo-slice (compiled
  for the TPU; ``--interpret`` runs the Pallas interpreter instead, and
  its wall times are then the interpreter's).

Two things to watch in the output:

1. With a *uniform* cost model (every op = 1 cycle) the sim dispatches
   exactly the kernel's round-robin schedule — for deterministic-order
   locks (queue and ticket families) the admission-order prefixes
   printed at the bottom are identical, episode for episode.  That is
   the backend-agreement property CI gates on.  Racy locks (ttas) may
   legitimately differ: who wins a race is a tie-break the model does
   not pin down.
2. With the *default* (miss-priced) model, relative throughput between
   locks reshuffles: coherence misses dominate, which is the paper's
   point — and the gap between that column and the measured one is what
   ``bench/calibrate.py`` fits.

Run: PYTHONPATH=src python examples/measured_vs_sim.py [--threads 4]
     [--interpret]
"""
import argparse

import numpy as np

from repro.core.locks.pallas_backend import backends, run_measured
from repro.core.locks.programs import PROGRAMS
from repro.core.sim.machine import CostModel, run_machine

LOCKS = ("reciprocating", "ticket", "mcs", "ttas")


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--threads", type=int, default=4)
    ap.add_argument("--rounds", type=int, default=800)
    ap.add_argument("--interpret", action="store_true",
                    help="run the kernel in the Pallas interpreter "
                         "(needed on a host without a TPU)")
    args = ap.parse_args()
    T, rounds = args.threads, args.rounds
    sim_steps = rounds * T                    # same op budget per tier

    print("# backends")
    for row in backends():
        mark = "ok " if row["available"] else "-- "
        print(f"  {mark}{row['name']:17s} {row['detail']}")

    uni = CostModel(hit=1, local_miss=1, remote_miss=1)
    print(f"\n# {T} threads, {rounds} rounds, maximal contention")
    print(f"{'lock':15s} {'sim eps/kcyc':>13s} {'uniform':>9s} "
          f"{'meas eps/ks':>12s} {'meas eps/s':>11s} {'coll':>5s}")
    orders = {}
    for name in LOCKS:
        prog = PROGRAMS[name](T, ncs_max=0, cs_shared=True)
        s_def = run_machine(prog, T, sim_steps, cm=CostModel(), seed=0)
        s_uni = run_machine(prog, T, sim_steps, cm=uni, seed=0)
        r = run_measured(name, T, rounds, interpret=args.interpret)
        orders[name] = (
            np.asarray(s_uni.adm_log)[:int(s_uni.adm_cnt)][:16].tolist(),
            r.admissions[:min(r.admission_counts, 16)].tolist())

        def eps_kcyc(st):
            cyc = float(np.max(np.asarray(st.time)))
            return float(np.sum(np.asarray(st.episodes))) / max(cyc, 1) * 1e3

        print(f"{name:15s} {eps_kcyc(s_def):13.2f} {eps_kcyc(s_uni):9.1f} "
              f"{r.episodes_per_kslice:12.2f} {r.throughput_eps:11.0f} "
              f"{r.collisions:5d}")

    print("\n# admission order, uniform-cost sim vs Pallas (first 16)")
    for name, (sim_o, pal_o) in orders.items():
        tag = "==" if sim_o == pal_o[:len(sim_o)] or pal_o == \
            sim_o[:len(pal_o)] else "!="
        print(f"  {name:15s} sim {sim_o}\n  {'':15s} pal {pal_o}  [{tag}]")


if __name__ == "__main__":
    main()
