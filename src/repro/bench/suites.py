"""Built-in benchmark suites.

Each suite maps to a paper artifact (``PYTHONPATH=src python -m repro.bench
list`` shows the catalogue); the ``paper`` suite composes the figure/table
builders end-to-end and is what regenerates ``docs/RESULTS.md``:

  mutexbench   Fig. 1a/1b  thread sweep, maximal contention + random NCS
  atomics      Fig. 2      lock-striped ``std::atomic<struct>`` (rw CS)
  kvstore      Fig. 3      LevelDB-readrandom analogue (read-only CS)
  coherence    Table 1     invalidations / misses per episode
  locks-ext    beyond-paper extended lock zoo: DSL-authored variants
               (hapax / fissile / spin_then_park, core/locks/specs.py)
               vs the paper baselines, plus the park-cost sensitivity
               of spin_then_park
  topology     §3/§8 machine-model sweep: every lock on SMP vs 2/4-node
               NUMA vs clustered-CCX (core/sim/topology.py presets),
               remote-miss scaling vs node count, contiguous vs
               interleaved placement — all through SimEngine.grid
               (one jit per grid shape)
  hostile      beyond-paper hostile-OS sweep (core/sim/sched.py):
               locks × quantum × oversubscription, lock-holder-
               preemption stress, and the abort-rate ladder for the
               timed-wait locks — schedulers ride the grid as stacked
               data (one jit per grid shape)
  fairness     Table 2/§9  palindromic cycle, 2x bound, §9.4 mitigation,
                           bounded-bypass histograms (core.admission)
  residency    App. C      Jensen/decay residual-residency model
  scheduler    beyond-paper reciprocating continuous-batching admission
  serve        beyond-paper serving engine: policy × load sweep on the
               unified core + paged-KV pool, model-backed engine smoke
               (docs/SERVING.md)
  measured     beyond-sim measured tier (DESIGN.md §L2): the Fig 1-3
               sweeps as real Pallas kernels over the device atomics
               layer (bench/measured.py; --interpret off the TPU), the
               sim-vs-Pallas backend-agreement table, and the CostModel
               calibration error table (bench/calibrate.py)
  kernels      beyond-paper serpentine DMA savings accounting
  roofline     EXPERIMENTS  dry-run artifact aggregation
  paper        Figs 1-3 + Table 1 + topology + fairness/bypass + serve
               + measured, one document
"""
from __future__ import annotations

import glob
import json
import os
import time
from dataclasses import replace

import numpy as np

from repro.bench import report, sweep
from repro.bench.measured import build_measured
from repro.bench.registry import BenchConfig, emit, register
from repro.bench.schema import (
    hist_experiment, scalars_experiment, sweep_experiment, table_experiment,
)
from repro.core.sim import topology as topo
from repro.core.sim.engine import Workload
from repro.core.sim.machine import CostModel

# Lock subsets mirroring what each paper figure actually plots.
FIG1_ALGS = sweep.ALL_ALGS                      # every registered program
FIG2_ALGS = ("reciprocating", "ticket", "mcs", "clh", "hemlock", "ttas")
FIG3_ALGS = ("reciprocating", "ticket", "mcs", "clh", "hemlock")
# Paper Table 1 invalidation counts (T=10): the comparison column.
TABLE1_PAPER = {"reciprocating": 4, "clh": 5, "mcs": 6, "hemlock": 5,
                "ticket": 10, "anderson": None, "ttas": None,
                "retrograde": None}
ADMISSION_POLICIES = ("fifo", "lifo", "reciprocating",
                      "reciprocating_mitigated")


def _algs(cfg: BenchConfig, default) -> tuple:
    return tuple(cfg.algs) if cfg.algs else tuple(default)


# --- figure/table builders (shared by per-figure suites and `paper`) --------

def build_fig1(cfg: BenchConfig, on_result=None) -> list:
    """``on_result`` captures the max-contention BenchResults so composed
    suites (``paper`` -> locks-ext) can reuse the cells instead of
    re-simulating them."""
    a = sweep.lock_sweep(_algs(cfg, FIG1_ALGS), cfg, ncs_max=0,
                         tag="mutexbench_max_contention",
                         on_result=on_result)
    b = sweep.lock_sweep(_algs(cfg, FIG1_ALGS), cfg, ncs_max=250,
                         tag="mutexbench_random_ncs")
    return [
        sweep_experiment(
            "fig1a_max_contention",
            "Figure 1a — MutexBench throughput, maximal contention "
            "(empty NCS)", "threads", a),
        sweep_experiment(
            "fig1b_random_ncs",
            "Figure 1b — MutexBench throughput, random NCS delay",
            "threads", b),
    ]


def build_fig2(cfg: BenchConfig) -> list:
    s = sweep.lock_sweep(_algs(cfg, FIG2_ALGS), cfg, cs_shared="rw",
                         tag="atomics_xchg")
    return [sweep_experiment(
        "fig2_atomics",
        "Figure 2 — lock-striped std::atomic<struct> exchange "
        "(shared-rw CS, empty NCS)", "threads", s)]


def build_fig3(cfg: BenchConfig) -> list:
    s = sweep.lock_sweep(_algs(cfg, FIG3_ALGS), cfg, ncs_max=60,
                         cs_shared="ro", tag="kvstore")
    return [sweep_experiment(
        "fig3_kvstore",
        "Figure 3 — LevelDB-readrandom analogue (read-only CS, "
        "random key-gen NCS)", "threads", s)]


def build_table1(cfg: BenchConfig) -> list:
    rows = sweep.coherence_rows(_algs(cfg, tuple(TABLE1_PAPER)), cfg,
                                n_threads=10, paper=TABLE1_PAPER)
    return [table_experiment(
        "table1_coherence",
        "Table 1 — coherence traffic per contended episode "
        "(T=10, degenerate local CS)",
        ["lock", "miss_per_episode", "inval_per_episode",
         "remote_per_episode_numa", "paper_invalidations"], rows)]


LOCKS_EXT_BASELINES = ("reciprocating", "mcs", "ticket")
# (park_cost, unpark_cost) grid for the spin_then_park sensitivity table
PARK_COSTS = ((0, 0), (10, 30), (25, 75), (50, 150), (100, 300))


def build_locks_ext(cfg: BenchConfig, reuse_series: list | None = None,
                    reuse_cells: dict | None = None) -> list:
    """Extended lock zoo (DESIGN.md §L2): the three DSL-authored variants
    against the reference trio, a phase/coherence profile table at the
    largest thread count, and the spin_then_park park-cost sensitivity.

    ``reuse_series`` / ``reuse_cells`` let the ``paper`` suite hand over
    its already-run Fig. 1a series and per-cell BenchResults (same
    ncs/CS/seed settings) so composed runs re-simulate nothing."""
    from repro.core.locks.programs import NEW_VARIANTS, describe_program

    algs = _algs(cfg, LOCKS_EXT_BASELINES + NEW_VARIANTS)
    t_hi = max(cfg.threads)
    cells: dict = dict(reuse_cells or {})
    reused = {s["label"]: s for s in reuse_series or []}
    series = ([reused[a] for a in algs]
              if all(a in reused for a in algs)
              else sweep.lock_sweep(
                  algs, cfg, ncs_max=0, tag="locksext",
                  on_result=lambda a, t, r: cells.__setitem__((a, t), r)))

    prof_rows = []
    for alg in algs:
        r = cells.get((alg, t_hi))
        cell_us = 0.0                       # reused cell: no new simulation
        if r is None:
            t0 = time.time()
            r = sweep.bench_cell(alg, t_hi, cfg)
            cell_us = (time.time() - t0) * 1e6 / max(r.episodes, 1)
        d = describe_program(alg)
        phases = d["phases"]
        prof_rows.append({
            "lock": alg,
            "spec_steps": "/".join(
                f"{p[0].upper()}{len(phases[p])}"
                for p in ("doorway", "waiting", "entry", "release")),
            "throughput": round(r.throughput, 4),
            "miss_per_episode": round(r.miss_per_episode, 2),
            "latency": round(r.latency, 1),
            "unfairness": round(r.unfairness, 3),
            "bypass_bound": r.bypass_bound,
        })
        if cfg.verbose:
            emit(f"locksext/{alg}", cell_us,
                 f"thr={r.throughput:.3f}/kcyc bypass<={r.bypass_bound}")

    park_rows = []
    costs = PARK_COSTS[1:4] if cfg.quick else PARK_COSTS
    base = sweep.default_machine(cfg, t_hi)
    # the whole park-cost axis is one stacked-topology grid (one jit):
    # dataclasses.replace keeps every other CostModel field intact
    g = sweep.cached_grid(
        "spin_then_park",
        seeds=range(cfg.seed0, cfg.seed0 + cfg.n_replicas),
        topologies=[replace(base, park_cost=p, unpark_cost=u)
                    for p, u in costs],
        workloads=[Workload(0, True, cfg.n_steps)], threads=[t_hi])
    for (park, unpark), cell in zip(costs, g.cells):
        r = cell.result
        park_rows.append({
            "park_cost": park, "unpark_cost": unpark,
            "throughput": round(r.throughput, 4),
            "latency": round(r.latency, 1),
            "miss_per_episode": round(r.miss_per_episode, 2),
        })
    if cfg.verbose:
        lo, hi = park_rows[0]["throughput"], park_rows[-1]["throughput"]
        emit("locksext/park_sensitivity", 0.0,
             f"thr {lo:.3f}->{hi:.3f}/kcyc over {len(park_rows)} park costs")

    return [
        sweep_experiment(
            "locksext_sweep",
            "Extended lock zoo — DSL-authored variants (hapax, fissile, "
            "spin_then_park) vs paper baselines, maximal contention",
            "threads", series),
        table_experiment(
            "locksext_profile",
            f"Extended lock zoo — phase anatomy and coherence profile at "
            f"T={t_hi} (spec_steps = steps per "
            "Doorway/Waiting/Entry/Release phase)",
            ["lock", "spec_steps", "throughput", "miss_per_episode",
             "latency", "unfairness", "bypass_bound"], prof_rows),
        table_experiment(
            "locksext_park",
            f"spin_then_park — throughput/latency vs park+unpark cost "
            f"(T={t_hi}, CostModel hooks in core/sim/machine.py)",
            ["park_cost", "unpark_cost", "throughput", "latency",
             "miss_per_episode"], park_rows),
    ]


# Locks whose remote-miss scaling the paper contrasts (§3, Table 1).
TOPOLOGY_FOCUS = ("reciprocating", "mcs", "ticket")
TOPOLOGY_NODE_COUNTS = (1, 2, 4, 8)


def topology_machines(n_threads: int) -> list:
    """The suite's machine roster, sized so ``n_threads`` always fits:
    degenerate SMP, 2- and 4-node NUMA, and a clustered-CCX part."""
    per2 = max((n_threads + 1) // 2, 1)
    per4 = max((n_threads + 3) // 4, 1)
    return [topo.smp(n_threads), topo.numa(2, per2), topo.numa(4, per4),
            topo.ccx(sockets=2, ccx_per_socket=2, per_ccx=per4)]


def build_topology(cfg: BenchConfig) -> list:
    """Topology suite (DESIGN.md §L1): every lock across the machine
    roster, remote-miss scaling vs NUMA node count, and contiguous vs
    interleaved placement — each lock's whole machine grid is ONE
    ``SimEngine.grid`` call (seed x topology stacked into a single jit),
    and the compile accounting is exported so batching regressions are
    visible in the results document."""
    algs = _algs(cfg, sweep.ALL_ALGS)
    t_hi = min(16, max(max(cfg.threads), 4))
    seeds = range(cfg.seed0, cfg.seed0 + cfg.n_replicas)
    wl = Workload(0, False, cfg.n_steps, label="local_cs")
    machines = topology_machines(t_hi)
    machines.append(machines[1].interleave())     # numa2 + scatter pinning

    grid_rows, compiles, grids, points = [], 0, 0, 0
    for alg in algs:
        t0 = time.time()
        g = sweep.cached_grid(alg, seeds=seeds, topologies=machines,
                              workloads=[wl], threads=[t_hi])
        compiles += g.compiles
        grids += 1
        points += len(machines) * cfg.n_replicas
        for c in g.cells:
            grid_rows.append({
                "lock": alg, "topology": c.topology,
                "throughput": round(c.result.throughput, 4),
                "miss_per_episode": round(c.result.miss_per_episode, 2),
                "remote_per_episode":
                    round(c.result.remote_per_episode, 2),
                "latency": round(c.result.latency, 1),
            })
        if cfg.verbose:
            base = g.cell(topology=machines[0].name).result
            worst = max(g.results(), key=lambda r: r.remote_per_episode)
            emit(f"topology/{alg}",
                 (time.time() - t0) * 1e6 / max(base.episodes, 1),
                 f"smp={base.throughput:.3f}/kcyc "
                 f"worst_remote/ep={worst.remote_per_episode:.2f} "
                 f"jits={g.compiles}")

    # remote-miss scaling vs node count: flat machines as pure data, so
    # the whole node axis shares one jit per lock
    focus = [a for a in TOPOLOGY_FOCUS if a in algs] or list(algs[:1])
    node_series = []
    for alg in focus:
        g = sweep.cached_grid(
            alg, seeds=seeds,
            topologies=[CostModel(n_nodes=k)
                        for k in TOPOLOGY_NODE_COUNTS],
            workloads=[wl], threads=[t_hi])
        compiles += g.compiles
        grids += 1
        points += len(TOPOLOGY_NODE_COUNTS) * cfg.n_replicas
        node_series.append({"label": alg, "points": [
            {"nodes": k,
             "remote_per_episode": round(c.result.remote_per_episode, 3),
             "throughput": round(c.result.throughput, 4)}
            for k, c in zip(TOPOLOGY_NODE_COUNTS, g.cells)]})

    placements = {machines[1].name: "contiguous",
                  machines[-1].name: "interleaved"}
    placement_rows = [
        {"lock": r["lock"], "placement": placements[r["topology"]],
         "throughput": r["throughput"],
         "remote_per_episode": r["remote_per_episode"]}
        for r in grid_rows
        if r["lock"] in focus and r["topology"] in placements]

    stats = {
        "grids": grids, "grid_points": points, "xla_compiles": compiles,
        "compiles_per_grid": round(compiles / max(grids, 1), 3),
        "machines": [m.name for m in machines],
        "threads": t_hi,
    }
    if cfg.verbose:
        emit("topology/compiles", 0.0,
             f"{compiles} jits for {grids} grids ({points} grid points)")
    return [
        table_experiment(
            "topology_grid",
            f"Topology grid — every lock on SMP / 2- and 4-node NUMA / "
            f"clustered-CCX / interleaved-NUMA machines "
            f"(T={t_hi}, degenerate local CS; one jit per lock)",
            ["lock", "topology", "throughput", "miss_per_episode",
             "remote_per_episode", "latency"], grid_rows),
        sweep_experiment(
            "topology_remote_scaling",
            "Remote misses per episode vs NUMA node count — "
            "queue locks stay O(1)-remote while global spinning scales "
            "(paper §3 Maximum Remote Misses)", "nodes", node_series),
        table_experiment(
            "topology_placement",
            f"Placement sensitivity — contiguous vs interleaved thread "
            f"pinning on the 2-node NUMA machine (T={t_hi})",
            ["lock", "placement", "throughput", "remote_per_episode"],
            placement_rows),
        scalars_experiment(
            "topology_compile",
            "Batched-grid compile accounting — SimEngine.grid shares one "
            "XLA program across the seed x topology axes", stats),
    ]


# Locks whose degradation the hostile suite contrasts: pure spinners
# (collapse under oversubscription), queue spinners (holder preemption
# stalls the relay), the parking hybrid (graceful), and the timed-wait
# abortable variants.
HOSTILE_LOCKS = ("reciprocating", "ticket", "mcs", "spin_then_park",
                 "reciprocating_abortable", "mcs_timeout")
HOSTILE_QUANTA = (1200, 2500)
HOSTILE_OVERSUB = (2, 4)
# escalating hostility for the abort-rate ladder
HOSTILE_LADDER = ("dedicated", "fair-2x", "fair-4x", "holder-bane",
                  "lhp:800x200x4")


def hostile_schedulers(quick: bool) -> list:
    """The quantum × oversubscription grid as shorthand names, dedicated
    first (the baseline column)."""
    quanta = HOSTILE_QUANTA[-1:] if quick else HOSTILE_QUANTA
    ovs = HOSTILE_OVERSUB[-1:] if quick else HOSTILE_OVERSUB
    return ["dedicated"] + [f"fair:{q}x{r}" for q in quanta for r in ovs]


def build_hostile(cfg: BenchConfig) -> list:
    """Hostile-OS suite (DESIGN.md §L1 "Scheduler model"): who degrades
    gracefully when the OS preempts and oversubscribes. Every lock's
    whole scheduler grid is ONE ``SimEngine.grid`` call — schedulers are
    stacked ``LoweredSched`` data, so the axis adds zero XLA traces
    (``hostile_compile`` exports the accounting; CI pins
    ``compiles_per_grid <= 1``)."""
    algs = _algs(cfg, HOSTILE_LOCKS)
    t_hi = min(16, max(max(cfg.threads), 4))
    seeds = range(cfg.seed0, cfg.seed0 + cfg.n_replicas)
    wl = Workload(0, True, cfg.n_steps, label="max_contention")
    scheds = hostile_schedulers(cfg.quick)

    grid_rows, compiles, grids, points = [], 0, 0, 0
    base_thr: dict = {}
    for alg in algs:
        t0 = time.time()
        g = sweep.cached_grid(alg, seeds=seeds, schedulers=scheds,
                              workloads=[wl], threads=[t_hi])
        compiles += g.compiles
        grids += 1
        points += len(scheds) * cfg.n_replicas
        base = g.cell(scheduler="dedicated").result
        base_thr[alg] = base.throughput
        for c in g.cells:
            r = c.result
            grid_rows.append({
                "lock": alg, "scheduler": c.scheduler,
                "throughput": round(r.throughput, 4),
                "vs_dedicated": round(r.throughput
                                      / max(base.throughput, 1e-9), 3),
                "latency": round(r.latency, 1),
                "unfairness": round(r.unfairness, 3),
                "preempts": r.preempts,
                "aborts": r.aborts,
            })
        if cfg.verbose:
            worst = min(g.results(), key=lambda r: r.throughput)
            emit(f"hostile/{alg}",
                 (time.time() - t0) * 1e6 / max(base.episodes, 1),
                 f"dedicated={base.throughput:.3f}/kcyc "
                 f"worst={worst.throughput:.3f}/kcyc jits={g.compiles}")

    # lock-holder-preemption stress: same quantum/oversubscription, with
    # and without the tight lock-held slice — the LHP delta isolates how
    # much of the collapse is the *holder* vanishing mid-CS.
    lhp_rows = []
    lhp_pair = ["fair:2500x2", "lhp:2500x600x2"]
    for alg in algs:
        g = sweep.cached_grid(alg, seeds=seeds, schedulers=lhp_pair,
                              workloads=[wl], threads=[t_hi])
        compiles += g.compiles
        grids += 1
        points += len(lhp_pair) * cfg.n_replicas
        fair, lhp = (g.cell(scheduler=s).result for s in lhp_pair)
        lhp_rows.append({
            "lock": alg,
            "fair_throughput": round(fair.throughput, 4),
            "lhp_throughput": round(lhp.throughput, 4),
            "lhp_penalty": round(fair.throughput
                                 / max(lhp.throughput, 1e-9), 3),
            "lhp_preempts": lhp.preempts,
            "lhp_latency": round(lhp.latency, 1),
        })
        if cfg.verbose:
            emit(f"hostile/lhp_{alg}", 0.0,
                 f"penalty={lhp_rows[-1]['lhp_penalty']}x "
                 f"preempts={lhp.preempts}")

    # abort-rate ladder: the timed-wait locks up the hostility scale —
    # aborts should be ~0 on the dedicated machine and climb with
    # preemption pressure while episodes keep flowing.
    abort_rows = []
    ladder = HOSTILE_LADDER[::2] if cfg.quick else HOSTILE_LADDER
    from repro.core.locks.programs import ABORTABLE_VARIANTS
    for alg in [a for a in algs if a in ABORTABLE_VARIANTS]:
        g = sweep.cached_grid(alg, seeds=seeds, schedulers=list(ladder),
                              workloads=[wl], threads=[t_hi])
        compiles += g.compiles
        grids += 1
        points += len(ladder) * cfg.n_replicas
        for c in g.cells:
            r = c.result
            abort_rows.append({
                "lock": alg, "scheduler": c.scheduler,
                "episodes": r.episodes, "aborts": r.aborts,
                "abort_rate": round(r.aborts
                                    / max(r.episodes + r.aborts, 1), 4),
                "throughput": round(r.throughput, 4),
                "preempts": r.preempts,
            })
        if cfg.verbose:
            emit(f"hostile/aborts_{alg}", 0.0,
                 " ".join(f"{row['scheduler']}={row['abort_rate']:.2%}"
                          for row in abort_rows if row["lock"] == alg))

    stats = {
        "grids": grids, "grid_points": points, "xla_compiles": compiles,
        "compiles_per_grid": round(compiles / max(grids, 1), 3),
        "schedulers": scheds, "threads": t_hi,
    }
    if cfg.verbose:
        emit("hostile/compiles", 0.0,
             f"{compiles} jits for {grids} grids ({points} grid points)")
    return [
        table_experiment(
            "hostile_grid",
            f"Hostile-OS grid — locks × (quantum × oversubscription) at "
            f"T={t_hi}, maximal contention: spinners collapse under "
            f"timeslicing, spin-then-park degrades gracefully "
            f"(vs_dedicated = throughput relative to the pinned machine)",
            ["lock", "scheduler", "throughput", "vs_dedicated", "latency",
             "unfairness", "preempts", "aborts"], grid_rows),
        table_experiment(
            "hostile_lhp",
            f"Lock-holder preemption — fair:2500x2 vs the same schedule "
            f"with a 600-cycle lock-held slice (T={t_hi}); lhp_penalty = "
            f"fair/lhp throughput ratio",
            ["lock", "fair_throughput", "lhp_throughput", "lhp_penalty",
             "lhp_preempts", "lhp_latency"], lhp_rows),
        table_experiment(
            "hostile_abort",
            f"Abortable acquisition — timed-wait locks up the hostility "
            f"ladder (T={t_hi}): abort rate climbs with preemption "
            f"pressure while mutual exclusion and progress hold",
            ["lock", "scheduler", "episodes", "aborts", "abort_rate",
             "throughput", "preempts"], abort_rows),
        scalars_experiment(
            "hostile_compile",
            "Batched-grid compile accounting — the scheduler axis is "
            "stacked LoweredSched data under the topology-grid jit",
            stats),
    ]


def build_fairness(cfg: BenchConfig) -> list:
    t0 = time.time()
    n_ops = 1500 if cfg.quick else 8000
    ref = sweep.reference_fairness(n_threads=5, n_ops=n_ops)
    values = {
        "table2_cycle": ref["cycle_str"],
        "table2_cycle_admissions_sorted": ref["cycle_admissions_sorted"],
        "reference_unfairness": ref["unfairness"],
        "mitigated_unfairness":
            round(sweep.mitigated_unfairness(
                n_events=800 if cfg.quick else 4000, seed=cfg.seed0), 3),
    }
    for alg in ("reciprocating", "ticket", "retrograde"):
        r = sweep.bench_cell(alg, 5, cfg, n_nodes=1)
        values[f"machine_unfairness_{alg}"] = round(r.unfairness, 3)
    if cfg.verbose:
        emit("fairness/table2", (time.time() - t0) * 1e6 / n_ops,
             f"cycle={values['table2_cycle']} "
             f"unfair={values['reference_unfairness']}")

    n_events = 400 if cfg.quick else 2000
    bins, series, stat_rows = sweep.bypass_histograms(
        ADMISSION_POLICIES, n_threads=8, n_events=n_events, seed=cfg.seed0)
    if cfg.verbose:
        for r in stat_rows:
            emit(f"fairness/bypass_{r['policy']}", 0.0,
                 f"max_single={r['max_bypass_by_single_thread']} "
                 f"bound={r['theoretical_single_thread_bound']} "
                 f"outstanding={r['max_outstanding_unserved']}")
    return [
        scalars_experiment(
            "fairness", "Fairness — Table 2 palindromic cycle, §9 "
            "long-run unfairness, §9.4 mitigation", values),
        hist_experiment(
            "bypass_hist",
            "Bounded bypass — per-wait overtake counts by admission "
            "policy (closed loop, 8 threads)", bins, series),
        table_experiment(
            "bypass_bounds",
            "Bounded bypass — observed vs theoretical single-thread "
            "bounds (paper §2)",
            ["policy", "completed_waits", "mean_bypass",
             "max_bypass_per_wait", "max_bypass_by_single_thread",
             "max_outstanding_unserved",
             "theoretical_single_thread_bound"], stat_rows),
    ]


def build_residency(cfg: BenchConfig) -> list:
    """App. C: residual cache residency, palindrome vs FIFO (Jensen)."""
    def schedule_residency(schedule, n, lam, cycles=200):
        last = {t: None for t in range(n)}
        acc = {t: [] for t in range(n)}
        step = 0
        for _ in range(cycles):
            for t in schedule:
                if last[t] is not None:
                    acc[t].append(np.exp(-(step - last[t]) * lam))
                last[t] = step
                step += 1
        return np.array([np.mean(acc[t]) for t in range(n)])

    n, lam = 5, 0.15
    fifo = list(range(n))
    palin = list(range(n)) + list(reversed(range(n)))
    r_fifo = schedule_residency(fifo, n, lam)
    r_palin = schedule_residency(palin, n, lam)
    values = {
        "lambda": lam,
        "fifo_mean": round(float(r_fifo.mean()), 4),
        "palindrome_mean": round(float(r_palin.mean()), 4),
        "palindrome_wins": bool(r_palin.mean() >= r_fifo.mean()),
        "per_party_never_worse": bool((r_palin >= r_fifo - 1e-12).all()),
        "disparity_palindrome": round(float(r_palin.max() / r_palin.min()),
                                      4),
    }
    if cfg.verbose:
        emit("residency/jensen", 0.0,
             f"palin={values['palindrome_mean']:.4f} "
             f"fifo={values['fifo_mean']:.4f} "
             f"wins={values['palindrome_wins']}")
    rows = []
    for lam_s in (0.02, 0.05, 0.1, 0.2, 0.4):
        a = float(schedule_residency(palin, n, lam_s).mean())
        b = float(schedule_residency(fifo, n, lam_s).mean())
        rows.append({"lambda": lam_s, "palindrome": round(a, 4),
                     "fifo": round(b, 4), "advantage": round(a / b, 4)})
    return [
        scalars_experiment(
            "residency", "Appendix C — residual residency under the "
            "palindromic admission schedule", values),
        table_experiment(
            "residency_sweep", "Appendix C — palindrome advantage vs "
            "residency decay rate",
            ["lambda", "palindrome", "fifo", "advantage"], rows),
    ]


def scheduler_drive(policy: str, *, n_req: int = 600, mean_gap: float = 14.0,
                    families: int = 64, pool: int = 96, seed: int = 0) -> dict:
    """Bursty shared-prefix workload against the continuous batcher: a
    family arrives as a burst of 2-6 requests close together (users
    iterating on one prompt) — the regime where admission order interacts
    with prefix residency (SERVING.md §4). ``mean_gap`` sets the offered
    load (mean burst size is 4 requests, so load ≈ 4/mean_gap req/step).
    Runs on the same ``ServeCore`` + ``PagedKVPool`` the model engine
    uses; the summary includes the pool's eviction count."""
    from repro.serve.scheduler import ContinuousBatcher, Request
    sched = ContinuousBatcher(policy=policy, max_batch=4, pool_blocks=pool,
                              seed=seed)
    rng = np.random.default_rng(seed)
    t, i = 0.0, 0
    while i < n_req:
        t += float(rng.exponential(mean_gap))
        fam = int(rng.integers(0, families))
        for _ in range(int(rng.integers(2, 7))):
            if i >= n_req:
                break
            sched.submit(Request(
                rid=i, arrival=t + float(rng.exponential(2.0)),
                prefix_id=fam, prefix_blocks=16, prompt_blocks=2,
                decode_tokens=int(rng.integers(4, 16))))
            i += 1
    sched.drain()
    s = sched.stats.summary()
    s["pool_evictions"] = sched.pool.stats.evictions
    return s


def build_scheduler(cfg: BenchConfig) -> list:
    """Beyond-paper: reciprocating admission in the serving scheduler
    (DESIGN.md §L3)."""
    drive = scheduler_drive
    n_req = 120 if cfg.quick else 600
    n_seeds = 1 if cfg.quick else 3
    rows = []
    for policy in ADMISSION_POLICIES:
        agg: dict = {}
        t0 = time.time()
        for seed in range(n_seeds):
            for k, v in drive(policy, n_req=n_req, seed=seed).items():
                agg.setdefault(k, []).append(v)
        row = {"policy": policy}
        row.update({k: round(float(np.mean(v)), 4) for k, v in agg.items()})
        rows.append(row)
        if cfg.verbose:
            emit(f"scheduler/{policy}",
                 (time.time() - t0) / n_seeds * 1e6 / n_req,
                 f"hit={row.get('prefix_hit_rate', 0):.3f} "
                 f"p99wait={row.get('p99_wait', 0):.1f}")
    cols = ["policy"] + [k for k in rows[0] if k != "policy"]
    return [table_experiment(
        "scheduler_policies",
        "Serving scheduler — admission policy comparison on a bursty "
        "shared-prefix workload", cols, rows)]


SERVE_GAPS_FULL = (28.0, 14.0, 7.0, 4.0)    # mean inter-burst gap (steps)
SERVE_GAPS_QUICK = (14.0, 7.0)
SERVE_METRICS = ("throughput_rps", "p99_wait", "max_wait", "p99_latency",
                 "mean_wait", "prefix_hit_rate", "pool_evictions")


def static_batch_slot_steps(done: list, max_batch: int) -> int:
    """Decode slot-steps the old detached-segment engine would burn:
    submission-order segments of ``max_batch``, every slot riding to the
    segment's longest request."""
    reqs = sorted(done, key=lambda r: r.rid)
    return sum(len(seg) * max(len(r.out) for r in seg)
               for seg in (reqs[i:i + max_batch]
                           for i in range(0, len(reqs), max_batch)))


def serve_engine_smoke(seed: int = 0) -> dict:
    """Model-backed serving smoke (SERVING.md §6): the paged continuous
    batcher on a reduced starcoder2-3b, two shared-prefix families, mixed
    ``max_new`` so early exit and per-step admission are both exercised."""
    import jax

    from repro.configs import get_config, smoke_config
    from repro.models import model as M_
    from repro.serve.engine import GenRequest, InferenceEngine

    mcfg = smoke_config(get_config("starcoder2-3b")).replace(
        n_layers=2, vocab_size=256)
    params = M_.init_params(mcfg, jax.random.PRNGKey(seed))
    eng = InferenceEngine(mcfg, params, policy="reciprocating",
                          max_batch=4, max_seq=64, block_size=8)
    rng = np.random.default_rng(seed)
    shared = {f: rng.integers(1, 97, 16, dtype=np.int32) for f in range(2)}
    t0 = time.time()
    for i in range(8):
        fam = i % 2
        toks = np.concatenate(
            [shared[fam], rng.integers(1, 97, 4, dtype=np.int32)])
        eng.submit(GenRequest(rid=i, tokens=toks, prefix_id=fam,
                              prefix_len=16,
                              max_new=int(rng.integers(2, 9))))
    done = eng.run()
    wall = time.time() - t0
    gen = sum(len(r.out) for r in done)
    c = eng.counters
    naive = static_batch_slot_steps(done, max_batch=4)
    return {
        "requests": len(done),
        "generated_tokens": gen,
        "scheduler_steps": int(eng.core.time),
        "decode_batches": c.decode_batches,
        "slot_steps": c.slot_steps,
        "slot_steps_static_batch": naive,
        "early_exit_savings":
            round(1.0 - c.slot_steps / max(naive, 1), 4),
        "mean_prefill_hit":
            round(float(np.mean([r.prefill_hit for r in done])), 4),
        "pool": eng.pool.stats.to_dict(),
        "wall_s": round(wall, 2),
        "tokens_per_s": round(gen / max(wall, 1e-9), 2),
    }


def build_serve(cfg: BenchConfig) -> list:
    """Serving suite (SERVING.md §6): policy × offered-load sweep on the
    unified scheduler core, pool/starvation table at the heaviest load,
    and (full runs only) the model-backed paged-engine smoke."""
    gaps = SERVE_GAPS_QUICK if cfg.quick else SERVE_GAPS_FULL
    n_req = 120 if cfg.quick else 600
    n_seeds = 1 if cfg.quick else 3
    series, heavy_rows = [], []
    for policy in ADMISSION_POLICIES:
        t0 = time.time()
        pts = []
        for gap in gaps:
            agg: dict = {}
            for seed in range(n_seeds):
                d = scheduler_drive(policy, n_req=n_req, mean_gap=gap,
                                    seed=cfg.seed0 + seed)
                for k in SERVE_METRICS:
                    agg.setdefault(k, []).append(d[k])
            pt = {"offered_load": round(4.0 / gap, 3)}
            pt.update({k: round(float(np.mean(v)), 4)
                       for k, v in agg.items()})
            pts.append(pt)
        series.append({"label": policy, "points": pts})
        heavy = dict(pts[-1])
        heavy_rows.append({"policy": policy, **heavy})
        if cfg.verbose:
            emit(f"serve/{policy}",
                 (time.time() - t0) * 1e6 / (len(gaps) * n_seeds * n_req),
                 f"hit={pts[-1]['prefix_hit_rate']:.3f} "
                 f"p99wait={pts[-1]['p99_wait']:.1f} "
                 f"maxwait={pts[-1]['max_wait']:.1f}")
    exps = [
        sweep_experiment(
            "serve_policy_load",
            "Serving — throughput / tail wait / prefix hit vs offered "
            "load × admission policy (unified scheduler core, paged-KV "
            "pool)", "offered_load", series,
            meta={"series_label": "policy"}),
        table_experiment(
            "serve_pool",
            "Serving — starvation and paged-KV pool behaviour at the "
            "heaviest offered load",
            ["policy", "offered_load"] + list(SERVE_METRICS), heavy_rows),
    ]
    if not cfg.quick:
        t0 = time.time()
        vals = serve_engine_smoke(cfg.seed0)
        if cfg.verbose:
            emit("serve/engine_smoke", (time.time() - t0) * 1e6
                 / max(vals["generated_tokens"], 1),
                 f"steps={vals['scheduler_steps']} "
                 f"early_exit={vals['early_exit_savings']:.2%} "
                 f"hit={vals['mean_prefill_hit']:.2f}")
        exps.append(scalars_experiment(
            "serve_engine_smoke",
            "Serving — model-backed paged continuous-batching engine "
            "smoke (reduced starcoder2-3b, CPU)", vals))
    return exps


GATEWAY_ROUTERS = ("round_robin", "random", "least_loaded", "prefix",
                   "reciprocating")
GATEWAY_METRICS = ("hit_rate", "mean_ttft", "p99_ttft", "mean_tpot",
                   "goodput_tok_per_step", "load_imbalance", "mean_wait")
#: Fleet shape shared by every gateway experiment: 8 replicas x 8 slots,
#: per-replica pools sized so the tenant working set (~160 tenants x
#: 4-12 shared blocks) fits the fleet aggregate but NOT one pool —
#: the regime where routing decides the global hit rate (SERVING.md §8).
GATEWAY_FLEET = {"n_replicas": 8, "max_slots": 8, "pool_blocks": 160,
                 "block_tokens": 16, "prefill_cost_per_block": 1.0,
                 "load_penalty": 4.0}


def fleet_drive(router: str, *, n_req: int, seed: int = 0,
                burst_rate: float = 0.2) -> dict:
    """One trace-to-drain fleet run, fronted by the experiment cache: a
    gateway drive is a pure function of (fleet shape, router, seeded
    trace spec), so its summary is content-addressed exactly like a sim
    grid cell (bench/cache.py) and warm paper re-runs replay it."""
    import hashlib

    from repro.bench import cache as cachemod
    from repro.serve.gateway import FleetGateway
    from repro.serve.traces import TraceSpec, generate

    gw_kwargs = dict(GATEWAY_FLEET, router=router, seed=seed)
    trace_kwargs = {"n_requests": n_req, "burst_rate": burst_rate,
                    "seed": seed}
    store = cachemod.get_cache()
    key = hashlib.sha256(json.dumps(
        {"v": cachemod.CACHE_KEY_VERSION, "kind": "fleet_drive",
         "gw": gw_kwargs, "trace": trace_kwargs},
        sort_keys=True).encode()).hexdigest()
    s = store.get(key)
    if s is None:
        if store.enabled:
            store.stats.misses += 1
        t0 = time.time()
        gw = FleetGateway(**gw_kwargs)
        s = gw.run(generate(TraceSpec(**trace_kwargs)))
        wall = time.time() - t0
        s["wall_s"] = round(wall, 3)
        s["req_per_s"] = round(n_req / max(wall, 1e-9), 1)
        if store.enabled:
            store.put(key, s)
    elif store.enabled:
        store.stats.hits += 1
    # O(requests) bookkeeping bound (serve/core.py): every request costs
    # exactly one arrival-heap pop and one slot retirement, regardless
    # of trace length — the micro-assert that keeps million-request
    # traces from going quadratic again.
    assert s["bookkeeping_ops"] == 2 * n_req, (
        f"bookkeeping ops {s['bookkeeping_ops']} != 2*{n_req}")
    return s


def build_gateway(cfg: BenchConfig) -> list:
    """Fleet tier (SERVING.md §8): router comparison table, offered-load
    sweep, and the at-scale prefix-vs-baselines run (100k requests
    quick, 1M full)."""
    seed = cfg.seed0
    n_table = 10_000 if cfg.quick else 100_000
    n_sweep = 4_000 if cfg.quick else 20_000
    n_scale = 100_000 if cfg.quick else 1_000_000
    rates = (0.12, 0.2) if cfg.quick else (0.1, 0.15, 0.2, 0.25)

    rows = []
    for router in GATEWAY_ROUTERS:
        t0 = time.time()
        s = fleet_drive(router, n_req=n_table, seed=seed)
        rows.append({"router": router,
                     **{k: round(float(s[k]), 4) for k in GATEWAY_METRICS},
                     "tree_nodes": s["tree_nodes"]})
        if cfg.verbose:
            emit(f"gateway/{router}", (time.time() - t0) * 1e6 / n_table,
                 f"hit={s['hit_rate']:.3f} ttft={s['mean_ttft']:.1f} "
                 f"imb={s['load_imbalance']:.2f}")

    series = []
    for router in GATEWAY_ROUTERS:
        pts = []
        for rate in rates:
            s = fleet_drive(router, n_req=n_sweep, seed=seed,
                            burst_rate=rate)
            pt = {"offered_load": round(rate * 7.0, 3)}
            pt.update({k: round(float(s[k]), 4) for k in GATEWAY_METRICS})
            pts.append(pt)
        series.append({"label": router, "points": pts})

    scale_routers = ("prefix", "random", "round_robin")
    scale: dict = {"n_requests": n_scale}
    for router in scale_routers:
        t0 = time.time()
        s = fleet_drive(router, n_req=n_scale, seed=seed)
        scale[router] = {k: round(float(s[k]), 4) for k in GATEWAY_METRICS}
        scale[router]["bookkeeping_ops"] = s["bookkeeping_ops"]
        scale[router]["req_per_s"] = s["req_per_s"]
        if cfg.verbose:
            emit(f"gateway/scale_{router}",
                 (time.time() - t0) * 1e6 / n_scale,
                 f"n={n_scale} hit={s['hit_rate']:.3f} "
                 f"ttft={s['mean_ttft']:.1f}")

    return [
        table_experiment(
            "gateway_routers",
            "Fleet gateway — routing policy comparison on the seeded "
            "multi-tenant trace (8 replicas, global radix prefix tree)",
            ["router"] + list(GATEWAY_METRICS) + ["tree_nodes"], rows),
        sweep_experiment(
            "gateway_load",
            "Fleet gateway — TTFT / hit rate / goodput vs offered load "
            "× router", "offered_load", series,
            meta={"series_label": "router"}),
        scalars_experiment(
            "gateway_scale",
            "Fleet gateway — prefix routing vs baselines at scale "
            "(the >=100k-request trace; 1M on full runs) with the "
            "O(requests) bookkeeping bound asserted", scale),
    ]


def build_kernels(cfg: BenchConfig) -> list:
    """Beyond-paper: serpentine-vs-ascending structural DMA accounting."""
    from repro.configs import get_config
    from repro.kernels.flash_attention import serpentine_savings

    cases = [
        ("granite-3-2b", 4096, 4096, 128),
        ("mixtral-8x7b", 4096, 4096, 128),
        ("starcoder2-7b", 32768, 32768, 256),
        ("deepseek-v2-236b", 4096, 4096, 128),
        ("whisper-large-v3", 4096, 1536, 128),
    ]
    rows = []
    for arch, sq, sk, blk in cases:
        cfg_a = get_config(arch)
        n_q, n_kv = sq // blk, sk // blk
        s = serpentine_savings(n_q, n_kv)
        kv_heads = max(cfg_a.n_kv_heads, 1)
        block_bytes = blk * cfg_a.hd * 2 * 2
        saved = (s["ascending"] - s["serpentine"]) * block_bytes * kv_heads
        rows.append({
            "arch": arch, "grid": f"{n_q}x{n_kv}",
            "ascending_fetches": int(s["ascending"]),
            "serpentine_fetches": int(s["serpentine"]),
            "saved_fraction": round(float(s["saved_fraction"]), 4),
            "hbm_mb_saved_per_batch_row": round(saved / 1e6, 2),
        })
        if cfg.verbose:
            emit(f"kernel/serpentine/{arch}", 0.0,
                 f"saved={s['saved_fraction'] * 100:.1f}% of KV fetches")
    return [table_experiment(
        "kernel_serpentine",
        "Serpentine flash-attention schedule — structural KV-fetch "
        "savings",
        ["arch", "grid", "ascending_fetches", "serpentine_fetches",
         "saved_fraction", "hbm_mb_saved_per_batch_row"], rows)]


def build_roofline(cfg: BenchConfig, artifacts_dir: str | None = None) -> list:
    """Aggregate ``repro.launch.dryrun`` artifacts (if any were produced)
    into the roofline table; an empty artifacts dir yields an empty table
    rather than an error."""
    art = artifacts_dir or os.environ.get(
        "REPRO_BENCH_ARTIFACTS",
        os.path.join("benchmarks", "artifacts"))
    rows = []
    for f in sorted(glob.glob(os.path.join(art, "dryrun_*_single.json"))):
        with open(f) as fh:
            d = json.load(fh)
        if d.get("status") != "ok":
            continue
        t = d["roofline_seconds"]
        bound = max(t.values())
        rows.append({
            "arch": d["arch"], "shape": d["shape"],
            "compute_ms": round(t["compute"] * 1e3, 2),
            "memory_ms": round(t["memory"] * 1e3, 2),
            "collective_ms": round(t["collective"] * 1e3, 2),
            "dominant": d["dominant"],
            "roofline_fraction": round(t["compute"] / bound, 4),
            "useful_flop_ratio": (round(d["useful_flop_ratio"], 4)
                                  if "useful_flop_ratio" in d else None),
            "peak_gb": round(d["peak_bytes_per_device"] / 1e9, 2),
            "fits_16gb": d["fits_16gb"],
        })
    if cfg.verbose:
        emit("roofline/cells", 0.0, f"{len(rows)} single-pod cells")
    return [table_experiment(
        "roofline", "Roofline — dry-run cell aggregation (single-pod)",
        ["arch", "shape", "compute_ms", "memory_ms", "collective_ms",
         "dominant", "roofline_fraction", "useful_flop_ratio", "peak_gb",
         "fits_16gb"], rows,
        meta={"artifacts_dir": art})]


def build_verify(cfg: BenchConfig) -> list:
    """The verified-property matrix as a table experiment: the paper's
    lock-comparison table with every cell machine-checked — structural
    passes from ``core/locks/cfg.py`` always; the exhaustive T=2 model
    check from ``core/locks/verify.py`` unless ``quick`` (CI smoke runs
    keep the structural column real but skip the interleaving
    enumeration)."""
    from repro.core.locks import verify as verify_mod
    t0 = time.time()
    verdicts = verify_mod.verify_all(names=cfg.algs, model=not cfg.quick)
    bad = [v.name for v in verdicts if not v.ok]
    emit("verify.matrix", (time.time() - t0) * 1e6,
         f"locks={len(verdicts)} failed={len(bad)}")
    if bad:
        raise RuntimeError(
            f"verification failed for {bad} — run `python -m repro.bench "
            "verify` for the counterexample traces")
    note = ("Structural properties proven per spec by `core/locks/cfg.py`"
            " at compile time; interleaving properties (mutual exclusion,"
            " deadlock freedom, no lost wakeups, bounded bypass) "
            "certified by exhaustively enumerating every schedule at the "
            "stated scope (`core/locks/verify.py`)."
            if not cfg.quick else
            "Structural passes only (`--quick`): run `python -m "
            "repro.bench verify` for the model-check column.")
    return [table_experiment(
        "verify_matrix", report.VERIFY_HEADER.lstrip("# "),
        verify_mod.matrix_columns(), verify_mod.matrix_rows(verdicts),
        meta={"note": note})]


# --- registered suites -------------------------------------------------------

register("mutexbench", "MutexBench thread sweeps (Fig. 1a/1b)",
         "Throughput/miss/latency vs threads for every lock program, "
         "maximal contention and random NCS.")(build_fig1)
register("atomics", "Lock-striped atomics (Fig. 2)",
         "std::atomic<struct> analogue: shared-rw CS, empty NCS.")(build_fig2)
register("kvstore", "KV-store readrandom (Fig. 3)",
         "Coarse lock over read-only lookups with random key-gen "
         "NCS.")(build_fig3)
register("coherence", "Coherence traffic (Table 1)",
         "Invalidations / misses / NUMA-remote misses per contended "
         "episode at T=10.")(build_table1)
register("locks-ext", "Extended lock zoo (beyond paper, DESIGN.md §L2)",
         "DSL-authored lock variants (hapax, fissile, spin_then_park) "
         "vs the paper baselines: thread sweep, phase/coherence profile "
         "with the observed bypass bound, and spin_then_park park-cost "
         "sensitivity.")(build_locks_ext)
register("topology", "Machine-topology sweep (DESIGN.md §L1)",
         "Every lock across SMP / NUMA / clustered-CCX machine models "
         "via SimEngine.grid: throughput and remote-miss scaling, "
         "placement sensitivity, and the one-jit-per-grid-shape compile "
         "accounting.")(build_topology)
register("hostile", "Hostile-OS scheduler sweep (beyond paper, "
         "DESIGN.md §L1)",
         "Preemption, oversubscription and lock-holder-preemption "
         "stress via core/sim/sched.py: locks × quantum × oversub grid, "
         "LHP penalty table, and the abort-rate ladder for the "
         "timed-wait locks.")(build_hostile)
register("fairness", "Fairness and bounded bypass (Table 2, §9)",
         "Palindromic admission cycle, long-run unfairness, §9.4 "
         "mitigation, and bypass histograms over core.admission "
         "policies.")(build_fairness)
register("residency", "Cache residency (App. C)",
         "Residual-residency decay model: palindrome vs FIFO under "
         "Jensen's inequality.")(build_residency)
register("scheduler", "Serving-scheduler admission (beyond paper)",
         "Reciprocating admission vs FIFO/LIFO in the continuous "
         "batcher.")(build_scheduler)
register("serve", "Serving engine (beyond paper, docs/SERVING.md)",
         "Policy × offered-load sweep on the unified continuous-batching "
         "core with the paged-KV pool, plus the model-backed engine "
         "smoke (full runs).")(build_serve)
register("gateway", "Fleet serving gateway (beyond paper, "
         "docs/SERVING.md §8)",
         "Multi-replica gateway with prefix-aware routing over a global "
         "radix prefix tree: router comparison table, offered-load "
         "sweep, and the 100k/1M-request at-scale run with the "
         "O(requests) bookkeeping bound asserted.")(build_gateway)
register("measured", "Measured tier: Pallas-backend paper sweeps "
         "(DESIGN.md §L2)",
         "Fig 1-3 style throughput/latency sweeps executed as real "
         "Pallas kernels over the device atomics layer (compiled for the "
         "TPU; --interpret for the Pallas interpreter), the sim-vs-Pallas "
         "backend-agreement table, "
         "and the CostModel calibration error table "
         "(bench/calibrate.py).")(build_measured)
register("kernels", "Serpentine kernel accounting (beyond paper)",
         "Structural KV-fetch savings of the serpentine flash-attention "
         "schedule.")(build_kernels)
register("roofline", "Roofline aggregation",
         "Aggregates repro.launch.dryrun artifacts into the roofline "
         "table.")(build_roofline)
register("verify", "Verified lock properties (DESIGN.md §L2)",
         "The paper's lock-comparison table, machine-checked: structural "
         "proofs (constant-time doorway/release, spin locality, waiting "
         "footprint) from core/locks/cfg.py plus the exhaustive "
         "small-scope model check (core/locks/verify.py).")(build_verify)


@register("paper", "Paper reproduction (Figs 1-3, Table 1, fairness)",
          "End-to-end reproduction of the paper's evaluation: "
          "throughput-vs-threads for every lock program, coherence "
          "traffic, fairness and bounded-bypass histograms — plus the "
          "beyond-paper extended lock zoo (locks-ext), machine-topology "
          "(topology), hostile-OS scheduler (hostile), serving "
          "(docs/SERVING.md), fleet-gateway (SERVING.md §8) and "
          "measured Pallas-backend (bench/measured.py) sections.",
          tags=("paper",))
def build_paper(cfg: BenchConfig) -> list:
    exps = []
    cells: dict = {}
    exps += build_fig1(cfg, on_result=lambda a, t, r:
                       cells.__setitem__((a, t), r))
    exps += build_fig2(cfg)
    exps += build_fig3(cfg)
    exps += build_table1(cfg)
    # locks-ext reuses Fig. 1a's max-contention curves and cells
    # (identical settings) and only simulates its park extras on top.
    fig1a = next(e for e in exps if e["name"] == "fig1a_max_contention")
    exps += build_locks_ext(cfg, reuse_series=fig1a["series"],
                            reuse_cells=cells)
    exps += build_topology(cfg)
    exps += build_hostile(cfg)
    exps += build_fairness(cfg)
    exps += build_serve(cfg)
    exps += build_gateway(cfg)
    exps += build_measured(cfg)
    exps += build_verify(cfg)
    return exps
