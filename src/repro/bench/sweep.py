"""Sweep driver: ``SimEngine`` grids over the lock simulator.

The unit of work is a *cell* — one (lock, thread count, machine,
workload) grid point. Cells run through the per-lock ``SimEngine``
sessions (``core/sim/engine.py``): thread count and workload fix the
compiled shape, while the seed and topology axes are stacked
``LoweredCost`` data vmapped through **one jit per shape** — Table 1's
1-node and 2-node variants, and the whole SMP/NUMA/CCX grid of the
``topology`` suite, share a compile (the engine's ``compiles`` counter
is what the CI batching assertion watches).

Cells are served through the content-addressed experiment cache
(``bench/cache.py``): ``cached_grid`` keys every cell of a grid call on
the canonical (program, machine, scheduler, workload, seeds) hash, and
an all-hit grid reconstructs its ``GridResult`` from the store with
zero XLA traces. Any miss runs the *whole* grid once (preserving the
one-jit batching contract) and stores every cell.

Also here: the admission-queue bypass instrumentation (paper §2 bounded
bypass, §9.4 mitigation) driven against ``repro.core.admission`` policies,
and the reference-interleaver fairness probes (Table 2).
"""
from __future__ import annotations

import time
import warnings
from dataclasses import replace

import numpy as np

from repro.bench import cache as cachemod
from repro.bench.registry import BenchConfig, emit
from repro.core.admission import POLICIES, max_bypass_bound
from repro.core.locks.programs import PROGRAMS
from repro.core.sim.engine import (
    GridCell, GridResult, SimEngine, Workload, cost_label, resolve_workload,
    sched_label, session, _lower_host, _lower_sched_host,
)
from repro.core.sim.machine import CostModel, MachineState

ALL_ALGS = tuple(sorted(PROGRAMS))

# Point metrics exported into sweep series (BenchResult field -> key).
POINT_METRICS = ("throughput", "miss_per_episode", "inval_per_episode",
                 "remote_per_episode", "latency", "unfairness")


def run_grid(prog, n_threads: int, n_steps: int, seeds, n_nodes,
             cost: CostModel = CostModel()) -> MachineState:  # noqa: B008
    """Deprecated shim: elementwise (seed, n_nodes) batch in one jit.
    Per-point cost models are now built with ``dataclasses.replace`` —
    every ``CostModel`` field rides through — and lowered to the stacked
    matrix batch by the engine. Use ``SimEngine.grid`` directly."""
    warnings.warn(
        "run_grid is deprecated; use repro.core.sim.engine.SimEngine"
        "(...).grid(seeds=..., topologies=[...])",
        DeprecationWarning, stacklevel=2)
    eng = SimEngine(prog, n_threads=n_threads,
                    workload=Workload(n_steps=n_steps))
    lows = [replace(cost, n_nodes=int(nn)) for nn in np.asarray(n_nodes)]
    slo = _lower_sched_host(None, n_threads)
    return eng._run_batch([int(s) for s in np.asarray(seeds)],
                          [_lower_host(c, n_threads) for c in lows],
                          [slo] * len(lows), eng.workload, n_threads)[0]


def cached_grid(alg: str, *, seeds, topologies=None, workloads=None,
                schedulers=None, threads=None) -> GridResult:
    """``session(alg).grid(...)`` fronted by the experiment cache.

    Computes the content key of every cell the grid *would* produce (in
    the engine's exact cell order: threads-major, then workload, then
    topology, then scheduler). All hits -> a ``GridResult`` rebuilt from
    the store, ``compiles == 0``, no simulation. Any miss -> one real
    grid call (the full batch, so the one-jit-per-shape contract and its
    compile accounting are untouched) whose cells are all stored."""
    eng = session(alg)
    store = cachemod.get_cache()
    if not store.enabled:
        return eng.grid(seeds=seeds, topologies=topologies,
                        workloads=workloads, schedulers=schedulers,
                        threads=threads)
    seeds = [int(s) for s in seeds]
    topos = (list(topologies) if topologies is not None
             else [eng.topology])
    schs = (list(schedulers) if schedulers is not None
            else [eng.scheduler])
    wls = [resolve_workload(w) if w is not None else eng.workload
           for w in (workloads if workloads is not None
                     else [eng.workload])]
    ts = list(threads) if threads is not None else [eng.n_threads]
    plan = []      # (key, n_threads, workload, topo label, sched label)
    for T in ts:
        lows = [(cost_label(c), _lower_host(c, T)) for c in topos]
        slos = [(sched_label(s), _lower_sched_host(s, T)) for s in schs]
        for wl in wls:
            fp = cachemod.program_fingerprint(eng.program(T, wl))
            for lab, lo in lows:
                for slab, sl in slos:
                    plan.append((cachemod.cell_key(fp, T, wl, lo, sl,
                                                   seeds),
                                 T, wl, lab, slab))
    found = [store.get(key) for key, *_ in plan]
    if all(doc is not None for doc in found):
        store.stats.hits += len(plan)
        cells = tuple(
            GridCell(lock=eng.name, n_threads=T, topology=lab,
                     workload=wl.name, scheduler=slab,
                     result=cachemod.result_from_doc(doc))
            for doc, (_, T, wl, lab, slab) in zip(found, plan))
        return GridResult(cells, 0)
    store.stats.misses += len(plan)
    g = eng.grid(seeds=seeds, topologies=topos, workloads=wls,
                 schedulers=schs, threads=ts)
    for (key, *_), cell in zip(plan, g.cells):
        store.put(key, cachemod.result_to_doc(cell.result))
    return g


def default_machine(cfg: BenchConfig, n_threads: int) -> CostModel:
    """The historical default machine for a cell: flat, 2 NUMA nodes
    above ``cfg.numa_above`` threads."""
    return CostModel(n_nodes=2 if n_threads > cfg.numa_above else 1)


def bench_cell(alg: str, n_threads: int, cfg: BenchConfig, *,
               ncs_max: int = 0, cs_shared=True, n_nodes=None,
               topology=None):
    """One cell through the shared per-lock session; returns BenchResult.
    ``topology`` (a ``Topology``/``CostModel``/preset name) overrides the
    flat ``n_nodes`` default."""
    if topology is None:
        topology = (default_machine(cfg, n_threads) if n_nodes is None
                    else CostModel(n_nodes=n_nodes))
    g = cached_grid(
        alg,
        seeds=range(cfg.seed0, cfg.seed0 + cfg.n_replicas),
        topologies=[topology],
        workloads=[Workload(ncs_max, cs_shared, cfg.n_steps)],
        threads=[n_threads])
    return g.cells[0].result


def lock_sweep(algs, cfg: BenchConfig, *, ncs_max: int = 0, cs_shared=True,
               tag: str = "sweep", on_result=None) -> list:
    """Thread sweep for each algorithm -> schema series list.
    ``on_result(alg, threads, BenchResult)`` lets a caller reuse the full
    per-cell results (e.g. locks-ext's profile table) without re-running
    the cells."""
    series = []
    for alg in algs:
        points = []
        for t in cfg.threads:
            t0 = time.time()
            r = bench_cell(alg, t, cfg, ncs_max=ncs_max, cs_shared=cs_shared)
            wall = time.time() - t0
            if on_result is not None:
                on_result(alg, t, r)
            p = {"threads": t, "episodes": r.episodes,
                 "wall_s": round(wall, 3)}
            for m in POINT_METRICS:
                p[m] = round(float(getattr(r, m)), 4)
            points.append(p)
            if cfg.verbose:
                emit(f"{tag}/{alg}/T{t}",
                     wall / max(r.episodes, 1) * 1e6,
                     f"thr={r.throughput:.3f}/kcyc "
                     f"miss/ep={r.miss_per_episode:.2f}")
        series.append({"label": alg, "points": points})
    return series


def coherence_rows(algs, cfg: BenchConfig, n_threads: int = 10,
                   paper: dict | None = None) -> list:
    """Table 1: coherence traffic per episode, degenerate local CS. The
    1-node and 2-node NUMA variants run in one jit per algorithm."""
    paper = paper or {}
    n_threads = min(n_threads, max(max(cfg.threads), 2))
    rows = []
    for alg in algs:
        t0 = time.time()
        # both NUMA variants are one stacked-topology grid: one jit/alg
        g = cached_grid(
            alg,
            seeds=range(cfg.seed0, cfg.seed0 + cfg.n_replicas),
            topologies=[CostModel(n_nodes=1), CostModel(n_nodes=2)],
            workloads=[Workload(0, False, cfg.n_steps)],
            threads=[n_threads])
        r1 = g.cell(topology="flat:1").result
        r2 = g.cell(topology="flat:2").result
        rows.append({
            "lock": alg,
            "miss_per_episode": round(r1.miss_per_episode, 2),
            "inval_per_episode": round(r1.inval_per_episode, 2),
            "remote_per_episode_numa": round(r2.remote_per_episode, 2),
            "paper_invalidations": paper.get(alg),
        })
        if cfg.verbose:
            emit(f"coherence/{alg}", (time.time() - t0) * 1e6
                 / max(r1.episodes, 1),
                 f"miss/ep={r1.miss_per_episode:.2f} "
                 f"paper={paper.get(alg)}")
    return rows


# --- admission-policy instrumentation (core.admission) ----------------------

def bypass_trace(policy: str, n_threads: int = 8, n_events: int = 2000,
                 seed: int = 0) -> dict:
    """Closed-loop drive of an ``AdmissionQueue``: every thread re-arrives
    immediately after service (sustained contention). For each completed
    wait, record how many admissions of later arrivals overtook it —
    total, and by any *single* other thread (the paper's §2 bound is 1 for
    reciprocating, 0 for FIFO, unbounded for LIFO)."""
    q = POLICIES[policy](seed)
    arrival: dict = {}
    suffered: dict = {}
    by_thread: dict = {}
    seq = 0
    for t in range(n_threads):
        q.push(t)
        arrival[t], suffered[t], by_thread[t] = seq, 0, {}
        seq += 1
    per_wait, per_wait_single = [], []
    for _ in range(n_events):
        s = q.pop()
        if s is None:
            break
        for t, a in arrival.items():
            if t != s and a < arrival[s]:
                suffered[t] += 1
                by_thread[t][s] = by_thread[t].get(s, 0) + 1
        per_wait.append(suffered[s])
        per_wait_single.append(max(by_thread[s].values(), default=0))
        del arrival[s]
        arrival[s], suffered[s], by_thread[s] = seq, 0, {}
        q.push(s)
        seq += 1
    return {
        "per_wait": per_wait,
        "per_wait_single": per_wait_single,
        # threads still waiting at the end (LIFO starvation shows here)
        "max_outstanding": max(suffered.values(), default=0),
    }


def bypass_histograms(policies, n_threads: int = 8, n_events: int = 2000,
                      seed: int = 0, max_bin: int = 8):
    """Histogram the per-wait bypass counts for each admission policy.

    Returns ``(bins, series, stat_rows)`` where bins are
    ``[0, 1, ..., max_bin-1, f"{max_bin}+"]``.
    """
    bins = [str(i) for i in range(max_bin)] + [f"{max_bin}+"]
    series, stat_rows = [], []
    for pol in policies:
        tr = bypass_trace(pol, n_threads=n_threads, n_events=n_events,
                          seed=seed)
        counts = [0] * (max_bin + 1)
        for v in tr["per_wait"]:
            counts[min(v, max_bin)] += 1
        series.append({"label": pol, "counts": counts})
        bound = max_bypass_bound(pol, n_threads)
        stat_rows.append({
            "policy": pol,
            "completed_waits": len(tr["per_wait"]),
            "mean_bypass": round(float(np.mean(tr["per_wait"] or [0])), 3),
            "max_bypass_per_wait": int(max(tr["per_wait"], default=0)),
            "max_bypass_by_single_thread":
                int(max(tr["per_wait_single"], default=0)),
            "max_outstanding_unserved": int(tr["max_outstanding"]),
            "theoretical_single_thread_bound":
                ("inf" if bound == float("inf") else int(bound)),
        })
    return bins, series, stat_rows


# --- reference-interleaver fairness probes (Table 2, §9) --------------------

def reference_fairness(n_threads: int = 5, n_ops: int = 8000) -> dict:
    from repro.core.locks.reference import ALGORITHMS
    from repro.core.sim.interleave import run as ref_run
    r = ref_run(ALGORITHMS["reciprocating"](n_threads), n_threads,
                n_ops=n_ops, policy="rr")
    cyc = r.cycle()
    letters = "ABCDEFGH"[:n_threads]
    return {
        "cycle": list(cyc) if cyc else None,
        "cycle_str": "".join(letters[t] for t in cyc) if cyc else None,
        "cycle_admissions_sorted":
            sorted(cyc.count(t) for t in range(n_threads)) if cyc else None,
        "unfairness": round(r.unfairness(), 3),
    }


def mitigated_unfairness(n_threads: int = 5, n_events: int = 4000,
                         seed: int = 0) -> float:
    """§9.4 randomized intra-segment order: long-run max/min admissions."""
    from repro.core.admission import ReciprocatingQueue
    q = ReciprocatingQueue(seed, mitigate=True)
    counts = np.zeros(n_threads, int)
    for i in range(n_events):
        q.push(i % n_threads)
        got = q.pop()
        if got is not None:
            counts[got] += 1
    return float(counts.max() / max(counts.min(), 1))
