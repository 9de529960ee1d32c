"""Driver of the ``sim_grid`` traffic: back-to-back ``SimEngine.grid``
calls of a ``lock_sim`` configuration.

Set-up builds one ``SimEngine`` per lock and makes one grid call per
lock (its compile or compile-cache load). The window then runs rounds
of grid calls, one call per lock, each with fresh seeds drawn from
``--seed``, and ends at the end of the round in which ``--seconds`` have
passed; a call ends when its summarised ``GridResult`` is on the host.
``lock_steps_per_s`` is the simulated micro-steps of the completed calls
(points x steps) over the window's seconds. A ``--trace 1`` run traces
the window's first ``trace_calls`` calls, which the per-layer readers
read, and runs the rest of the window untraced. After the window a
sample of calls drawn from the seed, one per lock, is compared point by
point with the plain reference (``reference/lock_machine.py``), field
by field. The reference holds itself to the configuration's guarantees
(mutual exclusion, the bypass bounds) on every point it runs.
"""
from __future__ import annotations

import time
from functools import partial
from types import SimpleNamespace

import numpy as np

from chipbench import harness as H
from chipbench import work
from chipbench.reference import lock_machine as ref

#: BenchResult fields compared with the reference, and bypass_bound,
#: which both sides derive from a cell's admission logs
FIELDS = ("throughput", "episodes", "miss_per_episode",
          "inval_per_episode", "remote_per_episode", "latency",
          "unfairness", "admissions", "admission_counts", "aborts",
          "preempts")


def topologies(cfg: dict) -> list:
    from repro.core.sim.topology import Level, Topology
    c = cfg["cost"]
    return [Topology(name, levels=tuple(Level(*lv) for lv in levels),
                     hit=c["hit"], park_cost=c["park"],
                     unpark_cost=c["unpark"], resched_cost=c["resched"])
            for name, levels in cfg["topologies"].items()]


def seed_stream(seed: int, stream: int, size: int):
    """Endless draws of ``size`` grid seeds (int32) from ``(seed,
    stream)``: stream 0 feeds the window, stream 1 the warm-up."""
    rng = np.random.default_rng([seed, stream])
    while True:
        yield [int(s) for s in rng.integers(0, 2**31 - 1, size=size)]


def reference_cells(cfg: dict, lock: str, seeds, broken=False) -> dict:
    """topology -> the reference's summary of one grid call's points:
    ``seeds`` on that topology, in order. ``broken`` runs the control's
    reciprocating lock."""
    make = (partial(ref.reciprocating, broken=True) if broken
            else ref.LOCKS[lock])
    out = {}
    for name, levels in cfg["topologies"].items():
        pts = [ref.simulate(make(cfg["threads"]), levels,
                            cfg["steps_per_call"], hit=cfg["cost"]["hit"],
                            seed=s, ncs_max=cfg["ncs_max"])
               for s in seeds]
        bound = cfg["guarantees"]["bypass_bound"].get(lock)
        if not broken and any(p["me_violations"] or (
                bound is not None and p["bypass"] > bound) for p in pts):
            raise H.BenchError(f"the reference broke a guarantee of "
                               f"{lock} on {name}")
        out[name] = ref.summarize(pts)
    return out


class ControlEngine:
    """The control in the program's place: a grid call answered by the
    reference with a reciprocating lock whose doorway lets a second
    thread in (``reference.lock_machine.reciprocating(broken=True)``)."""

    def __init__(self, lock: str, cfg: dict):
        if lock != "reciprocating":
            raise H.BenchError("the control breaks reciprocating only")
        self.cfg = cfg

    def grid(self, *, seeds, topologies, workloads, threads):
        got = reference_cells(self.cfg, "reciprocating", seeds, broken=True)
        return SimpleNamespace(cells=[
            SimpleNamespace(topology=t.name, result=SimpleNamespace(**{
                k: np.asarray(v) if isinstance(v, list) else v
                for k, v in got[t.name].items()}))
            for t in topologies])


def program_engine(lock: str, cfg: dict):
    from repro.core.sim.engine import SimEngine
    return SimEngine(lock, scheduler=cfg["scheduler"])


def differing(result, want: dict) -> list:
    """Names of the fields in which a program cell differs from the
    reference's."""
    bad = []
    for f in FIELDS:
        got = getattr(result, f)
        got = got.tolist() if hasattr(got, "tolist") else got
        if got != want[f]:
            bad.append(f)
    if ref.bypass_bound(result.admissions,
                        result.admission_counts) != want["bypass_bound"]:
        bad.append("bypass_bound")
    return bad


def run(ctx: H.RunContext, engine=program_engine) -> H.RunOutput:
    from repro.core.sim.engine import Workload, trace_count

    cfg, tr = ctx.cell.config, ctx.cell.traffic
    if cfg["scheduler"] != "dedicated":
        raise H.BenchError("the lock_machine reference covers a dedicated "
                           "scheduler only")
    T, steps = cfg["threads"], cfg["steps_per_call"]
    topos = topologies(cfg)
    n_seeds = cfg["seeds_per_topology"]
    points = n_seeds * len(topos)
    wl = Workload(cfg["ncs_max"], cfg["cs"], steps, label="mutexbench")
    locks = list(cfg["locks"])
    engines = {lock: engine(lock, cfg) for lock in locks}
    spans = ctx.spans

    def call(lock, seeds):
        with spans.span("sim.grid"):
            return engines[lock].grid(seeds=seeds, topologies=topos,
                                      workloads=[wl], threads=[T])

    warm = seed_stream(ctx.seed, 1, n_seeds)
    for lock in locks:
        for _ in range(tr["warmup_calls_per_lock"]):
            call(lock, next(warm))
    setup_s = time.perf_counter() - ctx.t_start
    H.log(f"[setup] setup_s={setup_s:.3f} ({len(locks)} locks x "
          f"{points} points x {steps} steps)")

    seeds = seed_stream(ctx.seed, 0, n_seeds)
    done, failed, rounds = [], 0, 0
    # a traced run traces only the window's first calls: the trace of a
    # scan holds every iteration's operations, some 0.7 GB a call, and
    # the profiler drops what passes 2 GB
    n_traced = tr["trace_calls"] if ctx.trace else 0
    prof = H.Profiler(ctx.trace)
    traced = spans.span("window")
    traces0 = trace_count()
    t0 = time.perf_counter()
    if n_traced:
        prof.start()
        traced.__enter__()
    while True:
        for lock in locks:
            s = next(seeds)
            try:
                done.append((lock, s, call(lock, s)))
            except Exception as e:          # a failed call counts, and
                failed += 1                 # the window goes on
                H.log(f"[window] grid call of round {rounds} ({lock}) "
                      f"failed: {e!r}")
            if len(done) + failed == n_traced:
                traced.__exit__(None, None, None)
                prof.halt()
        rounds += 1
        t_end = time.perf_counter()
        if t_end - t0 >= ctx.seconds:
            break
    traces_in_window = trace_count() - traces0
    reduced = prof.stop()
    window_s = t_end - t0
    device = ctx.device()
    if ctx.trace and reduced:
        device.update(busy_s=reduced["busy_s"],
                      window_s=reduced["window_s"])
    sim_steps = len(done) * points * steps
    H.log(f"[window] {rounds} rounds, {len(done) + failed} grid calls in "
          f"{window_s:.3f} s, {len(done)} completed, {sim_steps} "
          f"micro-steps, {traces_in_window} traces")
    calls = sorted((b - a, i) for i, (_, a, b) in enumerate(
        x for x in spans.items if x[0] == "sim.grid" and x[1] >= t0))
    H.log(f"[window] call seconds: median {calls[len(calls) // 2][0]:.4f}, "
          f"slowest " + ", ".join(f"{d:.4f} (call {i})"
                                  for d, i in calls[:-4:-1]))

    # every point of a sample of calls, drawn from the seed, against the
    # reference
    t_ref = time.perf_counter()
    pick = np.random.default_rng([ctx.seed, 2])
    sample = []
    for lock in locks:
        mine = [d for d in done if d[0] == lock]
        k = min(tr["compared_calls_per_lock"], len(mine))
        sample += [mine[i] for i in sorted(pick.choice(len(mine), k,
                                                       replace=False))]
    bad_fields, compared = 0, 0
    for lock, s, g in sample:
        want = reference_cells(cfg, lock, s)
        for cell in g.cells:
            bad = differing(cell.result, want[cell.topology])
            if bad:
                H.log(f"[check] {lock} {cell.topology} seeds {s}: differs "
                      f"from the reference in {bad}")
            bad_fields += len(bad)
            compared += n_seeds
    H.log(f"[check] {compared} points of {len(sample)} calls against the "
          f"reference in {time.perf_counter() - t_ref:.3f} s")
    checks = [
        H.Check("points_compared", compared, len(locks) * points,
                at_least=True),
        H.Check("fields_differing", bad_fields, 0),
        H.Check("failed_calls", failed, 0),
    ]
    peaks = ctx.peaks() if ctx.trace else None
    record = {
        "trace": reduced,
        "counters": {"sim_traces_in_window": traces_in_window,
                     "grid_calls": len(done),
                     "traced_scan_iterations": n_traced * steps,
                     "traced_sim_bytes": n_traced * points * steps
                     * work.sim_bytes_per_step(T)},
        "peaks": peaks,
        "breakdown": ({"device_ops": reduced["device_ops"],
                       "idle_gaps": reduced["idle_gaps"]}
                      if reduced else None),
    }
    return H.RunOutput(
        checks=checks, attempted=len(done) + failed, failed=failed,
        end_to_end={"lock_steps_per_s": sim_steps / window_s,
                    "setup_s": setup_s},
        device=device, record=record)
