"""``SimEngine``: the one execution session API over the lock simulator.

Historically the simulator grew five overlapping free-function entry
points (``run_machine``, ``run_ensemble``, ``bench_lock``, ``run_grid``,
``bench_cell``) that each re-plumbed a flat ``CostModel`` by hand. The
engine replaces them with a single composable session:

    eng = SimEngine("reciprocating", topology=numa(2, 8),
                    workload=Workload(ncs_max=250))
    r   = eng.run(seed=0)                     # one BenchResult
    r   = eng.ensemble(range(4))              # seed ensemble, one jit
    g   = eng.grid(seeds=range(4),            # seed x topology batched
                   topologies=[smp(16), numa(2, 8), "epyc-2s"],
                   workloads=["max_contention", "readonly"],
                   schedulers=["dedicated", "fair-4x"],
                   threads=[8, 16])
    g.cell(topology="numa2x8", workload="readonly").result.throughput

Batching contract (what the compile-count CI assertion pins): the seed,
topology and *scheduler* axes are *data* — every topology lowers to a
stacked ``LoweredCost`` thread x thread matrix batch, every scheduler to
a stacked ``LoweredSched`` scalar batch (``core/sim/sched.py``), and the
whole batch runs through **one jit per (threads, workload) shape**.
Thread counts change array shapes and workloads change the compiled
program, so each pair gets exactly one entry in the session's explicit
compile cache; re-running the same shape costs zero new XLA traces.
``self.compiles`` counts real traces (incremented from inside the traced
function), and ``GridResult.compiles`` reports how many a given grid
call paid. Per-session counters under-count the *process*: code that
builds a fresh engine per call (``api.bench_lock``, ad-hoc scripts)
pays traces no session sees, so suite-level accounting (BENCH_trend)
reads the module-wide ``trace_count()`` instead — it is bumped from the
same trace-time site as ``self.compiles`` for every engine in the
process.

Sharded execution: ``SimEngine(shard=...)`` (or the per-call ``shard=``
override on ``grid``) routes the vmapped point batch through
``shard_map`` over a 1-D device mesh, splitting the stacked
seed x topology x scheduler axis across devices. ``"auto"`` (the
default) shards only when >1 device is visible, so a single-device host
runs the plain vmap path; ``True`` forces the
shard_map path even on one device (a mesh of 1 — what the differential
equality tests exercise in-process). Batches are padded to a multiple
of the shard count by replicating the last point and trimmed after the
run; every point is an independent element-wise simulation, so sharded
and unsharded grids are bit-identical (pinned by
``tests/test_sweep_cache.py``).

``bench_lock`` / ``sweep_threads`` (core.sim.api), ``run_ensemble``
(core.sim.machine) and the ``repro.bench`` sweep driver are now thin
wrappers or deprecation shims over this class. See DESIGN.md §L1 for
the topology model and docs/RESULTS.md's topology section for what the
grid axes buy.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.sim import sched as schedmod
from repro.core.sim import topology as topo
from repro.core.sim.api import BenchResult, summarize_ensemble
from repro.core.sim.machine import (
    CostModel, LoweredCost, LoweredSched, Program, lower_cost, run_machine,
)

__all__ = ["Workload", "WORKLOADS", "SimEngine", "GridCell", "GridResult",
           "cost_label", "sched_label", "session", "trace_count"]


# --- process-wide trace accounting -------------------------------------------

_TRACES = 0


def _bump_traces() -> None:
    global _TRACES
    _TRACES += 1


def trace_count() -> int:
    """Process-wide count of fresh simulator XLA traces, across *every*
    engine — including throwaway ones no session counter sees. Deltas of
    this are what ``BENCH_trend.json`` reports per suite run."""
    return _TRACES


# --- sharded execution -------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _mesh(n_shards: int):
    from repro.sharding.ctx import make_mesh
    return make_mesh((n_shards,), ("cells",))


def _resolve_shards(mode) -> int:
    """Shard count: 0 means the plain vmap path; k >= 1 wraps the vmap
    in ``shard_map`` over a k-device mesh. ``"auto"`` shards only when
    >1 device is visible; ``True`` forces the shard_map path even on one
    device; an int asks for that many shards (clamped to the device
    count). A mesh that cannot be built is an error, not a fallback."""
    if mode in (None, False, 0):
        return 0
    n_dev = jax.device_count()
    if mode == "auto":
        return n_dev if n_dev > 1 else 0
    if mode is True:
        return n_dev
    return max(min(int(mode), n_dev), 1)


# --- workloads ---------------------------------------------------------------

@dataclass(frozen=True)
class Workload:
    """MutexBench workload knobs (paper §7.1) as one value: the random
    NCS delay bound, the CS profile (``"rw"``/``"ro"``/``"local"`` or the
    historical bool), and the horizon in machine micro-steps."""
    ncs_max: int = 0
    cs: object = True
    n_steps: int = 20_000
    label: str = ""

    @property
    def cs_mode(self) -> str:
        return self.cs if isinstance(self.cs, str) else (
            "rw" if self.cs else "local")

    @property
    def name(self) -> str:
        return self.label or f"{self.cs_mode}/ncs{self.ncs_max}"


#: Named workloads mirroring the paper's evaluation regimes.
WORKLOADS: dict = {
    "max_contention": Workload(0, "rw", label="max_contention"),
    "random_ncs": Workload(250, "rw", label="random_ncs"),
    "readonly": Workload(60, "ro", label="readonly"),
    "local_cs": Workload(0, "local", label="local_cs"),
}


def resolve_workload(w) -> Workload:
    if isinstance(w, Workload):
        return w
    try:
        return WORKLOADS[w]
    except (KeyError, TypeError):
        raise KeyError(f"unknown workload {w!r}; named workloads: "
                       f"{sorted(WORKLOADS)}") from None


# --- cost descriptions -------------------------------------------------------

def _resolve_cost(t):
    """Topology | CostModel | LoweredCost | preset-name string."""
    if isinstance(t, str):
        return topo.resolve(t)
    return t


def cost_label(t) -> str:
    """Stable display label for a grid's topology axis."""
    t = _resolve_cost(t)
    if isinstance(t, topo.Topology):
        return t.name
    if isinstance(t, CostModel):
        lab = f"flat:{t.n_nodes}"
        if (t.park_cost, t.unpark_cost) != (CostModel.park_cost,
                                            CostModel.unpark_cost):
            lab += f"/park{t.park_cost}+{t.unpark_cost}"
        return lab
    return "lowered"


def _lower_host(t, n_threads: int) -> tuple:
    """Lower to host ``(hit, miss, remote, park, unpark, resched)``
    arrays via the one true lowering (``machine.lower_cost``), so the
    engine path can never diverge from the ``run_machine`` path —
    concrete data, ready to stack into a topology batch the jit never
    specializes on."""
    return tuple(np.asarray(x)
                 for x in lower_cost(_resolve_cost(t), n_threads))


def sched_label(s) -> str:
    """Stable display label for a grid's scheduler axis."""
    return schedmod.resolve(s).name


def _lower_sched_host(s, n_threads: int) -> tuple:
    """Lower a scheduler description to host ``(quantum, lhp_quantum,
    cores, jitter)`` scalars — stacked-data siblings of ``_lower_host``
    so the scheduler axis never adds an XLA trace."""
    return tuple(np.asarray(x)
                 for x in schedmod.resolve(s).lower(n_threads))


# --- grid results ------------------------------------------------------------

@dataclass(frozen=True)
class GridCell:
    lock: str
    n_threads: int
    topology: str             # cost_label of the machine
    workload: str             # Workload.name
    result: BenchResult
    scheduler: str = "dedicated"   # sched_label of the OS model


@dataclass(frozen=True)
class GridResult:
    """Flat cell list (threads-major, then workload, then topology) plus
    the number of fresh XLA traces this grid call paid — 0 when every
    (threads, workload) shape was already in the session cache — and
    the number of devices the runner's output was computed on, read
    from its sharding (0: plain vmap on the default device; a cache
    replay reports 0 too)."""
    cells: tuple
    compiles: int
    shards: int = 0

    def __iter__(self):
        return iter(self.cells)

    def __len__(self):
        return len(self.cells)

    def results(self) -> list:
        return [c.result for c in self.cells]

    def cell(self, **want) -> GridCell:
        """The unique cell matching the given field values, e.g.
        ``g.cell(topology="numa2x8", workload="readonly")``."""
        hits = [c for c in self.cells
                if all(getattr(c, k) == v for k, v in want.items())]
        if len(hits) != 1:
            raise KeyError(f"{len(hits)} cells match {want}; have "
                           f"{[(c.n_threads, c.topology, c.scheduler, c.workload) for c in self.cells]}")
        return hits[0]


# --- the session -------------------------------------------------------------

class SimEngine:
    """One lock, many machines: a session holding the compile caches.

    ``lock`` is a registry name (``PROGRAMS``), a spec-builder callable
    with the ``(n_threads, ncs_max=..., cs_shared=...)`` signature (e.g.
    ``functools.partial(compile_spec, my_spec)``), or an already-built
    ``Program`` (then ``workload.ncs_max``/``cs`` are baked in and only
    ``n_steps`` applies). ``topology`` / ``workload`` / ``scheduler`` /
    ``n_threads`` set session defaults; every method takes per-call
    overrides. ``scheduler`` accepts anything ``sched.resolve`` does
    (``Scheduler``, preset name, ``"fair:QxR"`` shorthand, or ``None``
    for the dedicated machine). ``shard`` picks the batch execution
    path (see ``_resolve_shards``): ``"auto"`` (default) splits the
    stacked point axis across devices when more than one is visible and
    is a plain vmap otherwise.
    """

    def __init__(self, lock, *, topology=None, workload=None,
                 scheduler=None, n_threads: int = 8,
                 name: str | None = None, shard="auto"):
        if isinstance(lock, Program):
            self._fixed, self._builder = lock, None
            self.name = name or lock.name
        elif callable(lock):
            self._fixed, self._builder = None, lock
            self.name = name or getattr(lock, "__name__", "lock")
        else:
            from repro.core.locks.programs import PROGRAMS
            self._fixed, self._builder = None, PROGRAMS[lock]
            self.name = name or lock
        self.topology = topology if topology is not None else CostModel()
        self.workload = (resolve_workload(workload) if workload is not None
                         else Workload())
        self.scheduler = schedmod.resolve(scheduler)
        self.n_threads = n_threads
        self.shard = shard
        self._progs: dict = {}
        self._jits: dict = {}
        #: fresh XLA traces this session has paid (trace-time counter)
        self.compiles = 0

    # -- compile caches ------------------------------------------------------
    def program(self, n_threads: int | None = None,
                workload=None) -> Program:
        """The compiled lock program for (threads, workload), cached."""
        T = n_threads or self.n_threads
        wl = (resolve_workload(workload) if workload is not None
              else self.workload)
        if self._fixed is not None:
            return self._fixed
        key = (T, wl.ncs_max, wl.cs_mode)
        prog = self._progs.get(key)
        if prog is None:
            prog = self._progs[key] = self._builder(
                T, ncs_max=wl.ncs_max, cs_shared=wl.cs)
        return prog

    def _runner(self, T: int, wl: Workload, n_points: int,
                n_shards: int = 0):
        """The jitted batched executor for one (threads, workload) shape:
        vmap of the scan engine over ``n_points`` (seed, LoweredCost,
        LoweredSched) triples — wrapped in ``shard_map`` over a 1-D
        device mesh when ``n_shards >= 1``. One XLA trace per cache key,
        counted in ``compiles`` — scheduler scalars are vmapped data,
        never part of the key; the shard count IS part of the key, so
        toggling shard modes never reuses the wrong executable."""
        key = (T, wl.ncs_max, wl.cs_mode, wl.n_steps, n_points, n_shards)
        fn = self._jits.get(key)
        if fn is None:
            prog = self.program(T, wl)

            def go(seeds, hit, miss, remote, park, unpark, resched,
                   quantum, lhp, cores, jitter):
                self.compiles += 1     # runs at trace time only
                _bump_traces()

                def one(seed, h, m, r, p, u, rs, q, lq, co, ji):
                    return run_machine(prog, T, wl.n_steps,
                                       LoweredCost(h, m, r, p, u, rs),
                                       seed,
                                       LoweredSched(q, lq, co, ji))
                batched = jax.vmap(one)
                if n_shards:
                    spec = jax.sharding.PartitionSpec("cells")
                    batched = jax.shard_map(batched, mesh=_mesh(n_shards),
                                            in_specs=spec, out_specs=spec,
                                            check_vma=False)
                return batched(seeds, hit, miss, remote, park,
                               unpark, resched, quantum, lhp,
                               cores, jitter)
            fn = self._jits[key] = jax.jit(go)
        return fn

    def _run_batch(self, seeds, lowered, scheds, wl: Workload, T: int,
                   shard=None):
        """Elementwise batch: ``seeds[i]`` against ``lowered[i]`` under
        ``scheds[i]`` (host-lowered scheduler scalar tuples). When the
        resolved shard count doesn't divide the batch, the batch is
        padded with copies of its last point and the padding trimmed
        from the result — per-point simulations are independent, so
        padding never perturbs real points. Returns the states and the
        number of devices the runner's output lives on (0 on the plain
        vmap path)."""
        k = _resolve_shards(self.shard if shard is None else shard)
        n = len(lowered)
        seeds, lowered, scheds = list(seeds), list(lowered), list(scheds)
        pad = (-n) % k if k else 0
        if pad:
            seeds += [seeds[-1]] * pad
            lowered += [lowered[-1]] * pad
            scheds += [scheds[-1]] * pad
        seeds = jnp.asarray(seeds, jnp.int32)
        stacked = tuple(jnp.asarray(np.stack([lo[i] for lo in lowered]))
                        for i in range(6))
        sstack = tuple(jnp.asarray(np.stack([sc[i] for sc in scheds]))
                       for i in range(4))
        out = self._runner(T, wl, n + pad, k)(seeds, *stacked, *sstack)
        used = (len(jax.tree_util.tree_leaves(out)[0].sharding.device_set)
                if k else 0)
        if pad:
            out = jax.tree_util.tree_map(lambda a: a[:n], out)
        return out, used

    # -- execution -----------------------------------------------------------
    def states(self, seeds, *, topology=None, workload=None,
               scheduler=None, n_threads: int | None = None, shard=None):
        """Raw replica-stacked ``MachineState`` for a seed ensemble on
        one machine (feed to ``summarize_ensemble`` or inspect)."""
        T = n_threads or self.n_threads
        wl = (resolve_workload(workload) if workload is not None
              else self.workload)
        cm = topology if topology is not None else self.topology
        sc = (schedmod.resolve(scheduler) if scheduler is not None
              else self.scheduler)
        seeds = [int(s) for s in seeds]
        low = _lower_host(cm, T)
        slo = _lower_sched_host(sc, T)
        return self._run_batch(seeds, [low] * len(seeds),
                               [slo] * len(seeds), wl, T, shard=shard)[0]

    def run(self, seed: int = 0, **kw) -> BenchResult:
        """One replica, summarized."""
        return self.ensemble([seed], **kw)

    def ensemble(self, seeds, *, topology=None, workload=None,
                 scheduler=None, n_threads: int | None = None) -> BenchResult:
        """Seed ensemble on one machine, aggregated to the paper's
        metrics (one jit per shape, shared with ``grid``)."""
        T = n_threads or self.n_threads
        s = self.states(seeds, topology=topology, workload=workload,
                        scheduler=scheduler, n_threads=T)
        return summarize_ensemble(self.name, T, s)

    def grid(self, *, seeds=(0,), topologies=None, workloads=None,
             schedulers=None, threads=None, shard=None) -> GridResult:
        """Cross product of the seed x topology x scheduler x workload x
        threads axes. Seeds, topologies and schedulers batch into one jit
        per (threads, workload) shape — topologies are stacked
        ``LoweredCost`` data and schedulers stacked ``LoweredSched``
        data, so an SMP box and a 4-node NUMA box under dedicated and
        4x-oversubscribed OS models all share a compile. ``shard``
        overrides the session's batch execution path for this call
        (``False`` = plain vmap, ``True`` = force shard_map, ``"auto"``
        = shard when >1 device; results are bit-identical either way)."""
        seeds = [int(s) for s in seeds]
        topos = [(cost_label(c), _resolve_cost(c))
                 for c in (topologies if topologies is not None
                           else [self.topology])]
        schs = [(sched_label(s), schedmod.resolve(s))
                for s in (schedulers if schedulers is not None
                          else [self.scheduler])]
        wls = [resolve_workload(w) if w is not None else self.workload
               for w in (workloads if workloads is not None
                         else [self.workload])]
        ts = list(threads) if threads is not None else [self.n_threads]
        c0, S = self.compiles, len(seeds)
        cells, shards = [], 0
        for T in ts:
            lows = [(lab, _lower_host(c, T)) for lab, c in topos]
            slos = [(slab, _lower_sched_host(s, T)) for slab, s in schs]
            pairs = [(lab, lo, slab, sl)
                     for lab, lo in lows for slab, sl in slos]
            batch = [lo for _, lo, _, _ in pairs for _ in range(S)]
            sbatch = [sl for _, _, _, sl in pairs for _ in range(S)]
            tiled = [s for _ in pairs for s in seeds]
            for wl in wls:
                st, shards = self._run_batch(tiled, batch, sbatch, wl, T,
                                             shard=shard)
                for p, (lab, _, slab, _) in enumerate(pairs):
                    sl = jax.tree_util.tree_map(
                        lambda a, p=p: a[p * S:(p + 1) * S], st)
                    cells.append(GridCell(
                        lock=self.name, n_threads=T, topology=lab,
                        workload=wl.name, scheduler=slab,
                        result=summarize_ensemble(self.name, T, sl)))
        return GridResult(tuple(cells), self.compiles - c0, shards)


# --- process-wide sessions ---------------------------------------------------

_SESSIONS: dict = {}


def session(lock: str) -> SimEngine:
    """Shared per-lock session (registry names only): suites, the CLI
    and tests reuse one compile cache per lock instead of re-jitting
    per call."""
    eng = _SESSIONS.get(lock)
    if eng is None:
        eng = _SESSIONS[lock] = SimEngine(lock)
    return eng
