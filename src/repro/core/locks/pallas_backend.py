"""Backend #2: lower a ``LockIR`` to a Pallas kernel — the *measured* tier.

Where the sim backend (``ir.to_sim_program`` + ``core/sim/machine.py``)
*models* time — every micro-op is priced by a ``CostModel`` and the bus
serializes line transfers — this backend *spends* it: the same IR
handler table runs as a ``pl.pallas_call`` kernel in which each thread
is a grid program hammering the lock words through the device atomics
layer (``core/runtime/atomics.py``), and throughput is wall-clock
episodes per second.

Execution model
---------------
The kernel runs on a ``grid = (rounds, T)``: grid iteration is
row-major, so one *round* gives every thread one micro-op slice in
thread order — a deterministic round-robin schedule at op granularity
(the schedule the backend-agreement differential in
``tests/test_ir_backends.py`` replays through the sim machine with a
uniform cost model). A slice is exactly one turn of the machine's
op/handler crank:

1. execute the thread's pending op against shared memory via the
   ``atomics.rmw`` (one generic read-modify-write per the
   ``ir.OP_TABLE`` contract),
2. unsatisfied waits (SPIN/PARK) retry next round — no transition;
   timed parks burn a probe budget and complete with ``ok == 0``,
3. otherwise dispatch the per-pc handler (a tree of ``lax.cond`` over
   the IR's handler closures — the same closures the sim runs) and commit the
   transition: registers, next pc, next op, rng.

Lock state, per-thread machine state, and the metrics (episodes,
admission ring, arrive/admit latency in slices, the mutual-exclusion
guard/collision counter) live in SMEM output refs that stay resident
for the whole grid: the first slice copies the seeded inputs into them,
every later slice updates them in place, and they are written back once
at the end. The kernel is a single device launch.

Grid order
----------
Both grid axes are declared ``"arbitrary"``. A v5e chip has one
TensorCore, which runs such a grid's programs one at a time in
row-major order, so the plain read-modify-writes of
``core/runtime/atomics.py`` are linearizable on the device exactly as
they are in the Pallas interpreter. The registers live in scalars (the
DSL register file is a tuple here), so the kernel emits no ``scatter``.

Modes
-----
:func:`run_measured` compiles the kernel for the TPU it runs on and
raises on any other backend. ``interpret=True`` runs the identical
kernel through the Pallas interpreter; only callers that ask for it
(the CPU tests, ``repro.bench run --interpret``) get it.
:func:`build_measured` returns the jitted call without running it, for
ahead-of-time compiles and for :func:`backends`, which probes what this
process can run (the ``repro.bench list --backends`` catalogue).
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from functools import partial

import numpy as np

from repro.core.locks.ir import LockIR, lower_spec
from repro.core.runtime.atomics import rmw
from repro.core.sim import machine as M

__all__ = ["MeasuredResult", "run_measured", "build_measured",
           "initial_buffers", "backends", "resolve_ir", "ADM_LOG_M"]

#: admission-ring capacity (slot ADM_LOG_M is the overflow spill slot)
ADM_LOG_M = 256


@dataclass
class MeasuredResult:
    """One measured run: paper metrics in wall-clock/slice units."""
    name: str
    n_threads: int
    rounds: int
    backend: str                 # "pallas-interpret" | "pallas-device"
    platform: str                # jax.devices()[0].platform
    device_kind: str             # jax.devices()[0].device_kind
    device_count: int            # len(jax.devices())
    episodes: int                # total CS admissions
    per_thread: np.ndarray       # (T,) episodes per thread
    collisions: int              # ME violations observed (must be 0)
    admissions: np.ndarray       # (ADM_LOG_M,) ring of admitted tids
    admission_counts: int        # total admissions (ring position)
    returns: int                 # NCS returns (returns - episodes = aborts)
    wall_s: float                # host clock around the warm launch
    compile_s: float             # trace + compile + first launch

    @property
    def slices(self) -> int:
        return self.rounds * self.n_threads

    @property
    def throughput_eps(self) -> float:
        """Episodes per wall-second — the measured analogue of the sim's
        episodes-per-kilocycle."""
        return self.episodes / max(self.wall_s, 1e-9)

    @property
    def episodes_per_kslice(self) -> float:
        """Wall-free progress rate: episodes per 1000 op slices (the
        schedule-normalized number the calibration layer fits)."""
        return self.episodes * 1e3 / max(self.slices, 1)

    @property
    def latency_slices(self) -> float:
        return self._lat_sum / max(self.episodes, 1)

    _lat_sum: int = 0
    aborts: int = 0


def resolve_ir(lock, n_threads: int, *, ncs_max: int = 0,
               cs_shared=True) -> LockIR:
    """Accept a registered lock name, a spec author function, or an
    already-lowered ``LockIR``."""
    if isinstance(lock, LockIR):
        return lock
    if isinstance(lock, str):
        from repro.core.locks.specs import SPECS
        return lower_spec(SPECS[lock], n_threads, ncs_max=ncs_max,
                          cs_shared=cs_shared, name=lock)
    return lower_spec(lock, n_threads, ncs_max=ncs_max, cs_shared=cs_shared)


# --- kernel -------------------------------------------------------------------

#: the state refs, in kernel-argument order (inputs, then the same again
#: as outputs); ``regs`` and ``cur_op`` are flattened row-major (T*R, T*4)
STATE = ("mem", "pc", "regs", "cur_op", "rng", "tmo", "episodes",
         "returns", "arrive_slice", "lat_sum", "held", "scalars", "adm_log")


def _copy_ref(src, dst) -> None:
    """Element-by-element SMEM copy (SMEM is scalar-addressed)."""
    import jax

    def body(i, c):
        dst[i] = src[i]
        return c
    jax.lax.fori_loop(0, src.shape[0], body, 0)


def _switch(idx, branches, *args):
    """``lax.switch`` as a balanced tree of two-way ``cond``s. Mosaic
    lowers an N-way switch to an if/else cascade N-1 deep, and its
    layout pass crashes on the deepest handler tables in the zoo; the
    tree is log2(N) deep."""
    import jax
    if len(branches) == 1:
        return branches[0](*args)
    mid = len(branches) // 2
    return jax.lax.cond(
        idx < mid,
        lambda *a: _switch(idx, branches[:mid], *a),
        lambda *a: _switch(idx - mid, branches[mid:], *a),
        *args)


def _build_kernel(ir: LockIR, n_threads: int):
    """The per-slice kernel body. The first slice seeds the output refs
    from the inputs; every slice then updates the outputs in place."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    T = n_threads
    R = ir.n_regs
    n = len(STATE)
    handlers = ir.handlers
    i32 = jnp.int32

    def kernel(*refs):
        r_idx = pl.program_id(0)
        t = pl.program_id(1).astype(i32)

        @pl.when((r_idx == 0) & (t == 0))
        def _seed():
            for src, dst in zip(refs[:n], refs[n:]):
                _copy_ref(src, dst)

        (mem, pc, regs, cur_op, rng, tmo, episodes, returns,
         arrive_slice, lat_sum, held, scalars, adm_log) = refs[n:]
        slice_idx = r_idx.astype(i32) * T + t

        kind, addr = cur_op[t * 4], cur_op[t * 4 + 1]
        a, b = cur_op[t * 4 + 2], cur_op[t * 4 + 3]

        # -- op classes (ir.OP_TABLE as traced masks) -----------------------
        is_park_to = ((kind == M.PARK_EQ_TIMEOUT)
                      | (kind == M.PARK_NE_TIMEOUT))
        eq_wait = ((kind == M.SPIN_EQ) | (kind == M.PARK_EQ)
                   | (kind == M.PARK_EQ_TIMEOUT))
        ne_wait = (kind == M.SPIN_NE) | (kind == M.PARK_NE_TIMEOUT)

        # -- wait check + timed-park probe budget ---------------------------
        watched = mem[addr]
        unsat = (eq_wait & (watched != a)) | (ne_wait & (watched == a))
        budget = tmo[t]
        armed = budget >= 0
        timed_out = is_park_to & unsat & armed & (budget <= 0)
        spin_unsat = unsat & ~timed_out
        do_exec = ~spin_unsat
        # timeouts are probe-denominated on this backend: the op's
        # timeout operand counts unsatisfied rounds, not sim cycles
        tmo[t] = jnp.where(do_exec, i32(-1),
                           jnp.where(is_park_to,
                                     jnp.where(armed, budget - 1, b),
                                     budget))

        # -- memory effect: one RMW per the contract table ------------------
        # (waits/loads/delays write the old value back — a no-op by value)
        eff_kind = jnp.where(do_exec, kind, i32(M.NOP))
        old = rmw(mem, addr, eff_kind, a, b)

        # -- result encoding ------------------------------------------------
        cas_ok = (kind == M.CAS) & (old == a)
        res = jnp.where(kind == M.CAS, old * 2 + cas_ok.astype(i32),
                        jnp.where(is_park_to,
                                  old * 2 + jnp.where(timed_out, 0, 1),
                                  old))

        # -- DELAY burns real slices-worth of work --------------------------
        iters = jnp.where(do_exec & (kind == M.DELAY), a, 0)
        burn = jax.lax.fori_loop(0, iters, lambda i, x: x + i, i32(0))
        scalars[3] = scalars[3] + burn

        # -- transition: dispatch the IR handler at pc ----------------------
        pc_t = pc[t]
        regs_t = tuple(regs[t * R + i] for i in range(R))
        outs = _switch(pc_t, [partial(h, t) for h in handlers],
                       regs_t, res, rng[t])
        regs_new, next_pc, next_op, arrive, admit, rng_new = outs

        pc[t] = jnp.where(do_exec, next_pc, pc_t)
        rng[t] = jnp.where(do_exec, rng_new, rng[t])
        for i in range(R):
            regs[t * R + i] = jnp.where(do_exec, regs_new[i], regs_t[i])
        for i in range(4):
            cur_op[t * 4 + i] = jnp.where(do_exec, jnp.asarray(
                next_op[i], i32), cur_op[t * 4 + i])

        # -- metrics --------------------------------------------------------
        arrive_eff = do_exec & arrive
        admit_eff = do_exec & admit
        ret = do_exec & (next_pc == 0) & (pc_t != 0)

        arrive_slice[t] = jnp.where(arrive_eff, slice_idx, arrive_slice[t])
        lat_sum[t] = lat_sum[t] + jnp.where(
            admit_eff, slice_idx - arrive_slice[t], 0)
        episodes[t] = episodes[t] + admit_eff.astype(i32)
        returns[t] = returns[t] + ret.astype(i32)

        # admission ring with a spill slot at ADM_LOG_M: non-admissions
        # and overflow both land in the spill, real entries in 0..K-1
        cnt = scalars[0]
        pos = jnp.where(admit_eff, jnp.minimum(cnt, ADM_LOG_M),
                        i32(ADM_LOG_M))
        adm_log[pos] = jnp.where(admit_eff, t, adm_log[pos])
        scalars[0] = cnt + admit_eff.astype(i32)

        # mutual-exclusion guard: admitted while someone else holds the
        # admit..NCS-return window => collision (must never happen)
        g = scalars[1]
        scalars[2] = scalars[2] + jnp.where(admit_eff & (g != 0), 1, 0)
        dec = (ret & (held[t] != 0)).astype(i32)
        scalars[1] = g + admit_eff.astype(i32) - dec
        held[t] = jnp.where(admit_eff, i32(1),
                            jnp.where(ret, i32(0), held[t]))

    return kernel


def initial_buffers(ir: LockIR, n_threads: int, seed: int) -> tuple:
    """The seeded state, one host array per :data:`STATE` entry."""
    T, R = n_threads, ir.n_regs
    mem0 = np.zeros(max(ir.n_mem, 1), np.int32)
    for a, v in ir.init_mem:
        mem0[a] = v
    rng0 = (np.arange(T, dtype=np.uint32) * np.uint32(2654435761)
            + np.uint32(seed) * np.uint32(97) + np.uint32(1))
    nop = np.tile(np.array([M.NOP, 0, 0, 0], np.int32), T)
    return (
        mem0,                                         # mem
        np.zeros(T, np.int32),                        # pc
        np.zeros(T * R, np.int32),                    # regs
        nop,                                          # cur_op
        rng0,                                         # rng
        np.full(T, -1, np.int32),                     # tmo
        np.zeros(T, np.int32),                        # episodes
        np.zeros(T, np.int32),                        # returns
        np.zeros(T, np.int32),                        # arrive_slice
        np.zeros(T, np.int32),                        # lat_sum
        np.zeros(T, np.int32),                        # held
        np.zeros(4, np.int32),       # scalars: adm_cnt, guard, coll, burn
        np.full(ADM_LOG_M + 1, -1, np.int32),         # adm_log (+spill)
    )


def build_measured(ir: LockIR, n_threads: int, rounds: int, *,
                   interpret: bool = False):
    """The jitted ``pallas_call`` of ``ir`` on a ``(rounds, T)`` grid,
    built and not run: call it on :func:`initial_buffers`, or lower it
    on their shapes. Every state ref lives in SMEM, and both grid axes
    are ``"arbitrary"`` so the programs run one at a time, in order."""
    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    kernel = _build_kernel(ir, n_threads)
    shapes = [jax.ShapeDtypeStruct(x.shape, x.dtype)
              for x in initial_buffers(ir, n_threads, 0)]
    smem = [pl.BlockSpec(memory_space=pltpu.SMEM)] * len(shapes)
    return jax.jit(pl.pallas_call(
        kernel,
        grid=(rounds, n_threads),
        in_specs=smem,
        out_specs=smem,
        out_shape=shapes,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name=f"lock_{ir.name}",
    ))


def run_measured(lock, n_threads: int, rounds: int, *, ncs_max: int = 0,
                 cs_shared=True, seed: int = 0,
                 interpret: bool = False) -> MeasuredResult:
    """Run ``lock`` on the Pallas backend for ``rounds`` round-robin
    rounds of one micro-op per thread: compiled for the TPU this process
    holds, or through the Pallas interpreter when ``interpret=True``.
    Raises on a host without a TPU unless ``interpret=True``."""
    import jax

    dev = jax.devices()[0]
    if not interpret and dev.platform != "tpu":
        raise RuntimeError(
            f"run_measured compiles for a TPU, and this process sees "
            f"{dev.platform!r}; pass interpret=True to run the kernel in "
            "the Pallas interpreter")
    ir = resolve_ir(lock, n_threads, ncs_max=ncs_max, cs_shared=cs_shared)
    fn = build_measured(ir, n_threads, rounds, interpret=interpret)
    inits = [jax.device_put(x) for x in initial_buffers(ir, n_threads, seed)]
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*inits))          # trace + compile + warm
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    outs = jax.block_until_ready(fn(*inits))   # the timed launch
    wall = time.perf_counter() - t0

    (_mem, _pc, _regs, _op, _rng, _tmo, episodes, returns, _arr, lat_sum,
     _held, scalars, adm_log) = (np.asarray(o) for o in outs)
    eps = int(episodes.sum())
    rets = int(returns.sum())
    r = MeasuredResult(
        name=ir.name, n_threads=n_threads, rounds=rounds,
        backend="pallas-interpret" if interpret else "pallas-device",
        platform=dev.platform, device_kind=dev.device_kind,
        device_count=jax.device_count(),
        episodes=eps, per_thread=episodes, collisions=int(scalars[2]),
        admissions=adm_log[:ADM_LOG_M],
        admission_counts=int(scalars[0]), returns=rets,
        wall_s=wall, compile_s=compile_s)
    r._lat_sum = int(lat_sum.sum())
    r.aborts = max(rets - eps, 0)
    return r


# --- backend catalogue --------------------------------------------------------

def _probe_pallas(interpret: bool) -> tuple[bool, str]:
    """Can this process build and run the lock kernel in the given mode?
    A short ticket-lock run through :func:`run_measured` must admit
    episodes with no mutual-exclusion collision."""
    try:
        r = run_measured("ticket", 2, 16, interpret=interpret)
        ok = r.collisions == 0 and r.episodes > 0
        return ok, "ok" if ok else (f"probe mismatch: {r.episodes} "
                                    f"episodes, {r.collisions} collisions")
    except Exception as e:                      # noqa: BLE001
        return False, f"{type(e).__name__}: {e}"[:120]


def backends() -> list:
    """The backend catalogue with availability probing — what
    ``repro.bench list --backends`` prints. Rows:
    ``{"name", "available", "detail"}``."""
    import jax
    rows = [{
        "name": "sim",
        "available": True,
        "detail": "discrete-time coherence interpreter "
                  "(core/sim/machine.py handler tables under lax.scan)",
    }]
    ok, detail = _probe_pallas(interpret=True)
    rows.append({
        "name": "pallas-interpret",
        "available": ok,
        "detail": ("Pallas kernel in the interpreter (only on request: "
                   "interpret=True / --interpret)" if ok else detail),
    })
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        rows.append({
            "name": "pallas-device",
            "available": False,
            "detail": f"no TPU (jax platform: {dev.platform})",
        })
    else:
        ok, detail = _probe_pallas(interpret=False)
        rows.append({
            "name": "pallas-device",
            "available": ok,
            "detail": (f"compiled Pallas kernel on {dev.device_kind} "
                       "(SMEM state, grid in order)" if ok else detail),
        })
    return rows
