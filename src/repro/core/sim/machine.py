"""Vectorized cache-coherent shared-memory machine in JAX.

This is the performance substrate on which the lock algorithms are
evaluated (paper Figures 1-3, Table 1): a sequentially-consistent machine
with a MESI-lite per-word coherence model and a serialized coherence bus.

Model (DESIGN.md §L1):
* ``mem[W]``      — one word per cache line (the paper sequesters every
                    field at 128B, so word == line is faithful).
* ``owner[W]``    — thread holding the line Modified (-1: none).
* ``sharers[T,W]``— Shared copies.
* Loads:  hit (owner==t or sharer) costs 1 cycle and no bus traffic;
          a miss pays the distance-in-hierarchy transfer cost between
          the requester and the line's *home* thread — a traced
          ``LoweredCost.miss[t, home]`` lookup, lowered from either the
          flat ``CostModel`` or a ``topology.Topology`` tree — and
          downgrades a remote Modified copy to Shared.
* Stores/atomics: hit-in-M costs 1; otherwise a miss that *invalidates*
  all other copies (counted per victim — the paper's l2d_cache_inval).
* The bus serializes misses (global_time advances only on line transfers);
  cache hits and local DELAYs only advance the thread's own clock. This is
  what makes global spinning (Ticket) collapse at high T while local
  spinning (MCS/CLH/Reciprocating) hands off in O(1) bus transactions.
* SPIN ops block the thread (zero cost) until the watched word is written
  — a woken waiter then pays the coherence miss for its re-read, exactly
  the "local spinning" accounting of the paper.

Op/result-encoding contract (the single source of truth — the lock DSL
(``core/locks/dsl.py``) and the lock specs reference this table instead of
restating it):

  kind     operands              result ``res`` fed to the next handler
  -------  --------------------  ---------------------------------------
  NOP      (addr ignored, use 0) mem[addr]
  LOAD     addr                  mem[addr]
  STORE    addr, a=value         old mem[addr] (by convention ignored)
  XCHG     addr, a=value         old mem[addr]
  CAS      addr, a=expect,       ``old * 2 + ok`` — the old value and the
           b=new                 success bit packed in one word (all lock
                                 words are small and non-negative)
  FAA      addr, a=delta         old mem[addr]
  SPIN_EQ  addr, a               block (zero cost) until mem[addr] == a;
                                 res = the watched value once satisfied
  SPIN_NE  addr, a               block until mem[addr] != a; res likewise
  PARK_EQ  addr, a               SPIN_EQ semantics, plus the park cost
                                 model: ``CostModel.park_cost`` is charged
                                 when the thread blocks (the kernel-entry
                                 syscall) and ``CostModel.unpark_cost``
                                 when a writer wakes it — the unpark is a
                                 syscall *the waker executes*, so its
                                 cycles accrue to the waker's own
                                 timeline; the sleeper becomes runnable
                                 at the waking store's finish time
  PARK_EQ_TIMEOUT / PARK_NE_TIMEOUT
           addr, a, b=timeout    abortable waiting (the lock DSL's
                                 ``abort`` phase): PARK_EQ / PARK-NE
                                 blocking, but the wait gives up after
                                 ``b`` private cycles. Result packs like
                                 CAS: ``watched * 2 + ok`` — ok == 1 when
                                 the condition was met, 0 when the wait
                                 timed out (the abort path runs next)
  DELAY    a=cycles              advance only the issuing thread's clock;
                                 res = mem[addr] (use addr 0)

Scheduler (hostile OS) model — ``machine_step`` also consumes a
:class:`LoweredSched` (from ``core/sim/sched.py``), pure traced data like
``LoweredCost``: the earliest-ready selection key *is* the runnable mask.
A thread whose on-core slice exceeds the (seeded-jittered) quantum is
descheduled after its current op: its ``ready_at`` jumps by the
oversubscription gap plus ``CostModel.resched_cost`` (the re-dispatch
charge), which freezes its PC and stops its coherence traffic until
re-dispatch; a woken parker additionally pays one re-dispatch when cores
are oversubscribed. The degenerate scheduler (infinite quantum,
cores >= threads) makes every term collapse to the schedulerless
arithmetic — bit-identical states, pinned by tests/test_hostile.py.

Value/address conventions shared by every program: LOCKEDEMPTY == 1 marks
a detached-but-empty arrival word (so real element addresses must be > 1);
word 4 is the shared CS word, word 5 the second (read-only-profile) CS
word, words 0..3 are lock words, and per-thread wait elements live at
addresses >= 8.

Lock algorithms are table-driven state machines (``jax.lax.switch`` over a
per-algorithm handler list) authored as declarative ``LockSpec`` phase
specs (``core/locks/dsl.py``) and lowered by ``core/locks/compile.py`` to
the ``Program`` handler-table form below; the engine is a single
``jax.lax.scan`` over micro-steps, ``jax.vmap``-able over replica
ensembles and jit-compiled end to end.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

I32 = jnp.int32
F32 = jnp.float32
INF = jnp.array(2**31 - 1, jnp.int32)

# op kinds (semantics: the contract table in the module docstring)
NOP, LOAD, STORE, XCHG, CAS, FAA, SPIN_EQ, SPIN_NE, DELAY, PARK_EQ = \
    range(10)
PARK_EQ_TIMEOUT, PARK_NE_TIMEOUT = 10, 11


class Op(NamedTuple):
    kind: jnp.ndarray
    addr: jnp.ndarray
    a: jnp.ndarray
    b: jnp.ndarray


def op(kind, addr=0, a=0, b=0):
    return (jnp.asarray(kind, I32), jnp.asarray(addr, I32),
            jnp.asarray(a, I32), jnp.asarray(b, I32))


@dataclass(frozen=True)
class CostModel:
    """Flat two-tier cost model (one local/remote pair, contiguous
    thread->node split). Still accepted everywhere; richer machines are
    described by ``core.sim.topology.Topology``. Both lower to the same
    :class:`LoweredCost` thread x thread matrix the engine consumes."""
    hit: int = 1
    local_miss: int = 40
    remote_miss: int = 100
    n_nodes: int = 1          # NUMA nodes (threads split contiguously)
    # PARK_EQ hooks (spin-then-park locks): cycles charged on the blocking
    # park itself (kernel entry) and on the wake handoff (context switch).
    # Neither advances the coherence bus — parking is private time. The
    # park is paid by the sleeper; the unpark syscall by the *waker*.
    park_cost: int = 25
    unpark_cost: int = 75
    # Re-dispatch charge after a scheduler deschedule (or an oversubscribed
    # wake): the context-switch-in cost. Private time, like parking.
    resched_cost: int = 150


class LoweredCost(NamedTuple):
    """The one cost interface ``machine_step`` consumes: a *traced*
    thread x thread transfer-cost lookup. ``miss[t, h]`` is the cycles a
    coherence miss pays when thread ``t`` pulls a line homed with thread
    ``h`` (the distance-in-hierarchy lookup); ``remote[t, h]`` marks
    NUMA-remote transfers (the ``remote_per_episode`` metric). Every
    field is data, not shape — a grid of machines is a stacked batch of
    these, vmapped through one XLA program (``core.sim.engine``)."""
    hit: jnp.ndarray          # () i32
    miss: jnp.ndarray         # (T, T) i32  requester x home-thread
    remote: jnp.ndarray       # (T, T) bool
    park: jnp.ndarray         # () i32
    unpark: jnp.ndarray       # () i32
    resched: jnp.ndarray      # () i32


class LoweredSched(NamedTuple):
    """The lowered hostile-OS scheduler ``machine_step`` consumes —
    scalar traced data (like ``LoweredCost``, a grid of schedulers is a
    stacked batch sharing one jit; ``core/sim/sched.py`` builds these).
    ``quantum`` is the on-core timeslice in cycles (INF: never preempt),
    ``lhp_quantum`` the tighter slice applied while the thread holds the
    lock (lock-holder-preemption bias; INF: same as ``quantum``),
    ``cores`` the physical core count (cores < T: oversubscribed — a
    preempted thread waits out the other threads' quanta on its core),
    and ``jitter`` the seeded per-slice budget jitter span in cycles
    (deterministic preemption points from the per-thread xorshift)."""
    quantum: jnp.ndarray      # () i32
    lhp_quantum: jnp.ndarray  # () i32
    cores: jnp.ndarray        # () i32
    jitter: jnp.ndarray      # () i32


def lower_sched(sched, n_threads: int) -> LoweredSched:
    """Lower any scheduler description — ``None`` (the degenerate
    always-running OS), a ``sched.Scheduler`` (via its ``.lower``), or an
    already-lowered :class:`LoweredSched` — to the scalar form."""
    if sched is None:
        return LoweredSched(quantum=INF, lhp_quantum=INF,
                            cores=jnp.asarray(n_threads, I32),
                            jitter=jnp.zeros((), I32))
    if isinstance(sched, LoweredSched):
        return sched
    return sched.lower(n_threads)


def lower_cost(cm, n_threads: int) -> LoweredCost:
    """Lower any cost description — a flat :class:`CostModel`, a
    ``topology.Topology`` (via its ``.lower``), or an already-lowered
    :class:`LoweredCost` — to the matrix form. The flat lowering uses the
    historical contiguous-split node arithmetic, so it is bit-identical
    to the pre-topology branch; it stays pure data-flow, so a traced
    ``n_nodes`` still shares one compile across NUMA variants."""
    if isinstance(cm, LoweredCost):
        return cm
    lower = getattr(cm, "lower", None)
    if lower is not None:                 # Topology (duck-typed: no import
        return lower(n_threads)           # cycle with core.sim.topology)
    t = jnp.arange(n_threads)
    node = _node(t, n_threads, cm.n_nodes)
    remote = (node[:, None] != node[None, :]) & (cm.n_nodes > 1)
    return LoweredCost(
        hit=jnp.asarray(cm.hit, I32),
        miss=jnp.where(remote, cm.remote_miss, cm.local_miss).astype(I32),
        remote=remote,
        park=jnp.asarray(cm.park_cost, I32),
        unpark=jnp.asarray(cm.unpark_cost, I32),
        resched=jnp.asarray(cm.resched_cost, I32))


@dataclass(frozen=True)
class Program:
    """A lock+workload program: handlers[pc](t, regs, res, rng) ->
    (regs, next_pc, op4, arrive, admit, rng).

    ``home`` maps each word to the thread on whose NUMA node the line is
    homed (-1: lock/global words, homed on node 0). The paper's "Maximum
    Remote Misses" analysis assumes home-based snooping (UPI), so remote-ness
    is decided by the line's home, not its last writer."""
    handlers: tuple
    n_mem: int
    home: tuple = ()          # per-word home thread (-1 => node 0)
    name: str = "prog"
    n_regs: int = 8
    init_mem: tuple = ()      # ((addr, value), ...) initial memory words


class MachineState(NamedTuple):
    mem: jnp.ndarray          # (W,) i32
    owner: jnp.ndarray        # (W,) i32
    sharers: jnp.ndarray      # (T, W) bool
    last_writer: jnp.ndarray  # (W,) i32
    pc: jnp.ndarray           # (T,) i32
    regs: jnp.ndarray         # (T, R) i32
    cur_op: jnp.ndarray       # (T, 4) i32
    blocked: jnp.ndarray      # (T,) bool
    ready_at: jnp.ndarray     # (T,) i32
    time: jnp.ndarray         # () i32 bus clock
    rng: jnp.ndarray          # (T,) u32 xorshift state
    # metrics
    episodes: jnp.ndarray     # (T,) i32
    misses: jnp.ndarray       # (T,) i32
    remote: jnp.ndarray       # (T,) i32
    inval_recv: jnp.ndarray   # (T,) i32
    arrive_time: jnp.ndarray  # (T,) i32
    lat_sum: jnp.ndarray      # (T,) i32
    adm_log: jnp.ndarray      # (K,) i32
    adm_cnt: jnp.ndarray      # () i32
    # abortable waiting (PARK_*_TIMEOUT): per-thread wake deadline
    # (INF: no timed wait pending)
    timeout_at: jnp.ndarray   # (T,) i32
    # hostile-OS scheduler state/metrics (degenerate scheduler: inert)
    in_cs: jnp.ndarray        # (T,) bool  lock held (admit .. NCS return)
    slice_used: jnp.ndarray   # (T,) i32   on-core cycles this timeslice
    sched_rng: jnp.ndarray    # (T,) u32   preemption-point xorshift
    preempts: jnp.ndarray     # (T,) i32   involuntary deschedules
    returns: jnp.ndarray      # (T,) i32   episodes ended (NCS returns);
                              #            returns - episodes = aborts


ADM_LOG = 512


def init_state(prog: Program, n_threads: int, seed: int = 0) -> MachineState:
    T, W, R = n_threads, prog.n_mem, prog.n_regs
    mem0 = jnp.zeros(W, I32)
    for a, v in prog.init_mem:
        mem0 = mem0.at[a].set(v)
    return MachineState(
        mem=mem0,
        owner=jnp.full(W, -1, I32),
        sharers=jnp.zeros((T, W), bool),
        last_writer=jnp.full(W, -1, I32),
        pc=jnp.zeros(T, I32),
        regs=jnp.zeros((T, R), I32),
        cur_op=jnp.broadcast_to(jnp.array([NOP, 0, 0, 0], I32), (T, 4)),
        blocked=jnp.zeros(T, bool),
        ready_at=jnp.zeros(T, jnp.int32),
        time=jnp.zeros((), jnp.int32),
        rng=(jnp.arange(T, dtype=jnp.uint32) * jnp.uint32(2654435761)
             + jnp.uint32(seed) * jnp.uint32(97) + jnp.uint32(1)),
        episodes=jnp.zeros(T, I32),
        misses=jnp.zeros(T, I32),
        remote=jnp.zeros(T, I32),
        inval_recv=jnp.zeros(T, I32),
        arrive_time=jnp.zeros(T, jnp.int32),
        lat_sum=jnp.zeros(T, jnp.int32),
        adm_log=jnp.full(ADM_LOG, -1, I32),
        adm_cnt=jnp.zeros((), I32),
        timeout_at=jnp.full(T, INF, I32),
        in_cs=jnp.zeros(T, bool),
        slice_used=jnp.zeros(T, I32),
        # scheduler stream: distinct from the NCS rng so the hostile layer
        # never perturbs the workload's random delays
        sched_rng=((jnp.arange(T, dtype=jnp.uint32) + jnp.uint32(7))
                   * jnp.uint32(2246822519)
                   ^ (jnp.uint32(seed) * jnp.uint32(40503)
                      + jnp.uint32(11))),
        preempts=jnp.zeros(T, I32),
        returns=jnp.zeros(T, I32),
    )


def _node(t, T, n_nodes):
    return jnp.where(n_nodes <= 1, 0, t // jnp.maximum(T // n_nodes, 1))


def _read(hot, x):
    """``x[i]`` along axis 0, where ``hot`` is the one-hot mask
    ``arange(len(x)) == i``: a masked reduction, not a gather. Under the
    engine's vmap ``i`` differs per point, and a gather (or scatter) by
    per-point data cannot fuse with its neighbours; a select and a sum
    can. Exact for every dtype; ``i`` must be in range."""
    m = hot.reshape(hot.shape + (1,) * (x.ndim - hot.ndim))
    if x.dtype == jnp.bool_:
        return (m & x).any(tuple(range(hot.ndim)))
    return jnp.where(m, x, 0).sum(tuple(range(hot.ndim)), dtype=x.dtype)


def _write(hot, x, v):
    """``x`` with ``v`` written where ``hot`` is set (``x.at[i].set(v)``
    for a one-hot ``hot``), broadcast over ``x``'s trailing axes: a
    select, not a scatter."""
    return jnp.where(hot.reshape(hot.shape + (1,) * (x.ndim - hot.ndim)),
                     jnp.asarray(v).astype(x.dtype), x)


def machine_step(s: MachineState, prog: Program, cm, n_threads: int,
                 sched=None):
    """Execute one micro-op for the earliest-ready unblocked thread.
    ``cm`` is any cost description ``lower_cost`` accepts (flat
    ``CostModel``, ``topology.Topology``, or a ``LoweredCost``);
    ``sched`` any scheduler description ``lower_sched`` accepts (``None``
    — the degenerate always-running OS — a ``sched.Scheduler``, or a
    ``LoweredSched``).

    The thread ``t`` and the word ``addr`` differ per point of a vmapped
    grid, so every read and write at them is a one-hot select
    (``_read``/``_write``): the step is elementwise work and small
    reductions that XLA fuses, and no state array is copied whole for a
    one-element update."""
    T = n_threads
    lc = lower_cost(cm, T)
    ls = lower_sched(sched, T)
    threads = jnp.arange(T)

    # Runnable mask / dispatch key: a blocked thread is dispatchable only
    # at its abort deadline (INF for plain SPIN/PARK waits); a descheduled
    # thread's preemption gap is folded into ready_at, so "not runnable"
    # is simply "keyed in the future" — PC frozen, no coherence traffic.
    keyed = jnp.where(s.blocked, s.timeout_at, s.ready_at)
    t = jnp.argmin(keyed).astype(I32)
    hot_t = threads == t
    kind, addr, a, b = _read(hot_t, s.cur_op)
    hot_a = jnp.arange(prog.n_mem) == addr
    mval = _read(hot_a, s.mem)
    own = _read(hot_a, s.owner)           # s.owner[addr]
    sh_col = (s.sharers & hot_a[None, :]).any(1)   # s.sharers[:, addr]
    sh_ta = _read(hot_t, sh_col)          # s.sharers[t, addr]
    start = jnp.maximum(s.time, keyed.min())
    timeout_t = _read(hot_t, s.timeout_at)

    is_park_to = (kind == PARK_EQ_TIMEOUT) | (kind == PARK_NE_TIMEOUT)
    is_park = (kind == PARK_EQ) | is_park_to
    is_load = (kind == LOAD) | (kind == SPIN_EQ) | (kind == SPIN_NE) | is_park
    is_store = (kind == STORE) | (kind == XCHG) | (kind == CAS) | (kind == FAA)
    is_mem = is_load | is_store

    # --- spin semantics: unsatisfied -> block (woken by a write); a timed
    # wait whose deadline has passed completes instead, with ok == 0 ------
    eq_wait = (kind == SPIN_EQ) | (kind == PARK_EQ) | \
              (kind == PARK_EQ_TIMEOUT)
    ne_wait = (kind == SPIN_NE) | (kind == PARK_NE_TIMEOUT)
    unsat = (eq_wait & (mval != a)) | (ne_wait & (mval == a))
    timed_out = is_park_to & unsat & (timeout_t <= start)
    spin_unsat = unsat & ~timed_out

    # --- cache/cost: distance-in-hierarchy lookup ---------------------------
    hit = (own == t) | sh_ta
    home_arr = jnp.asarray(prog.home if prog.home else (-1,) * prog.n_mem,
                           I32)
    # home == -1 homes the word with thread 0 (lock/global words, node 0)
    eff_home = jnp.maximum(_read(hot_a, home_arr), 0)
    hot_th = hot_t[:, None] & (threads == eff_home)[None, :]
    remote = _read(hot_th, lc.remote)     # lc.remote[t, eff_home]
    miss = is_mem & ~hit
    cost = jnp.where(~is_mem, 0,
                     jnp.where(hit & ~is_store, lc.hit,
                               jnp.where(hit & is_store & (own == t),
                                         lc.hit,
                                         _read(hot_th, lc.miss))))
    # a store to a merely-Shared line is an upgrade: count as miss-ish
    upgrade = is_store & sh_ta & (own != t)
    miss = miss | upgrade

    # --- memory effect ------------------------------------------------------
    cas_ok = (kind == CAS) & (mval == a)
    newval = jnp.where(kind == STORE, a,
             jnp.where(kind == XCHG, a,
             jnp.where(kind == FAA, mval + a,
             jnp.where(cas_ok, b, mval))))
    writes = is_store & ((kind != CAS) | cas_ok)
    # failed CAS still takes the line exclusive (x86 semantics)
    takes_line = is_store
    cas_flag = jnp.where(cas_ok, 1, 0)

    do_exec = ~spin_unsat
    eff = do_exec & is_mem

    mem = _write(hot_a & do_exec & writes, s.mem, newval)

    # coherence updates
    others_sharing = sh_col & ~hot_t
    inval_recv = s.inval_recv + jnp.where(
        (do_exec & takes_line),
        others_sharing.astype(I32) + (threads == own) * (own != t), 0)

    # store: invalidate everyone else, become owner
    # load miss: downgrade owner to shared, join sharers
    # spin's failed probe still caches the line Shared
    new_sh_col = jnp.where(do_exec & takes_line, hot_t,
                           jnp.where(eff & is_load,
                                     sh_col | hot_t | (threads == own),
                                     sh_col))
    new_sh_col = new_sh_col | (spin_unsat & hot_t)
    sharers = jnp.where(hot_a[None, :], new_sh_col[:, None], s.sharers)
    owner = _write(hot_a, s.owner,
                   jnp.where(do_exec & takes_line, t,
                             jnp.where(eff & is_load & ~hit, -1, own)))
    last_writer = _write(hot_a & do_exec & writes, s.last_writer, t)

    # --- timing -------------------------------------------------------------
    # spin first-check also pays its read cost before blocking
    op_cost = jnp.where(kind == DELAY, a.astype(jnp.int32),
                        cost.astype(jnp.int32))
    # a blocking PARK additionally pays the kernel-entry park cost;
    # it is private time, so only the probe's line transfer hits the bus
    bus_finish = start + op_cost
    finish = bus_finish + jnp.where(is_park & spin_unsat, lc.park, 0)
    # bus serializes only on misses (line transfers)
    time = jnp.where(eff & miss | (spin_unsat & ~hit), bus_finish, s.time)
    ready_at = _write(hot_t, s.ready_at, finish)
    misses_ct = s.misses + (hot_t & (eff | spin_unsat) & miss)
    remote_ct = s.remote + (hot_t & (eff | spin_unsat) & miss & remote)

    # abortable waiting: arm the deadline on the *first* block of a timed
    # park (spurious wakes keep the original deadline); any completion —
    # satisfied or timed out — disarms it
    timeout_at = _write(
        hot_t, s.timeout_at,
        jnp.where(do_exec, INF,
                  jnp.where(spin_unsat & is_park_to & (timeout_t == INF),
                            finish + b, timeout_t)))

    # --- wake threads blocked on this word ----------------------------------
    woke = (do_exec & writes) & s.blocked & (s.cur_op[:, 1] == addr)
    parked = ((s.cur_op[:, 0] == PARK_EQ)
              | (s.cur_op[:, 0] == PARK_EQ_TIMEOUT)
              | (s.cur_op[:, 0] == PARK_NE_TIMEOUT))
    blocked = jnp.where(woke, False, s.blocked)
    # the unpark is a syscall the *waker* executes: its cycles accrue to
    # t's own timeline (one fee per parked sleeper this store wakes)
    ready_at = ready_at + jnp.where(hot_t, lc.unpark * (woke & parked).sum(),
                                    0)
    # the sleeper becomes runnable at the waking store's finish; on an
    # oversubscribed machine a woken parker also waits out one re-dispatch
    redisp = jnp.where((ls.cores < T) & parked, lc.resched, 0)
    # a spin-waiter busy-waits *on-core*: its blocked wall-time counts
    # against its slice budget (a parked waiter sleeps off-core), so the
    # scheduler eventually deschedules long spinners — charged as a
    # deferred gap at the spinner's next dispatch
    spin_span = jnp.maximum(finish - s.ready_at, 0)
    slice_used = jnp.where(woke & ~parked, s.slice_used + spin_span,
                           s.slice_used)
    ready_at = jnp.where(woke, jnp.maximum(ready_at, finish) + redisp,
                         ready_at)
    blocked = _write(hot_t, blocked, spin_unsat)

    # --- transition (only when the op completed) -----------------------------
    def run_handler(pc_regs_res):
        pc_v, regs_v, res_v, rng_v = pc_regs_res
        outs = jax.lax.switch(
            pc_v, [partial(h, t) for h in prog.handlers], regs_v, res_v,
            rng_v)
        return outs   # (regs, next_pc, op4, arrive, admit, rng)

    # timed parks pack like CAS: watched * 2 + ok (ok == 0: wait aborted)
    res_in = jnp.where(kind == CAS, mval * 2 + cas_flag,
                       jnp.where(is_park_to,
                                 mval * 2 + jnp.where(timed_out, 0, 1), mval))
    pc_t = _read(hot_t, s.pc)
    regs_t, next_pc, next_op, arrive, admit, rng_t = run_handler(
        (pc_t, _read(hot_t, s.regs), res_in, _read(hot_t, s.rng)))

    adv = do_exec
    hot_adv = hot_t & adv
    pc = _write(hot_adv, s.pc, next_pc)
    regs = _write(hot_adv, s.regs, regs_t)
    cur_op = _write(hot_adv, s.cur_op, jnp.stack(next_op))
    rng = _write(hot_adv, s.rng, rng_t)

    arrive = adv & arrive
    admit = adv & admit
    arrive_time = _write(hot_t & arrive, s.arrive_time, finish)
    lat_sum = s.lat_sum + jnp.where(
        hot_t & admit, finish - _read(hot_t, s.arrive_time), 0)
    episodes = s.episodes + (hot_t & admit)
    adm_log = _write((jnp.arange(ADM_LOG) == s.adm_cnt % ADM_LOG) & admit,
                     s.adm_log, t)
    adm_cnt = s.adm_cnt + jnp.where(admit, 1, 0)

    # every return to the NCS top (admitted or abort path) — so
    # returns - episodes counts aborted acquisitions
    ret = adv & (next_pc == 0) & (pc_t != 0)
    returns = s.returns + (hot_t & ret)
    # lock-held window: admission .. NCS return (CS plus release path),
    # the span the lhp_quantum bias tightens
    holding = jnp.where(admit, True,
                        jnp.where(ret, False, _read(hot_t, s.in_cs)))
    in_cs = _write(hot_t, s.in_cs, holding)

    # --- hostile-OS scheduler: deschedule after the op if over budget -------
    # on-core cycles this dispatch (incl. private park/delay time)
    burn = finish - start
    slice_new = _read(hot_t, slice_used) + burn
    q_eff = jnp.minimum(jnp.where(holding, ls.lhp_quantum, ls.quantum),
                        ls.quantum)
    sr = _read(hot_t, s.sched_rng)
    jit_off = jnp.where(
        ls.jitter > 0,
        (sr % (jnp.maximum(ls.jitter, 1).astype(jnp.uint32)
               + jnp.uint32(1))).astype(I32), 0)
    budget = q_eff - jit_off
    preempt = adv & (slice_new >= budget)
    # a preempted thread waits out the other runnables' *base* quanta on
    # its core (their slices are not lhp-tightened), then pays the
    # re-dispatch; the gap collapses to 0 on a dedicated machine
    # (cores == T), and preempt never fires there (budget == INF)
    gap = ((ls.quantum - jit_off) * (jnp.asarray(T, I32) - ls.cores)
           // jnp.maximum(ls.cores, 1))
    ready_at = ready_at + jnp.where(hot_t & preempt, gap + lc.resched, 0)
    # the slice empties on a deschedule or an off-core park; a spin-block
    # keeps accruing (the busy-wait never yields the core voluntarily)
    slice_used = _write(
        hot_t, slice_used,
        jnp.where(preempt | (spin_unsat & is_park), 0, slice_new))
    sr = sr ^ (sr << jnp.uint32(13))
    sr = sr ^ (sr >> jnp.uint32(17))
    sr = sr ^ (sr << jnp.uint32(5))
    sched_rng = _write(hot_t & preempt, s.sched_rng, sr)
    preempts = s.preempts + (hot_t & preempt)

    return MachineState(mem, owner, sharers, last_writer, pc, regs, cur_op,
                        blocked, ready_at, time, rng, episodes, misses_ct,
                        remote_ct, inval_recv, arrive_time, lat_sum,
                        adm_log, adm_cnt, timeout_at, in_cs, slice_used,
                        sched_rng, preempts, returns)


def run_machine(prog: Program, n_threads: int, n_steps: int,
                cm=CostModel(), seed: int = 0,  # noqa: B008
                sched=None) -> MachineState:
    """One replica. ``cm``: flat ``CostModel``, ``topology.Topology``, or
    ``LoweredCost``; ``sched``: ``None``, ``sched.Scheduler``, or
    ``LoweredSched`` — both lowered once, outside the scan."""
    s0 = init_state(prog, n_threads, seed)
    lc = lower_cost(cm, n_threads)
    ls = lower_sched(sched, n_threads)

    def body(s, _):
        return machine_step(s, prog, lc, n_threads, ls), None

    s, _ = jax.lax.scan(body, s0, None, length=n_steps)
    return s


def run_ensemble(prog: Program, n_threads: int, n_steps: int,
                 cm=CostModel(), n_replicas: int = 8,  # noqa: B008
                 seed0: int = 0):
    """Deprecated: forward to ``core.sim.engine.SimEngine(...).states``,
    the one session API (same stacked-``MachineState`` return)."""
    import warnings

    from repro.core.sim.engine import SimEngine, Workload
    warnings.warn(
        "run_ensemble is deprecated; use repro.core.sim.engine."
        "SimEngine(prog, topology=..., workload=...).states(seeds)",
        DeprecationWarning, stacklevel=2)
    eng = SimEngine(prog, topology=cm, n_threads=n_threads,
                    workload=Workload(n_steps=n_steps))
    return eng.states(range(seed0, seed0 + n_replicas))
