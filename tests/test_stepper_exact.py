"""The one-hot stepper against the gather/scatter stepper it replaced.

``machine_step`` reads and writes its state at the chosen thread and
word with one-hot selects, so that under the engine's vmap no gather or
scatter by per-point data remains in the scan body. Selects are exact,
so every ``MachineState`` leaf must equal what the earlier indexed
stepper (frozen below, as it was) computes, step for step, on the
benchmark's shapes: the four locks at T=64 on both 64-core cost trees,
vmapped over seeds, plus oversubscribed and lock-holder-preempting
schedulers and a lock with timed parks.
"""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.locks.programs import PROGRAMS
from repro.core.sim import sched as schedmod
from repro.core.sim import topology as topo
from repro.core.sim.machine import (
    ADM_LOG, CAS, DELAY, FAA, I32, INF, LOAD, PARK_EQ, PARK_EQ_TIMEOUT,
    PARK_NE_TIMEOUT, SPIN_EQ, SPIN_NE, STORE, XCHG, MachineState, Program,
    init_state, lower_cost, lower_sched, machine_step,
)

SEEDS = (3, 2**31 + 11)


def _gather_step(s: MachineState, prog: Program, cm, n_threads: int,
                 sched=None):
    """``machine_step`` as it was before its one-hot rewrite (less one
    unused local)."""
    T = n_threads
    lc = lower_cost(cm, T)
    ls = lower_sched(sched, T)

    # Runnable mask / dispatch key: a blocked thread is dispatchable only
    # at its abort deadline (INF for plain SPIN/PARK waits); a descheduled
    # thread's preemption gap is folded into ready_at, so "not runnable"
    # is simply "keyed in the future" — PC frozen, no coherence traffic.
    keyed = jnp.where(s.blocked, s.timeout_at, s.ready_at)
    t = jnp.argmin(keyed).astype(I32)
    kind, addr, a, b = (s.cur_op[t, 0], s.cur_op[t, 1], s.cur_op[t, 2],
                        s.cur_op[t, 3])
    mval = s.mem[addr]
    start = jnp.maximum(s.time, keyed[t])

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
    timed_out = is_park_to & unsat & (s.timeout_at[t] <= start)
    spin_unsat = unsat & ~timed_out

    # --- cache/cost: distance-in-hierarchy lookup ---------------------------
    hit = (s.owner[addr] == t) | s.sharers[t, addr]
    home_arr = jnp.asarray(prog.home if prog.home else (-1,) * prog.n_mem,
                           I32)
    # home == -1 homes the word with thread 0 (lock/global words, node 0)
    eff_home = jnp.maximum(home_arr[addr], 0)
    remote = lc.remote[t, eff_home]
    miss = is_mem & ~hit
    cost = jnp.where(~is_mem, 0,
                     jnp.where(hit & ~is_store, lc.hit,
                               jnp.where(hit & is_store & (s.owner[addr] == t),
                                         lc.hit,
                                         lc.miss[t, eff_home])))
    # a store to a merely-Shared line is an upgrade: count as miss-ish
    upgrade = is_store & s.sharers[t, addr] & (s.owner[addr] != t)
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
    res = jnp.where(kind == CAS, mval, jnp.where(is_load, mval, mval))
    res = jnp.where(kind == XCHG, mval, res)
    res = jnp.where(kind == FAA, mval, res)
    cas_flag = jnp.where(cas_ok, 1, 0)

    do_exec = ~spin_unsat
    eff = do_exec & is_mem

    mem = s.mem.at[addr].set(jnp.where(do_exec & writes, newval, s.mem[addr]))

    # coherence updates
    sh_col = s.sharers[:, addr]
    others_sharing = sh_col & (jnp.arange(T) != t)
    inval_recv = s.inval_recv + jnp.where(
        (do_exec & takes_line),
        others_sharing.astype(I32) +
        (jnp.arange(T) == s.owner[addr]) * (s.owner[addr] != t), 0)

    # store: invalidate everyone else, become owner
    # load miss: downgrade owner to shared, join sharers
    new_sh_col = jnp.where(do_exec & takes_line,
                           jnp.arange(T) == t,
                           jnp.where(eff & is_load,
                                     sh_col | (jnp.arange(T) == t) |
                                     (jnp.arange(T) == s.owner[addr]),
                                     sh_col))
    sharers = s.sharers.at[:, addr].set(new_sh_col)
    owner = s.owner.at[addr].set(
        jnp.where(do_exec & takes_line, t,
                  jnp.where(eff & is_load & ~hit, -1, s.owner[addr])))
    last_writer = s.last_writer.at[addr].set(
        jnp.where(do_exec & writes, t, s.last_writer[addr]))

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
    ready_at = s.ready_at.at[t].set(finish)
    misses_ct = s.misses.at[t].add(
        jnp.where((eff | spin_unsat) & miss, 1, 0))
    remote_ct = s.remote.at[t].add(
        jnp.where((eff | spin_unsat) & miss & remote, 1, 0))
    # spin's failed probe still cached the line Shared
    sharers = sharers.at[t, addr].set(
        jnp.where(spin_unsat, True, sharers[t, addr]))

    # abortable waiting: arm the deadline on the *first* block of a timed
    # park (spurious wakes keep the original deadline); any completion —
    # satisfied or timed out — disarms it
    timeout_at = s.timeout_at.at[t].set(
        jnp.where(do_exec, INF,
                  jnp.where(spin_unsat & is_park_to
                            & (s.timeout_at[t] == INF),
                            finish + b, s.timeout_at[t])))

    # --- wake threads blocked on this word ----------------------------------
    woke = (do_exec & writes) & s.blocked & (s.cur_op[:, 1] == addr)
    parked = ((s.cur_op[:, 0] == PARK_EQ)
              | (s.cur_op[:, 0] == PARK_EQ_TIMEOUT)
              | (s.cur_op[:, 0] == PARK_NE_TIMEOUT))
    blocked = jnp.where(woke, False, s.blocked)
    # the unpark is a syscall the *waker* executes: its cycles accrue to
    # t's own timeline (one fee per parked sleeper this store wakes)
    ready_at = ready_at.at[t].add(lc.unpark * (woke & parked).sum())
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
    blocked = blocked.at[t].set(spin_unsat)

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
                                 mval * 2 + jnp.where(timed_out, 0, 1), res))
    regs_t, next_pc, next_op, arrive, admit, rng_t = run_handler(
        (s.pc[t], s.regs[t], res_in, s.rng[t]))

    adv = do_exec
    pc = s.pc.at[t].set(jnp.where(adv, next_pc, s.pc[t]))
    regs = s.regs.at[t].set(jnp.where(adv, regs_t, s.regs[t]))
    cur_op = s.cur_op.at[t].set(
        jnp.where(adv, jnp.stack(next_op), s.cur_op[t]))
    rng = s.rng.at[t].set(jnp.where(adv, rng_t, s.rng[t]))

    arrive = adv & arrive
    admit = adv & admit
    arrive_time = s.arrive_time.at[t].set(
        jnp.where(arrive, finish, s.arrive_time[t]))
    lat_sum = s.lat_sum.at[t].add(
        jnp.where(admit, finish - s.arrive_time[t], 0))
    episodes = s.episodes.at[t].add(jnp.where(admit, 1, 0))
    adm_log = s.adm_log.at[s.adm_cnt % ADM_LOG].set(
        jnp.where(admit, t, s.adm_log[s.adm_cnt % ADM_LOG]))
    adm_cnt = s.adm_cnt + jnp.where(admit, 1, 0)

    # every return to the NCS top (admitted or abort path) — so
    # returns - episodes counts aborted acquisitions
    ret = adv & (next_pc == 0) & (s.pc[t] != 0)
    returns = s.returns.at[t].add(jnp.where(ret, 1, 0))
    # lock-held window: admission .. NCS return (CS plus release path),
    # the span the lhp_quantum bias tightens
    holding = jnp.where(admit, True, jnp.where(ret, False, s.in_cs[t]))
    in_cs = s.in_cs.at[t].set(holding)

    # --- hostile-OS scheduler: deschedule after the op if over budget -------
    # on-core cycles this dispatch (incl. private park/delay time)
    burn = finish - start
    slice_new = slice_used[t] + burn
    q_eff = jnp.minimum(jnp.where(holding, ls.lhp_quantum, ls.quantum),
                        ls.quantum)
    jit_off = jnp.where(
        ls.jitter > 0,
        (s.sched_rng[t] % (jnp.maximum(ls.jitter, 1).astype(jnp.uint32)
                           + jnp.uint32(1))).astype(I32), 0)
    budget = q_eff - jit_off
    preempt = adv & (slice_new >= budget)
    # a preempted thread waits out the other runnables' *base* quanta on
    # its core (their slices are not lhp-tightened), then pays the
    # re-dispatch; the gap collapses to 0 on a dedicated machine
    # (cores == T), and preempt never fires there (budget == INF)
    gap = ((ls.quantum - jit_off) * (jnp.asarray(T, I32) - ls.cores)
           // jnp.maximum(ls.cores, 1))
    ready_at = ready_at.at[t].add(
        jnp.where(preempt, gap + lc.resched, 0))
    # the slice empties on a deschedule or an off-core park; a spin-block
    # keeps accruing (the busy-wait never yields the core voluntarily)
    slice_used = slice_used.at[t].set(
        jnp.where(preempt | (spin_unsat & is_park), 0, slice_new))
    sr = s.sched_rng[t]
    sr = sr ^ (sr << jnp.uint32(13))
    sr = sr ^ (sr >> jnp.uint32(17))
    sr = sr ^ (sr << jnp.uint32(5))
    sched_rng = s.sched_rng.at[t].set(
        jnp.where(preempt, sr, s.sched_rng[t]))
    preempts = s.preempts.at[t].add(jnp.where(preempt, 1, 0))

    return MachineState(mem, owner, sharers, last_writer, pc, regs, cur_op,
                        blocked, ready_at, time, rng, episodes, misses_ct,
                        remote_ct, inval_recv, arrive_time, lat_sum,
                        adm_log, adm_cnt, timeout_at, in_cs, slice_used,
                        sched_rng, preempts, returns)


def _final_states(step, prog, T, cm, sched, steps):
    lc, ls = lower_cost(cm, T), lower_sched(sched, T)

    def one(seed):
        s, _ = jax.lax.scan(lambda s, _: (step(s, prog, lc, T, ls), None),
                            init_state(prog, T, seed), None, length=steps)
        return s
    return jax.jit(jax.vmap(one))(jnp.asarray(SEEDS, jnp.uint32))


#: (lock, cost tree, scheduler, threads, NCS bound, steps): the
#: benchmark's points, then preemption, timed parks and a run long enough
#: to wrap the admission log
CASES = [(lock, tree, "dedicated", 64, 250, 4_000)
         for lock in ("reciprocating", "mcs", "clh", "ticket")
         for tree in ("epyc-2s", "xeon-4s")]
CASES += [("reciprocating", "epyc-2s", "fair-2x", 64, 250, 4_000),
          ("mcs_timeout", "xeon-4s", "lhp:150x50x2", 16, 40, 4_000),
          ("spin_then_park", "epyc-2s", "fair:400x4", 16, 40, 4_000),
          ("reciprocating", "epyc-2s", "dedicated", 2, 0, 6_000)]


@pytest.mark.parametrize("lock,tree,sched,T,ncs,steps", CASES)
def test_one_hot_stepper_matches_gather_stepper(lock, tree, sched, T, ncs,
                                                steps):
    prog = PROGRAMS[lock](T, ncs_max=ncs, cs_shared=True)
    cm, sc = topo.resolve(tree), schedmod.resolve(sched)
    new = _final_states(machine_step, prog, T, cm, sc, steps)
    old = _final_states(_gather_step, prog, T, cm, sc, steps)
    assert int(new.episodes.sum()) > 0
    for f in MachineState._fields:
        x, y = getattr(new, f), getattr(old, f)
        assert x.dtype == y.dtype, f
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=f)
