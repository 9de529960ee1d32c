"""Plain reference of the lock laboratory's coherence machine, for the
``lock_sim`` configurations.

It is written from the machine's published contract (the op table and
cost model of the Reciprocating Locks simulator: one micro-op of the
earliest-ready thread per step, a serialized coherence bus, MESI-lite
lines with a home thread per word) and from the lock algorithms as the
paper lists them, in plain Python with no JAX and nothing of the program
under test. It covers what the configurations state: ``rw`` critical
sections, a random NCS delay of 0 to ``ncs_max - 1`` cycles drawn from
each thread's xorshift stream (which the point's seed starts), and a
dedicated scheduler (one core per thread, no preemption), so its
scheduler stream is never drawn.

``simulate`` runs one point and returns its final counters;
``summarize`` folds points into the per-cell numbers the timed path
reports; ``bypass_bound`` derives the admission-interleave bound from an
admission log.
"""
from __future__ import annotations

import numpy as np

INF = 2**31 - 1
M32 = 2**32 - 1
ADM_LOG = 512
CS_WORD = 4
ELEM_BASE = 8
LOCKEDEMPTY = 1

NOP, LOAD, STORE, XCHG, CAS, FAA, SPIN_EQ, SPIN_NE, DELAY = range(9)
LOADS = (LOAD, SPIN_EQ, SPIN_NE)
STORES = (STORE, XCHG, CAS, FAA)


class Lock:
    """A lock as the machine runs it: word layout, NUMA homes and one
    handler per program counter. Handler ``pc`` takes ``(t, regs, res)``
    (``res``: the result of the op the thread just completed) and
    returns ``(next_pc, op, arrive, admit)``; ``op`` is ``(kind, addr,
    a, b)``. Program counter 0 is the NCS, whose delay ``simulate``
    draws; ``cs2`` is the second CS op and ``release`` the first release
    step."""

    def __init__(self, n_threads: int):
        self.T = n_threads
        self.n_mem = ELEM_BASE
        self.home = {}                  # word -> thread (else thread 0)
        self.init = {}
        self.handlers = {}

    def per_thread(self) -> int:
        base = self.n_mem
        self.n_mem += self.T
        for t in range(self.T):
            self.home[base + t] = t
        return base

    def cs_entry(self, admit: bool, arrive: bool = False):
        return self.cs2, (LOAD, CS_WORD, 0, 0), arrive, admit

    def finish(self, release: int, cs2: int) -> None:
        self.release, self.cs2 = release, cs2
        self.handlers[0] = lambda t, r, res: (1, (DELAY, 0, 0, 0),
                                              False, False)
        self.handlers[cs2] = lambda t, r, res: (
            release, (STORE, CS_WORD, res + 1, 0), False, False)


def reciprocating(T: int, *, broken: bool = False) -> Lock:
    """Paper Listing 1. ``broken`` is the control's lock: the doorway
    also treats a LOCKEDEMPTY old tail as an empty stack, which breaks
    mutual exclusion."""
    k = Lock(T)
    arrivals = 0
    elem = k.per_thread()
    h = k.handlers
    SUCC, EOS = 0, 1

    h[1] = lambda t, r, res: (2, (STORE, elem + t, 0, 0), False, False)
    h[2] = lambda t, r, res: (3, (XCHG, arrivals, elem + t, 0), False, False)

    def consume_tail(t, r, res):
        uncont = res == 0 or (broken and res == LOCKEDEMPTY)
        if uncont:
            r[SUCC], r[EOS] = 0, elem + t
            return k.cs_entry(admit=True, arrive=True)
        r[SUCC], r[EOS] = (0 if res <= 1 else res), 0
        return 4, (SPIN_NE, elem + t, 0, 0), True, False
    h[3] = consume_tail

    def woke(t, r, res):
        if r[SUCC] == res:              # the terminus: end of segment
            r[SUCC], r[EOS] = 0, LOCKEDEMPTY
        else:
            r[EOS] = res
        return k.cs_entry(admit=True)
    h[4] = woke

    def handoff(t, r, res):
        if r[SUCC] != 0:
            return 0, (STORE, r[SUCC], r[EOS], 0), False, False
        return 6, (CAS, arrivals, r[EOS], 0), False, False
    h[5] = handoff

    def close(t, r, res):
        if res % 2 == 1:
            return 0, (NOP, 0, 0, 0), False, False
        return 7, (XCHG, arrivals, LOCKEDEMPTY, 0), False, False
    h[6] = close
    h[7] = lambda t, r, res: (0, (STORE, res, r[EOS], 0), False, False)
    k.finish(release=5, cs2=8)
    return k


def ticket(T: int) -> Lock:
    k = Lock(T)
    tk, gr = 0, 1
    h = k.handlers
    h[1] = lambda t, r, res: (2, (FAA, tk, 1, 0), False, False)

    def got(t, r, res):
        r[0] = res
        return 3, (SPIN_EQ, gr, res, 0), True, False
    h[2] = got
    h[3] = lambda t, r, res: k.cs_entry(admit=True)
    h[4] = lambda t, r, res: (5, (LOAD, gr, 0, 0), False, False)
    h[5] = lambda t, r, res: (0, (STORE, gr, res + 1, 0), False, False)
    k.finish(release=4, cs2=6)
    return k


def mcs(T: int) -> Lock:
    k = Lock(T)
    tail = 0
    nxt = k.per_thread()
    lck = k.per_thread()
    h = k.handlers
    h[1] = lambda t, r, res: (2, (STORE, nxt + t, 0, 0), False, False)
    h[2] = lambda t, r, res: (3, (STORE, lck + t, 1, 0), False, False)
    h[3] = lambda t, r, res: (4, (XCHG, tail, nxt + t, 0), False, False)

    def link(t, r, res):
        if res == 0:
            return k.cs_entry(admit=True, arrive=True)
        return 5, (STORE, res, nxt + t, 0), True, False
    h[4] = link
    h[5] = lambda t, r, res: (6, (SPIN_EQ, lck + t, 0, 0), False, False)
    h[6] = lambda t, r, res: k.cs_entry(admit=True)
    h[7] = lambda t, r, res: (8, (LOAD, nxt + t, 0, 0), False, False)

    def pass_or_close(t, r, res):
        if res != 0:        # the successor's locked flag, beside its next
            return 0, (STORE, res + (lck - nxt), 0, 0), False, False
        return 9, (CAS, tail, nxt + t, 0), False, False
    h[8] = pass_or_close

    def cas_done(t, r, res):
        if res % 2 == 1:
            return 0, (NOP, 0, 0, 0), False, False
        return 10, (SPIN_NE, nxt + t, 0, 0), False, False
    h[9] = cas_done
    h[10] = lambda t, r, res: (0, (STORE, res + (lck - nxt), 0, 0),
                               False, False)
    k.finish(release=7, cs2=11)
    return k


def clh(T: int) -> Lock:
    k = Lock(T)
    node = k.per_thread()
    dummy = k.n_mem
    k.n_mem += 1
    tail, head = 0, 1
    k.init[tail] = dummy
    h = k.handlers
    MYNODE, PRED = 0, 1

    def claim(t, r, res):
        if r[MYNODE] == 0:
            r[MYNODE] = node + t
        return 2, (STORE, r[MYNODE], 1, 0), False, False
    h[1] = claim
    h[2] = lambda t, r, res: (3, (XCHG, tail, r[MYNODE], 0), False, False)

    def watch_pred(t, r, res):
        r[PRED] = res
        return 4, (SPIN_EQ, res, 0, 0), True, False
    h[3] = watch_pred
    h[4] = lambda t, r, res: (5, (STORE, head, r[MYNODE], 0), False, False)

    def adopt(t, r, res):
        r[MYNODE] = r[PRED]
        return k.cs_entry(admit=True)
    h[5] = adopt
    h[6] = lambda t, r, res: (7, (LOAD, head, 0, 0), False, False)
    h[7] = lambda t, r, res: (0, (STORE, res, 0, 0), False, False)
    k.finish(release=6, cs2=8)
    return k


LOCKS = {"reciprocating": reciprocating, "ticket": ticket, "mcs": mcs,
         "clh": clh}


def cost_matrix(levels, T: int):
    """``(miss, remote)`` thread x home-thread tables of a balanced
    domain tree, ``levels`` innermost first as ``(name, units, cycles,
    numa_remote)``, threads packed contiguously."""
    caps, c = [], 1
    for lv in levels:
        c *= lv[1]
        caps.append(c)
    if T > caps[-1]:
        raise ValueError(f"{T} threads do not fit {caps[-1]} leaves")
    miss = [[0] * T for _ in range(T)]
    remote = [[False] * T for _ in range(T)]
    for i in range(T):
        for j in range(T):
            d = next(d for d, cap in enumerate(caps) if i // cap == j // cap)
            miss[i][j] = levels[d][2]
            remote[i][j] = bool(levels[d][3])
    return miss, remote


def xorshift(r: int) -> int:
    """One step of the 32-bit xorshift (13, 17, 5) of a thread's stream."""
    r ^= (r << 13) & M32
    r ^= r >> 17
    return r ^ ((r << 5) & M32)


def simulate(lock: Lock, levels, n_steps: int, *, hit: int = 1,
             seed: int = 0, ncs_max: int = 0) -> dict:
    """Run one point for ``n_steps`` micro-steps; return its counters.
    Thread ``t``'s NCS stream starts at ``t * 2654435761 + seed * 97 + 1``
    (mod 2**32) and steps once per NCS; the delay is the new state modulo
    ``ncs_max`` (0 with an empty NCS). ``me_violations`` counts
    admissions made while another thread held the lock (admission to its
    return to the NCS); ``bypass`` is the most admissions of any one
    thread while another waited (from the end of its doorway to its
    admission)."""
    T, W = lock.T, lock.n_mem
    rng = [(t * 2654435761 + seed * 97 + 1) & M32 for t in range(T)]
    miss_c, remote_c = cost_matrix(levels, T)
    home = [lock.home.get(w, 0) for w in range(W)]
    mem = [lock.init.get(w, 0) for w in range(W)]
    owner = [-1] * W
    sharers = [0] * W                   # bit t: thread t holds a copy
    pc = [0] * T
    regs = [[0] * 8 for _ in range(T)]
    cur = [(NOP, 0, 0, 0)] * T
    keyed = [0] * T                     # ready time, INF while blocked
    ready = [0] * T
    waiters = {}                        # word -> threads blocked on it
    episodes, misses, remote, inval = [0] * T, [0] * T, [0] * T, [0] * T
    arrive_time, lat_sum, returns = [0] * T, [0] * T, [0] * T
    adm_log = [-1] * ADM_LOG
    adm_cnt = 0
    now = 0
    holder, me_violations = -1, 0
    waiting, bypass = {}, 0             # waiter -> {peer: admissions}
    handlers = lock.handlers

    for _ in range(n_steps):
        t = keyed.index(min(keyed))
        kind, addr, a, b = cur[t]
        mval = mem[addr]
        start = max(now, ready[t])
        own = owner[addr]
        bit = 1 << t
        shared = sharers[addr] & bit
        hit_ = own == t or bool(shared)
        is_store = kind in STORES
        is_mem = is_store or kind in LOADS
        if not is_mem:
            cost = a if kind == DELAY else 0
            missed = False
        else:
            missed = not hit_ or (is_store and bool(shared) and own != t)
            cost = hit if (hit_ and (not is_store or own == t)) \
                else miss_c[t][home[addr]]
        finish = start + cost
        if missed:
            misses[t] += 1
            if remote_c[t][home[addr]]:
                remote[t] += 1
        if (kind == SPIN_EQ and mval != a) or (kind == SPIN_NE and mval == a):
            # the probe is paid and leaves a shared copy; the thread
            # blocks until a write to the word
            if not hit_:
                now = finish
            sharers[addr] |= bit
            ready[t] = finish
            keyed[t] = INF
            waiters.setdefault(addr, []).append(t)
            continue
        if missed:
            now = finish
        res = mval
        if is_store:
            if kind == CAS:
                ok = mval == a
                res = mval * 2 + ok
                if ok:
                    mem[addr] = b
            else:
                mem[addr] = mval + a if kind == FAA else a
            m = sharers[addr] & ~bit
            while m:
                low = m & -m
                inval[low.bit_length() - 1] += 1
                m ^= low
            if own >= 0 and own != t:
                inval[own] += 1
            sharers[addr] = bit
            owner[addr] = t
            if kind != CAS or res % 2 == 1:
                for w in waiters.pop(addr, ()):
                    ready[w] = max(ready[w], finish)
                    keyed[w] = ready[w]
        elif is_mem:
            sharers[addr] |= bit | ((1 << own) if own >= 0 else 0)
            if not hit_:
                owner[addr] = -1
        ready[t] = finish
        keyed[t] = finish
        old_pc = pc[t]
        npc, op, arrive, admit = handlers[old_pc](t, regs[t], res)
        if old_pc == 0:
            rng[t] = xorshift(rng[t])
            op = (DELAY, 0, rng[t] % ncs_max if ncs_max else 0, 0)
        if admit:
            lat_sum[t] += finish - arrive_time[t]
            episodes[t] += 1
            adm_log[adm_cnt % ADM_LOG] = t
            adm_cnt += 1
            if holder >= 0:
                me_violations += 1
            holder = t
            waiting.pop(t, None)
            for seen in waiting.values():
                seen[t] = seen.get(t, 0) + 1
                bypass = max(bypass, seen[t])
        if arrive:
            arrive_time[t] = finish
            if not admit:
                waiting[t] = {}
        if npc == 0 and old_pc != 0:
            returns[t] += 1
            if holder == t:
                holder = -1
        pc[t] = npc
        cur[t] = op
    return {"episodes": episodes, "misses": misses, "remote": remote,
            "inval_recv": inval, "lat_sum": lat_sum, "returns": returns,
            "time": now, "adm_log": adm_log, "adm_cnt": adm_cnt,
            "me_violations": me_violations, "bypass": bypass}


def bypass_bound(adm_log, adm_cnt) -> int:
    """Most admissions of any single other thread between two
    consecutive admissions of one thread, over the logged window (the
    newest ``ADM_LOG`` admissions, oldest first)."""
    worst = 0
    for log, cnt in zip(np.atleast_2d(np.asarray(adm_log)),
                        np.atleast_1d(np.asarray(adm_cnt))):
        K, cnt = len(log), int(cnt)
        seq = [int(x) for x in (np.roll(log, -(cnt % K)) if cnt >= K
                                else log[:cnt]) if x >= 0]
        last = {}
        for i, t in enumerate(seq):
            if t in last and i - last[t] > 1:
                counts = {}
                for u in seq[last[t] + 1:i]:
                    counts[u] = counts.get(u, 0) + 1
                worst = max(worst, max(counts.values()))
            last[t] = i
    return worst


def summarize(points: list) -> dict:
    """The per-cell numbers of a seed ensemble of points (each from
    ``simulate``): totals over the ensemble, means over its points."""
    eps = np.asarray([sum(p["episodes"]) for p in points])
    time = np.maximum(np.asarray([p["time"] for p in points]), 1)
    total = max(int(eps.sum()), 1)
    per_thread = np.asarray([p["episodes"] for p in points])
    lo = np.maximum(per_thread.min(axis=1), 1)

    def per_episode(key):
        return float(sum(sum(p[key]) for p in points) / total)
    adm = np.asarray([p["adm_log"] for p in points])
    cnt = np.asarray([p["adm_cnt"] for p in points])
    return {
        "throughput": float((eps / time).mean() * 1e3),
        "episodes": int(eps.sum()),
        "miss_per_episode": per_episode("misses"),
        "inval_per_episode": per_episode("inval_recv"),
        "remote_per_episode": per_episode("remote"),
        "latency": per_episode("lat_sum"),
        "unfairness": float((per_thread.max(axis=1) / lo).mean()),
        "admissions": adm.tolist(),
        "admission_counts": cnt.tolist(),
        "aborts": max(sum(sum(p["returns"]) for p in points)
                      - int(eps.sum()), 0),
        "preempts": 0,
        "bypass_bound": bypass_bound(adm, cnt),
    }
