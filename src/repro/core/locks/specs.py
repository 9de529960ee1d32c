"""The lock zoo, authored as declarative ``LockSpec`` phase specs.

Every lock is a spec function ``def name(s): ...`` declaring memory
regions, symbolic registers and labelled steps in the four phases
(``doorway`` / ``waiting`` / ``entry`` / ``release`` — see
``core/locks/dsl.py``); ``core/locks/compile.py`` lowers it to the
``Program`` handler-table form and injects the shared NCS/CS scaffolding.
Op semantics and result encodings (CAS ``old * 2 + ok``, SPIN blocking,
PARK_EQ costs, LOCKEDEMPTY == 1) are the contract table at the top of
``core/sim/machine.py``.

Paper roster (each compiles to byte-identical metrics vs the pre-DSL
hand-rolled tables — asserted by ``tests/test_lock_dsl.py``):
``reciprocating`` (Listing 1), ``retrograde`` ticket (Listing 7),
``ticket``, ``mcs``, ``clh``, ``hemlock``, ``ttas``, ``anderson``.

Extended roster (the follow-up papers the DSL makes cheap to express —
PAPERS.md): ``hapax`` (value-based FIFO admission), ``fissile`` (TS fast
path grafted onto a queue slow path), ``spin_then_park`` (bounded spin,
then park/unpark under the machine's park cost model).

Abortable roster (the hostile-OS layer — timed waits via the DSL's
``abort`` phase and the ``PARK_*_TIMEOUT`` ops): ``reciprocating_abortable``
(true abort: a CAS-consumed grant *baton* over ticket-tagged cells, so an
impatient waiter withdraws by publishing an abort marker the release walk
reclaims) and ``mcs_timeout`` (relay abort, AQS-style: a timed-out waiter
keeps its queue node and, once granted, forwards the handoff through the
release chain without entering the CS).
"""
from __future__ import annotations

from repro.core.locks.dsl import (
    CAS, DELAY, FAA, LOAD, LOCKEDEMPTY, NCS, NOP, PARK_EQ, PARK_EQ_TIMEOUT,
    SPIN_EQ, SPIN_NE, STORE, XCHG,
)


# ---------------------------------------------------------------------------
# Reciprocating (paper Listing 1)
# ---------------------------------------------------------------------------
def reciprocating(s):
    """Arrival stack + detached entry segment: XCHG push in the doorway,
    local spin on the own element, handoff (or segment close) on release."""
    arrivals = s.word("arrivals")
    elem = s.per_thread("element")
    s.regs("succ", "eos")
    s.expect(doorway="constant", release="wait_free", spin="own",
             footprint=1, bypass=2)

    @s.step("doorway")
    def prepare(c):                         # E = 0 (clean wait element)
        return c.op(STORE(elem.at(c.t), 0))

    @s.step("doorway")
    def push(c):                            # push E onto the arrival stack
        return c.op(XCHG(arrivals, elem.at(c.t)))

    @s.step("doorway")
    def consume_tail(c):                    # doorway: inspect the old tail
        E = elem.at(c.t)
        uncont = c.res == 0
        succ = c.where(c.res <= 1, 0, c.res)      # coerce LOCKEDEMPTY
        c.r.succ = c.where(uncont, 0, succ)
        c.r.eos = c.where(uncont, E, 0)
        return c.when(uncont, c.enter_cs(admit=True),
                      c.op(SPIN_NE(E, 0), to="woke"), arrive=True)

    @s.step("waiting")
    def woke(c):                            # res = eos value from the gate
        succ = c.r.succ
        term = succ == c.res                # terminus sentinel?
        c.r.succ = c.where(term, 0, succ)
        c.r.eos = c.where(term, LOCKEDEMPTY, c.res)
        return c.enter_cs(admit=True)

    @s.step("release")
    def handoff(c):                         # pass eos to succ, or close
        succ, eos = c.r.succ, c.r.eos
        has_succ = succ != 0
        return c.when(has_succ, c.op(STORE(succ, eos), to=NCS),
                      c.op(CAS(arrivals, eos, 0)))

    @s.step("release")
    def close(c):                           # res = CAS old*2+ok
        ok = (c.res % 2) == 1
        return c.when(ok, c.op(NOP(), to=NCS),
                      c.op(XCHG(arrivals, LOCKEDEMPTY)))

    @s.step("release")
    def detach(c):                          # res = detached head element
        return c.op(STORE(c.res, c.r.eos), to=NCS)


# ---------------------------------------------------------------------------
# Ticket lock
# ---------------------------------------------------------------------------
def ticket(s):
    """FIFO by FAA ticket; global spin on the grant word (the Fig. 1
    collapse case)."""
    tk, gr = s.word("ticket"), s.word("grant")
    s.regs("my")
    s.expect(doorway="constant", release="wait_free", spin="shared",
             footprint=0, bypass=1)

    @s.step("doorway")
    def take(c):
        return c.op(FAA(tk, 1))

    @s.step("doorway")
    def got(c):
        c.r.my = c.res
        return c.op(SPIN_EQ(gr, c.res), arrive=True)

    @s.step("entry")
    def granted(c):
        return c.enter_cs(admit=True)

    @s.step("release")
    def load_grant(c):
        return c.op(LOAD(gr))

    @s.step("release")
    def bump_grant(c):
        return c.op(STORE(gr, c.res + 1), to=NCS)


# ---------------------------------------------------------------------------
# Retrograde ticket (paper Listing 7)
# ---------------------------------------------------------------------------
def retrograde(s):
    """Ticket lock whose release walks the grant *backwards* through the
    entry segment — mimics reciprocating admission on ticket state."""
    tk, gr = s.word("ticket"), s.word("grant")
    top, bs = s.word("top"), s.word("base")
    s.regs("my", "g", "hi", "tmp")
    s.expect(doorway="constant", release="wait_free", spin="shared",
             footprint=0, bypass=2)

    @s.step("doorway")
    def take(c):
        return c.op(FAA(tk, 1))

    @s.step("doorway")
    def got(c):
        c.r.my = c.res
        return c.op(SPIN_EQ(gr, c.res), arrive=True)

    @s.step("entry")
    def granted(c):
        return c.enter_cs(admit=True)

    @s.step("release")
    def load_grant(c):
        return c.op(LOAD(gr))

    @s.step("release")
    def load_base(c):
        c.r.g = c.res - 1
        return c.op(LOAD(bs))

    @s.step("release")
    def descend_or_flip(c):                 # res = base of entry segment
        desc = c.r.g > c.res                # still inside the segment
        return c.when(desc, c.op(STORE(gr, c.r.g), to=NCS),
                      c.op(LOAD(top)))

    @s.step("release")
    def read_top(c):                        # res = segment top
        c.r.hi = c.res
        return c.op(STORE(bs, c.res))

    @s.step("release")
    def read_ticket(c):
        return c.op(LOAD(tk))

    @s.step("release")
    def stage_top(c):                       # res = current ticket
        c.r.tmp = c.res
        return c.op(STORE(top, c.res - 1))

    @s.step("release")
    def flip(c):
        empty = c.r.tmp == c.r.hi + 1       # no waiters
        return c.when(empty, c.op(STORE(top, c.r.tmp)),
                      c.op(STORE(gr, c.r.tmp - 1), to=NCS))

    @s.step("release")
    def reset_base(c):
        return c.op(STORE(bs, c.r.tmp))

    @s.step("release")
    def reset_grant(c):
        return c.op(STORE(gr, c.r.tmp), to=NCS)


# ---------------------------------------------------------------------------
# MCS
# ---------------------------------------------------------------------------
def mcs(s):
    """Queue lock: swap onto the tail, link behind the predecessor, local
    spin on the own ``locked`` flag."""
    tail = s.word("tail")
    nxt = s.per_thread("next")
    lck = s.per_thread("locked")
    s.expect(doorway="constant", release="waits", spin="own",
             footprint=2, bypass=1)

    @s.step("doorway")
    def clear_next(c):
        return c.op(STORE(nxt.at(c.t), 0))

    @s.step("doorway")
    def set_locked(c):
        return c.op(STORE(lck.at(c.t), 1))

    @s.step("doorway")
    def swap_tail(c):
        return c.op(XCHG(tail, nxt.at(c.t)))

    @s.step("doorway")
    def link(c):                            # res = predecessor (old tail)
        uncont = c.res == 0
        return c.when(uncont, c.enter_cs(admit=True),
                      c.op(STORE(c.res, nxt.at(c.t))), arrive=True)

    @s.step("waiting")
    def wait_grant(c):
        return c.op(SPIN_EQ(lck.at(c.t), 0))

    @s.step("entry")
    def granted(c):
        return c.enter_cs(admit=True)

    @s.step("release")
    def read_next(c):
        return c.op(LOAD(nxt.at(c.t)))

    @s.step("release")
    def pass_or_close(c):                   # res = successor next-addr
        has = c.res != 0
        return c.when(has, c.op(STORE(lck.translate(c.res, nxt), 0), to=NCS),
                      c.op(CAS(tail, nxt.at(c.t), 0)))

    @s.step("release")
    def cas_done(c):                        # res = CAS old*2+ok
        ok = (c.res % 2) == 1
        return c.when(ok, c.op(NOP(), to=NCS),
                      c.op(SPIN_NE(nxt.at(c.t), 0)))

    @s.step("release")
    def wake_late(c):                       # res = late successor next-addr
        return c.op(STORE(lck.translate(c.res, nxt), 0), to=NCS)


# ---------------------------------------------------------------------------
# CLH (Scott 4.14) — nodes circulate; T+1 nodes, tail starts at the dummy
# ---------------------------------------------------------------------------
def clh(s):
    """Implicit queue: spin on the *predecessor's* node. Nodes circulate,
    so static NUMA homes go stale over time — exactly the paper's point."""
    node = s.per_thread("node")
    dummy = s.array("dummy", 1)
    tail = s.word("tail", init=dummy.base)
    head = s.word("head")
    s.regs("mynode", "pred")
    s.expect(doorway="constant", release="wait_free", spin="cell",
             footprint=1, bypass=1)

    @s.step("doorway")
    def claim(c):                           # lazy first-episode node init
        mynode = c.where(c.r.mynode == 0, node.at(c.t), c.r.mynode)
        c.r.mynode = mynode
        return c.op(STORE(mynode, 1))

    @s.step("doorway")
    def swap_tail(c):
        return c.op(XCHG(tail, c.r.mynode))

    @s.step("doorway")
    def watch_pred(c):                      # res = predecessor node
        c.r.pred = c.res
        return c.op(SPIN_EQ(c.res, 0), arrive=True)

    @s.step("waiting")
    def publish_head(c):
        return c.op(STORE(head, c.r.mynode))

    @s.step("entry")
    def adopt(c):                           # recycle the pred's node
        c.r.mynode = c.r.pred
        return c.enter_cs(admit=True)

    @s.step("release")
    def load_head(c):
        return c.op(LOAD(head))

    @s.step("release")
    def clear_flag(c):                      # res = head node addr
        return c.op(STORE(c.res, 0), to=NCS)


# ---------------------------------------------------------------------------
# HemLock — CTR-style: grant word doubles as the queue link
# ---------------------------------------------------------------------------
def hemlock(s):
    """Tail swap like MCS, but the successor acknowledges the handoff by
    clearing the *predecessor's* grant word (no queue nodes)."""
    LOCK_ID = 5     # sentinel *value* written into a grant word
    tail = s.word("tail")
    grant = s.per_thread("grant")
    s.regs("pred")
    s.expect(doorway="constant", release="waits", spin="cell",
             footprint=1, bypass=1)

    @s.step("doorway")
    def swap_tail(c):
        return c.op(XCHG(tail, grant.at(c.t)))

    @s.step("doorway")
    def check(c):                           # res = predecessor grant addr
        uncont = c.res == 0
        c.r.pred = c.res
        return c.when(uncont, c.enter_cs(admit=True),
                      c.op(SPIN_EQ(c.res, LOCK_ID)), arrive=True)

    @s.step("waiting")
    def ack(c):                             # grant[pred] = 0 (consume)
        return c.op(STORE(c.r.pred, 0))

    @s.step("entry")
    def granted(c):
        return c.enter_cs(admit=True)

    @s.step("release")
    def try_close(c):
        return c.op(CAS(tail, grant.at(c.t), 0))

    @s.step("release")
    def closed(c):                          # res = CAS old*2+ok
        ok = (c.res % 2) == 1
        return c.when(ok, c.op(NOP(), to=NCS),
                      c.op(STORE(grant.at(c.t), LOCK_ID)))

    @s.step("release")
    def wait_ack(c):
        return c.op(SPIN_EQ(grant.at(c.t), 0), to=NCS)


# ---------------------------------------------------------------------------
# TTAS (polite test-and-test-and-set) — no doorway: not FCFS
# ---------------------------------------------------------------------------
def ttas(s):
    """Global spinning on one flag word; every handoff is a broadcast
    invalidation storm (the other Fig. 1 collapse case)."""
    flag = s.word("flag")
    s.expect(doorway="none", release="wait_free", spin="shared",
             footprint=0, bypass=None)

    @s.step("waiting")
    def wait_free(c):
        return c.op(SPIN_EQ(flag, 0), arrive=True)

    @s.step("entry")
    def grab(c):
        return c.op(XCHG(flag, 1))

    @s.step("entry")
    def check(c):                           # res = old flag value
        got = c.res == 0
        return c.when(got, c.enter_cs(admit=True),
                      c.op(SPIN_EQ(flag, 0), to="grab"))

    @s.step("release")
    def unlock(c):
        return c.op(STORE(flag, 0), to=NCS)


# ---------------------------------------------------------------------------
# Anderson array lock
# ---------------------------------------------------------------------------
def anderson(s):
    """FIFO by FAA over an array of spin slots (flag-based; contrast with
    ``hapax``'s value-based cells)."""
    nxt = s.word("next_slot")
    slots = s.array("slots", s.T, init={0: 1})
    s.regs("slot")
    s.expect(doorway="constant", release="wait_free", spin="cell",
             footprint=0, bypass=1)

    @s.step("doorway")
    def take(c):
        return c.op(FAA(nxt, 1))

    @s.step("doorway")
    def watch(c):                           # res = my slot index (ticket)
        slot = slots.at(c.res % s.T)
        c.r.slot = slot
        return c.op(SPIN_EQ(slot, 1), arrive=True)

    @s.step("waiting")
    def consume(c):                         # reset my slot for reuse
        return c.op(STORE(c.r.slot, 0))

    @s.step("entry")
    def granted(c):
        return c.enter_cs(admit=True)

    @s.step("release")
    def grant_next(c):
        here = c.r.slot - slots.base
        return c.op(STORE(slots.at((here + 1) % s.T), 1), to=NCS)


# ---------------------------------------------------------------------------
# Hapax — value-based FIFO admission (extended roster, PAPERS.md)
# ---------------------------------------------------------------------------
def hapax(s):
    """Hapax-style value-based mutual exclusion (Dice & Kogan): FIFO
    admission decided by *values*, constant-time arrival and release.

    Ticket k waits until cell ``k mod T`` *holds the value k*; release of
    k publishes ``k+1`` into the successor cell. Values increase
    monotonically, so a stale cell can never falsely admit — the ABA
    hazard that forces flag-based array locks (``anderson``) to consume
    and reset their slots disappears, and release is a single store.
    (Sim-level embodiment of the value-based idea, not the paper's exact
    word layout.)"""
    tk = s.word("ticket")
    cells = s.array("cells", s.T)
    s.regs("my")
    s.expect(doorway="constant", release="wait_free", spin="cell",
             footprint=0, bypass=1)

    @s.step("doorway")
    def take(c):
        return c.op(FAA(tk, 1))

    @s.step("doorway")
    def watch(c):                           # res = my ticket value
        c.r.my = c.res
        return c.op(SPIN_EQ(cells.at(c.res % s.T), c.res), arrive=True)

    @s.step("entry")
    def granted(c):
        return c.enter_cs(admit=True)

    @s.step("release")
    def publish(c):
        nxt = c.r.my + 1
        return c.op(STORE(cells.at(nxt % s.T), nxt), to=NCS)


# ---------------------------------------------------------------------------
# Fissile — TS fast path over a queue slow path (extended roster)
# ---------------------------------------------------------------------------
def fissile(s):
    """Fissile-style composite lock (Dice & Kogan): an uncontended
    test-and-set fast path grafted onto a FIFO queue slow path.

    Arrivals first try one XCHG on the fast word; on failure they take a
    ticket and wait in value-based FIFO order (as ``hapax``), and *only
    the queue head* competes with barging fast-path arrivals for the fast
    word — competition for the TS word stays O(1) while the queue absorbs
    the rest. Release is a single store for both paths."""
    fast = s.word("fast")
    tk = s.word("ticket")
    cells = s.array("cells", s.T)
    s.regs("my")
    s.expect(doorway="constant", release="wait_free", spin="shared",
             footprint=0, bypass=None)

    @s.step("doorway")
    def try_fast(c):
        return c.op(XCHG(fast, 1))

    @s.step("doorway")
    def check_fast(c):                      # res = old fast word
        got = c.res == 0
        return c.when(got, c.enter_cs(admit=True),
                      c.op(FAA(tk, 1)), arrive=True)

    @s.step("waiting")
    def join_queue(c):                      # res = my ticket value
        c.r.my = c.res
        return c.op(SPIN_EQ(cells.at(c.res % s.T), c.res))

    @s.step("waiting")
    def head_grab(c):                       # queue head: contend for fast
        return c.op(XCHG(fast, 1))

    @s.step("waiting")
    def head_check(c):                      # res = old fast word
        got = c.res == 0
        nxt = c.r.my + 1
        return c.when(got, c.op(STORE(cells.at(nxt % s.T), nxt)),
                      c.op(DELAY(8), to="head_grab"))

    @s.step("entry")
    def pass_baton(c):                      # successor advances to head
        return c.enter_cs(admit=True)

    @s.step("release")
    def unlock(c):
        return c.op(STORE(fast, 0), to=NCS)


# ---------------------------------------------------------------------------
# Spin-then-park — MCS waiting with a bounded spin, then PARK (extended)
# ---------------------------------------------------------------------------
def spin_then_park(s):
    """MCS queue with the classic engineering compromise in the waiting
    phase: probe the grant flag a few times (fast handoff while the CS is
    short), then *park*. Park/unpark latencies are charged by the
    machine's cost model (``CostModel.park_cost`` / ``unpark_cost`` — the
    PARK_EQ row of the machine.py contract table), so the throughput cost
    of parking is measurable, not assumed."""
    SPIN_BUDGET = 4     # probes before giving up and parking
    BACKOFF = 6         # private cycles between probes
    tail = s.word("tail")
    nxt = s.per_thread("next")
    lck = s.per_thread("locked")
    s.regs("spins")
    s.expect(doorway="constant", release="waits", spin="own",
             footprint=2, bypass=1)

    @s.step("doorway")
    def clear_next(c):
        return c.op(STORE(nxt.at(c.t), 0))

    @s.step("doorway")
    def set_locked(c):
        return c.op(STORE(lck.at(c.t), 1))

    @s.step("doorway")
    def swap_tail(c):
        return c.op(XCHG(tail, nxt.at(c.t)))

    @s.step("doorway")
    def link(c):                            # res = predecessor (old tail)
        uncont = c.res == 0
        c.r.spins = SPIN_BUDGET
        return c.when(uncont, c.enter_cs(admit=True),
                      c.op(STORE(c.res, nxt.at(c.t))), arrive=True)

    @s.step("waiting")
    def probe(c):
        return c.op(LOAD(lck.at(c.t)))

    @s.step("waiting")
    def probe_check(c):                     # res = my locked flag
        free = c.res == 0
        c.r.spins = c.r.spins - 1
        exhausted = c.r.spins <= 0
        park = c.op(PARK_EQ(lck.at(c.t), 0), to="granted")
        spin_more = c.op(DELAY(BACKOFF), to="probe")
        return c.when(free, c.enter_cs(admit=True),
                      c.when(exhausted, park, spin_more))

    @s.step("entry")
    def granted(c):
        return c.enter_cs(admit=True)

    @s.step("release")
    def read_next(c):
        return c.op(LOAD(nxt.at(c.t)))

    @s.step("release")
    def pass_or_close(c):                   # res = successor next-addr
        has = c.res != 0
        return c.when(has, c.op(STORE(lck.translate(c.res, nxt), 0), to=NCS),
                      c.op(CAS(tail, nxt.at(c.t), 0)))

    @s.step("release")
    def cas_done(c):                        # res = CAS old*2+ok
        ok = (c.res % 2) == 1
        return c.when(ok, c.op(NOP(), to=NCS),
                      c.op(SPIN_NE(nxt.at(c.t), 0)))

    @s.step("release")
    def wake_late(c):                       # res = late successor next-addr
        return c.op(STORE(lck.translate(c.res, nxt), 0), to=NCS)


# ---------------------------------------------------------------------------
# Reciprocating-abortable — true abort over ticket-tagged grant batons
# ---------------------------------------------------------------------------
def reciprocating_abortable(s):
    """Retrograde (reciprocating-admission) ticket lock with *true abort*.

    Grants travel as a **baton**: releasing ticket g's holder XCHGs the
    tag ``g*4+1`` into cell ``g mod T``; admission is an atomic
    CAS-consume of a baton (tag -> 0), so at most one baton exists and
    mutual exclusion reduces to CAS atomicity. Ticket-unique tags make
    cell reuse ABA-safe without generation counters.

    An impatient waiter (timed park exhausted) withdraws by CASing the
    abort marker ``my*4+2`` into its cell — never over a live baton: a
    baton found while probing is the lock itself and is consumed
    instead (ghost batons of aborted residue-mates are reclaimed the
    same way, which is what keeps the lock live when a marker was
    displaced). The release walk, on finding its grant displaced an
    abort marker, retracts the just-published baton by CAS and walks on
    to the next ticket — unless the retract loses, which means a prober
    already consumed the baton and the handoff is complete."""
    PATIENCE = 1200     # private cycles per timed-park round
    ROUNDS = 4          # park rounds before withdrawing
    tk = s.word("ticket")
    gr = s.word("grant")
    top = s.word("top")
    bs = s.word("base")
    cells = s.array("cells", s.T, init={0: 1})   # baton for ticket 0
    s.regs("my", "tries", "g", "hi", "tmp")
    # The release walk retracts ghost batons in a loop (retract ->
    # load_base) — the declared opt-out the gate's safety floor points at.
    s.expect(doorway="constant", release="unbounded", spin="cell",
             footprint=0, bypass=2)

    def park(c, to="round"):
        return c.op(PARK_EQ_TIMEOUT(cells.at(c.r.my % s.T),
                                    c.r.my * 4 + 1, PATIENCE), to=to)

    @s.step("doorway")
    def take(c):
        return c.op(FAA(tk, 1))

    @s.step("doorway")
    def got(c):                             # res = my ticket
        c.r.my = c.res
        c.r.tries = ROUNDS
        return c.op(PARK_EQ_TIMEOUT(cells.at(c.res % s.T), c.res * 4 + 1,
                                    PATIENCE), to="round", arrive=True)

    @s.step("waiting")
    def round(c):                           # res = cell*2 + ok
        ok = (c.res % 2) == 1
        mine = cells.at(c.r.my % s.T)
        return c.when(ok, c.op(CAS(mine, c.r.my * 4 + 1, 0), to="consume"),
                      c.op(LOAD(mine), to="probe"))

    @s.step("waiting")
    def consume(c):                         # res = old*2 + ok
        ok = (c.res % 2) == 1
        # lost the baton to a ghost-reclaiming residue mate: wait again
        return c.when(ok, c.enter_cs(admit=True), park(c))

    @s.step("abort")
    def probe(c):                           # res = cell value (timed out)
        v = c.res
        mine = cells.at(c.r.my % s.T)
        is_baton = (v % 4) == 1             # a grant tag — mine or a ghost
        c.r.tries = c.r.tries - 1
        exhausted = c.r.tries <= 0
        take_baton = c.op(CAS(mine, v, 0), to="reclaim")
        withdraw = c.op(CAS(mine, v, c.r.my * 4 + 2), to="abort_done")
        return c.when(is_baton, take_baton,
                      c.when(exhausted, withdraw, park(c)))

    @s.step("abort")
    def reclaim(c):                         # res = old*2 + ok
        ok = (c.res % 2) == 1
        return c.when(ok, c.enter_cs(admit=True), park(c))

    @s.step("abort")
    def abort_done(c):                      # res = old*2 + ok
        ok = (c.res % 2) == 1
        # marker placed: episode abandoned (no admit). A failed CAS
        # means the cell changed under us — re-examine before leaving.
        return c.when(ok, c.op(NOP(), to=NCS), park(c))

    @s.step("release")
    def load_grant(c):
        return c.op(LOAD(gr))

    @s.step("release")
    def load_base(c):                       # res = granted ticket
        c.r.g = c.res - 1
        return c.op(LOAD(bs))

    @s.step("release")
    def descend_or_flip(c):                 # res = segment base
        desc = c.r.g > c.res
        return c.when(desc, c.op(STORE(gr, c.r.g), to="publish"),
                      c.op(LOAD(top), to="read_top"))

    @s.step("release")
    def publish(c):                         # baton for ticket g
        g = c.r.g
        return c.op(XCHG(cells.at(g % s.T), g * 4 + 1))

    @s.step("release")
    def delivered(c):                       # res = displaced cell value
        aborted = c.res == c.r.g * 4 + 2
        return c.when(aborted,
                      c.op(CAS(cells.at(c.r.g % s.T), c.r.g * 4 + 1, 0),
                           to="retract"),
                      c.op(NOP(), to=NCS))

    @s.step("release")
    def retract(c):                         # res = old*2 + ok
        ok = (c.res % 2) == 1
        # retracted the ghost baton: reclaim g, walk on to the next
        # ticket; a lost CAS means a prober consumed it — handoff done
        return c.when(ok, c.op(LOAD(gr), to="load_base"),
                      c.op(NOP(), to=NCS))

    @s.step("release")
    def read_top(c):                        # res = segment top
        c.r.hi = c.res
        return c.op(STORE(bs, c.res))

    @s.step("release")
    def read_ticket(c):
        return c.op(LOAD(tk))

    @s.step("release")
    def stage_top(c):                       # res = current ticket
        c.r.tmp = c.res
        return c.op(STORE(top, c.res - 1))

    @s.step("release")
    def flip(c):
        empty = c.r.tmp == c.r.hi + 1       # no waiters
        c.r.g = c.where(empty, c.r.tmp, c.r.tmp - 1)
        return c.when(empty, c.op(STORE(top, c.r.tmp)),
                      c.op(STORE(gr, c.r.tmp - 1), to="publish"))

    @s.step("release")
    def reset_base(c):
        return c.op(STORE(bs, c.r.tmp))

    @s.step("release")
    def reset_grant(c):                     # pre-grant the next ticket
        return c.op(STORE(gr, c.r.tmp), to="publish")


# ---------------------------------------------------------------------------
# MCS-timeout — relay abort (AQS-style lazy cancellation)
# ---------------------------------------------------------------------------
def mcs_timeout(s):
    """MCS whose waiters time out into *relay* mode: the impatient waiter
    abandons its CS claim but keeps its queue node (unlinking a middle
    node needs neighbour coordination — the AQS/lazy-abort compromise);
    once the grant arrives it forwards the handoff straight through the
    shared release chain without entering the critical section. Queue
    integrity is preserved by construction; the cost is that an aborted
    waiter is only *logically* gone until its grant shows up."""
    PATIENCE = 1600     # private cycles per timed-park round
    ROUNDS = 3          # park rounds before giving up the claim
    tail = s.word("tail")
    nxt = s.per_thread("next")
    lck = s.per_thread("locked")
    s.regs("tries")
    s.expect(doorway="constant", release="waits", spin="own",
             footprint=2, bypass=1)

    @s.step("doorway")
    def clear_next(c):
        return c.op(STORE(nxt.at(c.t), 0))

    @s.step("doorway")
    def set_locked(c):
        return c.op(STORE(lck.at(c.t), 1))

    @s.step("doorway")
    def swap_tail(c):
        return c.op(XCHG(tail, nxt.at(c.t)))

    @s.step("doorway")
    def link(c):                            # res = predecessor (old tail)
        uncont = c.res == 0
        c.r.tries = ROUNDS
        return c.when(uncont, c.enter_cs(admit=True),
                      c.op(STORE(c.res, nxt.at(c.t))), arrive=True)

    @s.step("waiting")
    def wait_grant(c):
        return c.op(PARK_EQ_TIMEOUT(lck.at(c.t), 0, PATIENCE))

    @s.step("waiting")
    def check_grant(c):                     # res = lck*2 + ok
        ok = (c.res % 2) == 1
        c.r.tries = c.r.tries - 1
        patient = c.r.tries > 0
        return c.when(ok, c.enter_cs(admit=True),
                      c.when(patient,
                             c.op(PARK_EQ_TIMEOUT(lck.at(c.t), 0, PATIENCE),
                                  to="check_grant"),
                             c.op(PARK_EQ(lck.at(c.t), 0), to="relay")))

    @s.step("abort")
    def relay(c):
        # granted after giving up: skip the CS, relay the handoff
        return c.op(LOAD(nxt.at(c.t)), to="pass_or_close")

    @s.step("release")
    def read_next(c):
        return c.op(LOAD(nxt.at(c.t)))

    @s.step("release")
    def pass_or_close(c):                   # res = successor next-addr
        has = c.res != 0
        return c.when(has, c.op(STORE(lck.translate(c.res, nxt), 0), to=NCS),
                      c.op(CAS(tail, nxt.at(c.t), 0)))

    @s.step("release")
    def cas_done(c):                        # res = CAS old*2+ok
        ok = (c.res % 2) == 1
        return c.when(ok, c.op(NOP(), to=NCS),
                      c.op(SPIN_NE(nxt.at(c.t), 0)))

    @s.step("release")
    def wake_late(c):                       # res = late successor next-addr
        return c.op(STORE(lck.translate(c.res, nxt), 0), to=NCS)


#: The full roster: paper locks first (spec-for-spec equal to the frozen
#: pre-DSL tables), then the extended variants the DSL made cheap.
SPECS = {
    "reciprocating": reciprocating,
    "ticket": ticket,
    "retrograde": retrograde,
    "mcs": mcs,
    "clh": clh,
    "hemlock": hemlock,
    "ttas": ttas,
    "anderson": anderson,
    "hapax": hapax,
    "fissile": fissile,
    "spin_then_park": spin_then_park,
    "reciprocating_abortable": reciprocating_abortable,
    "mcs_timeout": mcs_timeout,
}

#: Variants added on top of the paper's roster (the `locks-ext` suite).
NEW_VARIANTS = ("hapax", "fissile", "spin_then_park")

#: Abortable/timeout variants (the `hostile` suite): locks whose specs
#: use the DSL ``abort`` phase and the timed-park ops.
ABORTABLE_VARIANTS = ("reciprocating_abortable", "mcs_timeout")
