"""Declarative lock-authoring DSL: ``LockSpec`` phase specs.

A lock is authored as named *phases* — ``doorway`` (the constant-time
arrival path), ``waiting`` (local spinning on a wait element), ``entry``
(admission into the critical section) and ``release`` — each a short list
of *steps*. A step is a function ``fn(c)`` receiving a :class:`Ctx` and
returning a :class:`StepOut`; it consumes ``c.res``, the result of the op
the previous step emitted, and emits the next op. Op semantics and result
encodings (CAS ``old * 2 + ok``, SPIN_EQ/SPIN_NE blocking, PARK_EQ park
costs, LOCKEDEMPTY == 1) are defined once, in the contract table at the
top of ``core/sim/machine.py`` — not here.

What the DSL removes relative to hand-rolled handler tables:

* **raw PCs** — steps are addressed by *label* (default: the step
  function's name); ``to="woke"`` instead of ``pc=4``. The compiler
  (``core/locks/compile.py``) assigns program counters.
* **magic addresses** — memory is *declared*: ``s.word("tail")`` for lock
  words (compiler-assigned addresses 0..3, NUMA-homed on node 0),
  ``s.per_thread("element")`` for per-thread wait elements (homed on the
  owning thread's node — the paper's 128B sequestering), ``s.array(...)``
  for global slot arrays.
* **copy-pasted scaffolding** — the NCS delay handler and the CS-profile
  handlers (``rw``/``ro``/``local``, paper §7.1) are injected by the
  compiler. A step enters the critical section with ``c.enter_cs()``; an
  episode ends with ``to=NCS``.
* **implicit instrumentation** — ``arrive=True`` marks doorway completion
  and ``admit=True`` marks CS admission (they feed the latency/fairness
  metrics and the admission log); the markers are explicit keywords, not
  buried flag tuples.

Control flow is data-flow, exactly as in the underlying machine: a step
branches with ``c.when(cond, then_out, else_out)``, which merges two
``StepOut``s component-wise with ``jnp.where``. Conditional register
updates are written the same way: ``c.r.succ = c.where(cond, a, b)``.

A complete lock in ~15 lines (see ``core/locks/specs.py`` for the zoo,
``examples/define_a_lock.py`` for a runnable walkthrough)::

    def ticket(s):
        tk, gr = s.word("ticket"), s.word("grant")
        s.regs("my")

        @s.step("doorway")
        def take(c):
            return c.op(FAA(tk, 1))             # falls through to `got`

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
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax.numpy as jnp

from repro.core.sim import machine as M

I32 = jnp.int32

#: Reserved jump target: episode complete, re-enter the injected NCS delay.
NCS = "ncs"

#: Phase taxonomy (paper's structure). ``doorway`` may be empty for
#: non-FCFS locks (TTAS has no constant-time doorway — that's the point).
#: ``abort`` holds the steps an impatient waiter runs after a timed wait
#: (``PARK_*_TIMEOUT``) gives up — they must restore queue integrity: an
#: aborted waiter leaves no live cell behind (tests/test_hostile.py).
PHASES = ("doorway", "waiting", "entry", "release", "abort")

# Address/value conventions — machine.py contract table.
CS_WORD, CS2_WORD, ELEM_BASE = 4, 5, 8
LOCKEDEMPTY = 1
MAX_LOCK_WORDS = CS_WORD


def _i(x) -> jnp.ndarray:
    return jnp.asarray(x, I32)


def _b(x) -> jnp.ndarray:
    return jnp.asarray(x, bool)


class OpExpr(NamedTuple):
    """One machine op: ``(kind, addr, a, b)``, fields int or traced i32.
    Semantics/result encoding: the contract table in ``core/sim/machine``."""
    kind: Any
    addr: Any
    a: Any = 0
    b: Any = 0


def LOAD(addr) -> OpExpr:
    return OpExpr(M.LOAD, addr)


def STORE(addr, value) -> OpExpr:
    return OpExpr(M.STORE, addr, value)


def XCHG(addr, value) -> OpExpr:
    return OpExpr(M.XCHG, addr, value)


def CAS(addr, expect, new) -> OpExpr:
    """Result is ``old * 2 + ok`` (machine.py contract table)."""
    return OpExpr(M.CAS, addr, expect, new)


def FAA(addr, delta) -> OpExpr:
    return OpExpr(M.FAA, addr, delta)


def SPIN_EQ(addr, value) -> OpExpr:
    return OpExpr(M.SPIN_EQ, addr, value)


def SPIN_NE(addr, value) -> OpExpr:
    return OpExpr(M.SPIN_NE, addr, value)


def PARK_EQ(addr, value) -> OpExpr:
    """Blocking wait with the park/unpark cost model (machine.py table)."""
    return OpExpr(M.PARK_EQ, addr, value)


def PARK_EQ_TIMEOUT(addr, value, timeout) -> OpExpr:
    """Abortable wait: PARK_EQ that gives up after ``timeout`` private
    cycles. Result packs like CAS: ``watched * 2 + ok`` — ok == 0 means
    the wait timed out and the spec's ``abort`` phase runs next."""
    return OpExpr(M.PARK_EQ_TIMEOUT, addr, value, timeout)


def PARK_NE_TIMEOUT(addr, value, timeout) -> OpExpr:
    """Abortable wait for the word to *differ* from ``value`` (timed
    SPIN_NE under the park cost model); result as PARK_EQ_TIMEOUT."""
    return OpExpr(M.PARK_NE_TIMEOUT, addr, value, timeout)


def DELAY(cycles) -> OpExpr:
    return OpExpr(M.DELAY, 0, cycles)


def NOP() -> OpExpr:
    return OpExpr(M.NOP, 0)


class Region:
    """A declared block of words. ``at(i)`` addresses the i-th word
    (accepts traced indices); ``translate(addr, src)`` maps an address in
    region ``src`` to the same offset here (queue locks keep parallel
    per-thread arrays — e.g. MCS's ``next``/``locked``)."""

    def __init__(self, name: str, base: int, size: int, homed: bool):
        self.name, self.base, self.size, self.homed = name, base, size, homed

    def at(self, i):
        return self.base + i

    def translate(self, addr, src: "Region"):
        return addr + (self.base - src.base)

    def __repr__(self):
        kind = "per-thread" if self.homed else "array"
        return f"Region({self.name}@{self.base}+{self.size}, {kind})"


class StepOut(NamedTuple):
    """What a step returns: the next op, the jump target (already resolved
    to a pc by the Ctx), and the arrive/admit instrumentation markers."""
    op: tuple
    pc: Any
    arrive: Any = False
    admit: Any = False


class Step(NamedTuple):
    label: str
    phase: str
    fn: Callable


class SpecError(ValueError):
    pass


class _Regs:
    """Attribute-style symbolic register file: ``c.r.succ = value`` lowers
    to ``regs.at[i].set(value)``; reads return ``regs[i]``. Conditional
    updates are data-flow: ``c.r.x = c.where(cond, a, b)``.

    The file is an ``(R,)`` array in the sim and a tuple of ``R`` scalars
    in the Pallas kernel, where a scalar core holds the registers and an
    indexed array update would lower to a ``scatter`` it cannot run."""

    __slots__ = ("_arr", "_map")

    def __init__(self, arr, regmap):
        object.__setattr__(self, "_arr", arr)
        object.__setattr__(self, "_map", regmap)

    def _idx(self, name):
        if name.startswith("_"):        # protocol probes (__deepcopy__, ...)
            raise AttributeError(name)
        try:
            return self._map[name]
        except KeyError:
            raise SpecError(
                f"unknown register {name!r}; declare it with "
                f"s.regs({name!r}) (have: {sorted(self._map)})") from None

    def __getattr__(self, name):
        return self._arr[self._idx(name)]

    def __setattr__(self, name, value):
        i, arr = self._idx(name), self._arr
        if isinstance(arr, tuple):
            arr = arr[:i] + (_i(value),) + arr[i + 1:]
        else:
            arr = arr.at[i].set(_i(value))
        object.__setattr__(self, "_arr", arr)


class Ctx:
    """Per-step context: ``t`` (thread id), ``T`` (thread count), ``res``
    (previous op's result — encodings per the machine.py contract table),
    ``r`` (symbolic registers), ``rng`` (read-only per-thread xorshift
    word, consumed by the injected NCS handler)."""

    def __init__(self, *, t, T, res, regs, rng, regmap, labels,
                 fallthrough, cs1_op, cs2_pc):
        self.t, self.T, self.res, self.rng = t, T, res, rng
        self.r = _Regs(regs, regmap)
        self._labels = labels
        self._fallthrough = fallthrough
        self._cs1_op, self._cs2_pc = cs1_op, cs2_pc

    # -- jump-target resolution ---------------------------------------------
    def _pc(self, to):
        if to is None:
            if self._fallthrough is None:
                raise SpecError(
                    "last declared step cannot fall through; give an "
                    "explicit to= (e.g. to=NCS)")
            return self._fallthrough
        if isinstance(to, str):
            try:
                return self._labels[to]
            except KeyError:
                raise SpecError(
                    f"unknown label {to!r}; declared steps: "
                    f"{sorted(k for k in self._labels if k != NCS)}"
                ) from None
        return to                       # already a pc (merged / traced)

    # -- step outputs --------------------------------------------------------
    def op(self, op: OpExpr, to=None, arrive=False, admit=False) -> StepOut:
        """Emit ``op`` and jump to ``to`` (default: the next declared
        step; ``NCS`` ends the episode)."""
        return StepOut(op=tuple(op), pc=self._pc(to),
                       arrive=arrive, admit=admit)

    def enter_cs(self, admit=False, arrive=False) -> StepOut:
        """Enter the critical section: emits the first CS-profile op and
        routes through the compiler-injected CS scaffolding into the
        first ``release`` step."""
        return StepOut(op=self._cs1_op, pc=self._cs2_pc,
                       arrive=arrive, admit=admit)

    @staticmethod
    def where(cond, a, b):
        """Data-flow value select (``jnp.where``): how a step picks
        between two values. The CFG recorder (``core/locks/cfg.py``)
        replaces it with a select on concrete witnesses."""
        return jnp.where(cond, a, b)

    def when(self, cond, then: StepOut, other: StepOut, *,
             arrive=None, admit=None) -> StepOut:
        """Data-flow branch: merge two step outputs with ``jnp.where``.
        ``arrive``/``admit`` override the merged markers when given."""
        op = tuple(jnp.where(cond, _i(x), _i(y))
                   for x, y in zip(then.op, other.op))
        pc = jnp.where(cond, _i(then.pc), _i(other.pc))
        arr = (_b(arrive) if arrive is not None
               else jnp.where(cond, _b(then.arrive), _b(other.arrive)))
        adm = (_b(admit) if admit is not None
               else jnp.where(cond, _b(then.admit), _b(other.admit)))
        return StepOut(op=op, pc=pc, arrive=arr, admit=adm)


class LockSpec:
    """Builder handed to a spec author function ``def mylock(s): ...``.

    Declares memory regions (addresses are assigned eagerly, following the
    machine.py layout conventions: lock words 0..3, CS words 4/5, arrays
    from 8), symbolic registers, and the labelled steps of each phase.
    ``core/locks/compile.py`` lowers the collected spec to a ``Program``.
    """

    def __init__(self, name: str, n_threads: int):
        self.name = name
        self.T = n_threads
        self.steps: list[Step] = []
        self.regions: list[Region] = []
        self.words: dict[str, int] = {}
        self.inits: list[tuple] = []
        self.regmap: dict[str, int] = {}
        self.expectations: dict = {}
        self._next_word = 0
        self._array_top = ELEM_BASE

    # -- declared property expectations --------------------------------------
    def expect(self, **props) -> None:
        """Declare the paper-table properties this lock claims, checked
        *two-sided* against the static analyzer (``core/locks/cfg.py``)
        at compile time — a ticket lock must say ``spin="shared"``, and
        a stale declaration is as much an error as a false claim.

        Keys: ``doorway`` ("constant" / "none" / "unbounded"),
        ``release`` ("wait_free" / "waits" / "unbounded"), ``spin``
        ("own" / "cell" / "shared"), ``footprint`` (sequestered words
        per thread), ``bypass`` (admission-bypass bound, ``None`` for
        unbounded — certified by the small-scope model checker in
        ``core/locks/verify.py``, not at compile time). Undeclared
        specs get only the safety floor: loop-free doorway/release."""
        from repro.core.locks.cfg import validate_expectations
        merged = {**self.expectations, **props}
        validate_expectations(merged, self.name)
        self.expectations = merged

    # -- memory declarations -------------------------------------------------
    def word(self, name: str, init: int | None = None) -> int:
        """Declare a lock word (homed on node 0); returns its address."""
        if self._next_word >= MAX_LOCK_WORDS:
            raise SpecError(f"{self.name}: more than {MAX_LOCK_WORDS} lock "
                            "words (addresses 0..3 are reserved for them)")
        addr = self._next_word
        self._next_word += 1
        self.words[name] = addr
        if init is not None:
            self.init(addr, init)
        return addr

    def array(self, name: str, size: int, homed: bool = False,
              init: dict | None = None) -> Region:
        """Declare a block of ``size`` words above ``ELEM_BASE``.
        ``homed=True`` homes word ``base + i`` on thread ``i``'s NUMA node
        (only meaningful when ``size >= T``)."""
        r = Region(name, self._array_top, size, homed)
        self._array_top += size
        self.regions.append(r)
        for off, v in (init or {}).items():
            self.init(r.base + off, v)
        return r

    def per_thread(self, name: str, init: dict | None = None) -> Region:
        """A wait-element array with one word per thread, homed on the
        owning thread's node (the paper's sequestered-line layout)."""
        return self.array(name, self.T, homed=True, init=init)

    def init(self, addr: int, value: int) -> None:
        """Set an initial memory value (e.g. CLH's tail -> dummy node)."""
        self.inits.append((int(addr), int(value)))

    # -- registers -----------------------------------------------------------
    def regs(self, *names: str) -> tuple:
        """Declare symbolic registers, readable/writable as ``c.r.<name>``;
        returns their indices."""
        out = []
        for n in names:
            if n in self.regmap:
                raise SpecError(f"{self.name}: register {n!r} redeclared")
            self.regmap[n] = len(self.regmap)
            out.append(self.regmap[n])
        return tuple(out)

    # -- steps ---------------------------------------------------------------
    def step(self, phase: str, label: str | None = None):
        """Decorator registering a step in ``phase``. The label (default:
        the function name) is the jump target other steps use."""
        if phase not in PHASES:
            raise SpecError(f"{self.name}: unknown phase {phase!r} "
                            f"(must be one of {PHASES})")

        def deco(fn):
            lab = label or fn.__name__
            if lab == NCS or any(s.label == lab for s in self.steps):
                raise SpecError(f"{self.name}: duplicate/reserved step "
                                f"label {lab!r}")
            self.steps.append(Step(lab, phase, fn))
            return fn
        return deco

    # -- layout summary ------------------------------------------------------
    @property
    def n_mem(self) -> int:
        return self._array_top

    def home(self) -> tuple:
        """Per-word NUMA home thread (-1 => node 0), from the region
        declarations — replaces per-lock hand-built home tables."""
        home = [-1] * self.n_mem
        for r in self.regions:
            if r.homed:
                for t in range(min(r.size, self.T)):
                    home[r.base + t] = t
        return tuple(home)

    def validate(self) -> None:
        if not self.steps:
            raise SpecError(f"{self.name}: spec declares no steps")
        if not any(s.phase == "release" for s in self.steps):
            raise SpecError(f"{self.name}: spec has no release phase")
        if len(self.regmap) > 8:
            raise SpecError(f"{self.name}: more than 8 registers")

    def phase_summary(self) -> dict:
        out: dict = {p: [] for p in PHASES}
        for s in self.steps:
            out[s.phase].append(s.label)
        return out
