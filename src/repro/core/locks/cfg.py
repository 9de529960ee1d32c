"""Control-flow-graph lifting for ``LockSpec`` phase specs.

The paper's headline claims are *structural* — constant-time doorway and
release, local spinning on a single per-thread waiting element, one wait
element per thread — and the ``LockSpec`` DSL is exactly the IR to decide
them on: steps are labelled, phases are declared, memory is declared as
:class:`~repro.core.locks.dsl.Region` objects with homing, and branches
are explicit ``c.when(...)`` merges. This module recovers a per-phase
control-flow graph from a spec *without running the machine*, by
executing every step function once against a recording context
(:class:`SymCtx`) that

* hands out :class:`SymVal` symbols for ``c.t`` / ``c.res`` / register
  reads, so operand *provenance* survives the step body's arithmetic
  (``elem.at(c.t)`` classifies as the own sequestered cell, ``c.res`` as
  a pointer chase, ``cells.at(c.res % T)`` as a dynamic cell of the
  ``cells`` region);
* records **both** arms of every ``c.when`` instead of jnp-merging them
  (the DSL builds both ``StepOut``s eagerly — data-flow branching — so
  one execution per step surfaces every edge);
* degrades gracefully at value selects: ``c.where`` on symbols selects
  between their concrete *witness* values with ``jnp.where``, so the
  result is opaque; the whole extraction runs twice, with thread-id
  witnesses 0 and 1, and joining the two runs re-classifies opaque
  results (an address that shifts by exactly 1 with ``t`` is a
  thread-indexed cell; one that doesn't move is a fixed word).

On top of the CFG, :func:`analyze` computes the structural facts the
verifier (``core/locks/verify.py``) and the compile-time gate consume:

* **doorway** — is the pre-``arrive`` path loop-free, how many ops does
  the longest path complete before the arrive marker fires, and does it
  ever block;
* **release** — loop-free bound and whether any path waits (MCS's
  late-successor ``SPIN_NE`` vs the reciprocating lock's wait-free
  store/CAS tail);
* **spin locality** — every ``SPIN_*``/``PARK_*`` target classified
  ``own`` (homed region at index ``t``), ``cell`` (per-waiter dynamic or
  pointer-chased cell — single-spinner status is certified by the
  small-scope model checker), or ``shared`` (a lock word, or a
  waiting/entry loop that hammers one);
* **waiting footprint** — how many distinct per-thread sequestered words
  the spec ever touches (the paper's "one wait element per thread").

:func:`check_spec` compares the facts against the spec's *declared
expectations* (``s.expect(...)`` in the DSL): undeclared specs only get
the safety floor (doorway/release loop-freedom), declared ones are
checked two-sided — claiming less than is proven is as much an error as
claiming more, so declarations can't go stale.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import NamedTuple

from repro.core.locks.dsl import (
    MAX_LOCK_WORDS, NCS, LockSpec, OpExpr, SpecError, Step,
)
from repro.core.sim import machine as M

__all__ = ["SymVal", "SpecCFG", "Edge", "OpFacts", "StructuralFacts",
           "build_cfg", "analyze", "check_spec", "EXPECT_KEYS",
           "BLOCKING_KINDS", "TIMED_KINDS", "KIND_NAMES"]

BLOCKING_KINDS = (M.SPIN_EQ, M.SPIN_NE, M.PARK_EQ,
                  M.PARK_EQ_TIMEOUT, M.PARK_NE_TIMEOUT)
TIMED_KINDS = (M.PARK_EQ_TIMEOUT, M.PARK_NE_TIMEOUT)
KIND_NAMES = {M.NOP: "NOP", M.LOAD: "LOAD", M.STORE: "STORE",
              M.XCHG: "XCHG", M.CAS: "CAS", M.FAA: "FAA",
              M.SPIN_EQ: "SPIN_EQ", M.SPIN_NE: "SPIN_NE",
              M.DELAY: "DELAY", M.PARK_EQ: "PARK_EQ",
              M.PARK_EQ_TIMEOUT: "PARK_EQ_TIMEOUT",
              M.PARK_NE_TIMEOUT: "PARK_NE_TIMEOUT"}

#: Pseudo-targets: the injected CS scaffolding and the episode end.
CS, END = "@cs", NCS


# ---------------------------------------------------------------------------
# Symbolic values
# ---------------------------------------------------------------------------
class SymVal:
    """A symbolic int32: ``const + tco * t`` when ``roots`` is empty
    (exact affine in the thread id), otherwise an opaque combination of
    the provenance roots in ``roots`` ("res", "reg:<name>", "t") with
    ``const`` kept as an additive *base hint* (so ``region.base + f(x)``
    still classifies into the region). ``wit`` is the concrete witness
    that ``SymCtx.where`` selects on. jnp never sees a ``SymVal``."""

    __slots__ = ("const", "tco", "roots", "wit")

    def __init__(self, const=0, tco=0, roots=frozenset(), wit=0):
        self.const, self.tco = int(const), int(tco)
        self.roots, self.wit = frozenset(roots), wit

    # -- provenance helpers --------------------------------------------------
    def _all_roots(self):
        return self.roots | ({"t"} if self.tco else frozenset())

    @staticmethod
    def _of(x):
        if isinstance(x, SymVal):
            return x
        if isinstance(x, bool) or not isinstance(x, int):
            return None                     # arrays / floats: opaque
        return SymVal(const=x, wit=x)

    def _wit_of(self, x):
        return x.wit if isinstance(x, SymVal) else x

    # -- affine-preserving arithmetic ----------------------------------------
    def __add__(self, o):
        so = self._of(o)
        if so is None:
            return _opaque_binop(self, o, "+")
        return SymVal(self.const + so.const, self.tco + so.tco,
                      self.roots | so.roots, _wit(self.wit, "+", so.wit))

    __radd__ = __add__

    def __sub__(self, o):
        so = self._of(o)
        if so is None:
            return _opaque_binop(self, o, "-")
        return SymVal(self.const - so.const, self.tco - so.tco,
                      self.roots | so.roots, _wit(self.wit, "-", so.wit))

    def __rsub__(self, o):
        so = self._of(o)
        if so is None:
            return _opaque_binop(o, self, "-")
        return SymVal(so.const - self.const, so.tco - self.tco,
                      self.roots | so.roots, _wit(so.wit, "-", self.wit))

    def __mul__(self, o):
        so = self._of(o)
        if (so is not None and not so.roots and so.tco == 0
                and not self.roots and self.tco == 0):
            return SymVal(self.const * so.const, 0, frozenset(),
                          _wit(self.wit, "*", so.wit))
        return _mix(self, o, "*")

    __rmul__ = __mul__

    # -- structure-losing ops: provenance union, base hint reset -------------
    def __mod__(self, o):
        return _mix(self, o, "%")

    def __rmod__(self, o):
        return _mix(o, self, "%")

    def __floordiv__(self, o):
        return _mix(self, o, "//")

    def __rfloordiv__(self, o):
        return _mix(o, self, "//")

    def __neg__(self):
        return SymVal(-self.const, -self.tco, self.roots,
                      _wit(0, "-", self.wit))

    # -- comparisons: symbolic booleans --------------------------------------
    def _cmp(self, o, opname):
        return _mix(self, o, opname)

    def __eq__(self, o):                    # noqa: they are symbolic
        return self._cmp(o, "==")

    def __ne__(self, o):
        return self._cmp(o, "!=")

    def __lt__(self, o):
        return self._cmp(o, "<")

    def __le__(self, o):
        return self._cmp(o, "<=")

    def __gt__(self, o):
        return self._cmp(o, ">")

    def __ge__(self, o):
        return self._cmp(o, ">=")

    def __hash__(self):                     # __eq__ is symbolic
        return id(self)

    def __bool__(self):
        raise SpecError(
            "step control flow must be data-flow (`c.when(...)`), not a "
            "Python `if` on a traced value")

    def __repr__(self):
        if not self.roots:
            return (f"Sym({self.const}"
                    + (f"+{self.tco}*t" if self.tco else "") + ")")
        return f"Sym({self.const}+f({','.join(sorted(self.roots))}))"


def _wit(a, opname, b):
    try:
        return {"+": lambda: a + b, "-": lambda: a - b,
                "*": lambda: a * b, "%": lambda: a % b if b else 0,
                "//": lambda: a // b if b else 0,
                "==": lambda: a == b, "!=": lambda: a != b,
                "<": lambda: a < b, "<=": lambda: a <= b,
                ">": lambda: a > b, ">=": lambda: a >= b}[opname]()
    except TypeError:                       # witness already an array
        return 0


def _roots_of(x):
    if isinstance(x, SymVal):
        return x._all_roots()
    if isinstance(x, int) and not isinstance(x, bool):
        return frozenset()
    return frozenset({"opaque"})


def _wit_any(x):
    return x.wit if isinstance(x, SymVal) else (
        x if isinstance(x, int) else 0)


def _mix(a, b, opname):
    """Structure-losing combination: keep provenance, drop the affine
    form and the base hint (a `%`/`*`/comparison invalidates both)."""
    return SymVal(0, 0, _roots_of(a) | _roots_of(b),
                  _wit(_wit_any(a), opname, _wit_any(b)))


def _opaque_binop(a, b, opname):
    """+/- with a non-int partner (array): keep the int side's base."""
    sa = SymVal._of(a)
    base = sa.const if isinstance(sa, SymVal) else 0
    return SymVal(base, 0, _roots_of(a) | _roots_of(b) | {"opaque"},
                  _wit(_wit_any(a), opname, _wit_any(b)))


# ---------------------------------------------------------------------------
# Recording context (the SymCtx mirror of dsl.Ctx)
# ---------------------------------------------------------------------------
class _SymOut:
    """Either a leaf (one emitted op + target) or a branch of two."""

    def __init__(self, op=None, to=None, arrive=False, admit=False,
                 branches=None):
        self.op, self.to = op, to
        self.arrive, self.admit = arrive, admit
        self.branches = branches

    def leaves(self):
        if self.branches is None:
            yield self
            return
        for br in self.branches:
            for leaf in br.leaves():
                yield _SymOut(op=leaf.op, to=leaf.to,
                              arrive=(self.arrive if self.arrive is not None
                                      else leaf.arrive),
                              admit=(self.admit if self.admit is not None
                                     else leaf.admit))


class _SymRegs:
    """Register file for the recorder: reads return fresh symbols (one
    per declared register — cross-step flow is deliberately cut, each
    step is analyzed in isolation), reads-after-write within one step
    return the written value."""

    __slots__ = ("_vals", "_map")

    def __init__(self, regmap):
        object.__setattr__(self, "_vals", {})
        object.__setattr__(self, "_map", regmap)

    def __getattr__(self, name):
        if name.startswith("_"):
            raise AttributeError(name)
        if name not in self._map:
            raise SpecError(
                f"unknown register {name!r}; declare it with "
                f"s.regs({name!r}) (have: {sorted(self._map)})")
        return self._vals.get(
            name, SymVal(roots=frozenset({f"reg:{name}"}), wit=0))

    def __setattr__(self, name, value):
        if name not in self._map:
            raise SpecError(
                f"unknown register {name!r}; declare it with "
                f"s.regs({name!r}) (have: {sorted(self._map)})")
        self._vals[name] = value


class SymCtx:
    """Recording mirror of :class:`~repro.core.locks.dsl.Ctx`: same
    surface (``t``/``T``/``res``/``r``/``rng``, ``op``/``when``/
    ``enter_cs``), but ops are recorded instead of lowered and *both*
    ``when`` arms are kept."""

    def __init__(self, spec: LockSpec, step: Step, fallthrough, t_wit: int):
        self.t = SymVal(tco=1, wit=t_wit, roots=frozenset())
        self.T = spec.T
        self.res = SymVal(roots=frozenset({"res"}), wit=0)
        self.rng = SymVal(roots=frozenset({"rng"}), wit=1)
        self.r = _SymRegs(spec.regmap)
        self._spec, self._step = spec, step
        self._labels = {s.label for s in spec.steps} | {NCS}
        self._fallthrough = fallthrough

    def _target(self, to):
        if to is None:
            if self._fallthrough is None:
                raise SpecError(
                    "last declared step cannot fall through; give an "
                    "explicit to= (e.g. to=NCS)")
            return self._fallthrough
        if isinstance(to, str):
            if to not in self._labels:
                raise SpecError(
                    f"unknown label {to!r}; declared steps: "
                    f"{sorted(k for k in self._labels if k != NCS)}")
            return to
        return "@dynamic"                   # raw/traced pc: CFG-opaque

    def op(self, op: OpExpr, to=None, arrive=False, admit=False):
        return _SymOut(op=op, to=self._target(to),
                       arrive=bool(arrive), admit=bool(admit))

    def enter_cs(self, admit=False, arrive=False):
        return _SymOut(op=None, to=CS, arrive=bool(arrive),
                       admit=bool(admit))

    @staticmethod
    def where(cond, a, b):
        """Select on the concrete witnesses: an opaque array result."""
        import jax.numpy as jnp
        return jnp.where(*(x.wit if isinstance(x, SymVal) else x
                           for x in (cond, a, b)))

    def when(self, cond, then, other, *, arrive=None, admit=None):
        del cond                            # both arms recorded
        return _SymOut(branches=(then, other),
                       arrive=None if arrive is None else bool(arrive),
                       admit=None if admit is None else bool(admit))


# ---------------------------------------------------------------------------
# Operand classification and the CFG proper
# ---------------------------------------------------------------------------
class OperandClass(NamedTuple):
    """Where an op operand points: ``kind`` is ``word`` (a fixed lock /
    CS word), ``own`` (homed region at index exactly ``t``), ``cell``
    (region cell at a dynamic index, or the neighbour's cell), ``chase``
    (pointer value from ``res``/a register), or ``value`` (not an
    address-shaped operand)."""
    kind: str
    detail: str


def _region_of(spec: LockSpec, addr: int):
    for r in spec.regions:
        if r.base <= addr < r.base + r.size:
            return r
    return None


def _classify(spec: LockSpec, v0, v1) -> OperandClass:
    """Join the two probe runs (t witness 0 / 1) into one operand class.
    ``v0``/``v1`` are ints, SymVals, or opaque arrays."""
    def as_pair(v):
        if isinstance(v, SymVal):
            return v
        if isinstance(v, int) and not isinstance(v, bool):
            return SymVal(const=v, wit=v)
        return None                         # opaque array

    s0, s1 = as_pair(v0), as_pair(v1)
    if s0 is not None and not s0.roots:     # exact affine const + tco*t
        base, tco = s0.const, s0.tco
        if tco == 0:
            if base < MAX_LOCK_WORDS:
                name = next((n for n, a in spec.words.items() if a == base),
                            str(base))
                return OperandClass("word", name)
            r = _region_of(spec, base)
            if r is not None:
                return OperandClass("cell", f"{r.name}[{base - r.base}]")
            return OperandClass("word", str(base))
        r = _region_of(spec, base)
        if tco == 1 and r is not None and r.base == base and r.homed:
            return OperandClass("own", f"{r.name}[t]")
        if r is not None:
            return OperandClass("cell", f"{r.name}[{base - r.base}+{tco}t]")
        return OperandClass("cell", f"{base}+{tco}t")
    if s0 is not None:                      # provenance-tracked, non-affine
        r = _region_of(spec, s0.const)
        if r is not None and s0.const == r.base:
            return OperandClass("cell", f"{r.name}[dyn]")
        roots = ",".join(sorted(s0.roots)) or "dyn"
        return OperandClass("chase", roots)
    # fully opaque (jnp degradation): join the concrete witnesses
    w0 = int(getattr(v0, "item", lambda: v0)())
    w1 = int(getattr(v1, "item", lambda: v1)())
    if w0 == w1:
        return _classify(spec, w0, w0)
    if w1 - w0 == 1:
        r = _region_of(spec, w0)
        if r is not None and r.base == w0 and r.homed:
            return OperandClass("own", f"{r.name}[t]")
        if r is not None:
            return OperandClass("cell", f"{r.name}[{w0 - r.base}+t]")
    return OperandClass("chase", "opaque")


class OpFacts(NamedTuple):
    kind: int
    addr: OperandClass
    value: OperandClass | None      # classified stored value (publishes)
    blocking: bool
    timed: bool

    def describe(self):
        k = KIND_NAMES.get(self.kind, str(self.kind))
        return f"{k}({self.addr.detail})"


class Edge(NamedTuple):
    src: str
    dst: str                        # a step label, ``@cs`` or ``ncs``
    op: OpFacts | None              # None for ``enter_cs`` edges
    arrive: bool
    admit: bool


@dataclass
class SpecCFG:
    spec: LockSpec
    edges: list = field(default_factory=list)
    phase: dict = field(default_factory=dict)       # label -> phase
    entry: str = ""

    def out(self, label: str):
        return [e for e in self.edges if e.src == label]

    def phase_nodes(self, *phases: str):
        return [s.label for s in self.spec.steps if s.phase in phases]

    def subgraph_cycle(self, nodes) -> list | None:
        """Return one cycle (as a label path) within ``nodes``, or None."""
        nodeset = set(nodes)
        adj = {n: sorted({e.dst for e in self.out(n) if e.dst in nodeset})
               for n in nodes}
        WHITE, GREY, BLACK = 0, 1, 2
        color = dict.fromkeys(nodes, WHITE)
        stack: list = []

        def dfs(n):
            color[n] = GREY
            stack.append(n)
            for m in adj[n]:
                if color[m] == GREY:
                    return stack[stack.index(m):] + [m]
                if color[m] == WHITE:
                    cyc = dfs(m)
                    if cyc:
                        return cyc
            stack.pop()
            color[n] = BLACK
            return None

        for n in nodes:
            if color[n] == WHITE:
                cyc = dfs(n)
                if cyc:
                    return cyc
        return None

    def longest_path(self, nodes, sources) -> int:
        """Longest node-count path inside the (acyclic) ``nodes``
        subgraph starting from ``sources``."""
        nodeset = set(nodes)
        memo: dict = {}

        def depth(n):
            if n in memo:
                return memo[n]
            memo[n] = 1                      # cycle guard (caller checked)
            best = 1
            for e in self.out(n):
                if e.dst in nodeset:
                    best = max(best, 1 + depth(e.dst))
            memo[n] = best
            return best

        return max((depth(s) for s in sources if s in nodeset), default=0)


def build_cfg(author_or_spec, n_threads: int = 4,
              name: str | None = None) -> SpecCFG:
    """Lift a spec (or author function) to its control-flow graph by
    running every step once per thread-witness against :class:`SymCtx`."""
    from repro.core.locks.compile import build_spec
    spec = (author_or_spec if isinstance(author_or_spec, LockSpec)
            else build_spec(author_or_spec, n_threads, name))

    def one_run(t_wit: int):
        out = []
        for i, st in enumerate(spec.steps):
            fallthrough = (spec.steps[i + 1].label
                           if i + 1 < len(spec.steps) else None)
            c = SymCtx(spec, st, fallthrough, t_wit)
            try:
                res = st.fn(c)
            except SpecError as e:
                raise SpecError(f"{spec.name}.{st.label}: {e}") from e
            if res is None:
                raise SpecError(
                    f"{spec.name}.{st.label}: step returned None (must "
                    "return c.op/c.when/c.enter_cs)")
            out.append((st, list(res.leaves())))
        return out

    run0, run1 = one_run(0), one_run(1)
    cfg = SpecCFG(spec=spec, entry=spec.steps[0].label,
                  phase={s.label: s.phase for s in spec.steps})
    for (st, leaves0), (_, leaves1) in zip(run0, run1):
        if len(leaves0) != len(leaves1):
            raise SpecError(f"{spec.name}.{st.label}: control flow "
                            "depends on the thread id witness")
        for l0, l1 in zip(leaves0, leaves1):
            if l0.op is None:               # enter_cs
                cfg.edges.append(Edge(st.label, CS, None,
                                      bool(l0.arrive), bool(l0.admit)))
                continue
            kind = int(l0.op.kind)
            addr = _classify(spec, l0.op.addr, l1.op.addr)
            value = None
            if kind in (M.STORE, M.XCHG):
                value = _classify(spec, l0.op.a, l1.op.a)
            elif kind == M.CAS:
                value = _classify(spec, l0.op.b, l1.op.b)
            facts = OpFacts(kind=kind, addr=addr, value=value,
                            blocking=kind in BLOCKING_KINDS,
                            timed=kind in TIMED_KINDS)
            cfg.edges.append(Edge(st.label, l0.to, facts,
                                  bool(l0.arrive), bool(l0.admit)))
    return cfg


# ---------------------------------------------------------------------------
# Structural analyses
# ---------------------------------------------------------------------------
@dataclass
class PhaseFacts:
    present: bool
    loop: list | None           # one offending cycle (labels), if any
    bound: int | None           # max ops completed on any path (if a DAG)
    waits: list                 # step labels emitting blocking ops

    @property
    def loop_free(self) -> bool:
        return self.loop is None

    def grade(self) -> str:
        if not self.present:
            return "none"
        if not self.loop_free:
            return "unbounded"
        return "waits" if self.waits else "constant"


@dataclass
class StructuralFacts:
    """Everything the gate / matrix needs, decided from the CFG alone."""
    cfg: SpecCFG
    doorway: PhaseFacts
    release: PhaseFacts
    spin_level: str             # "own" | "cell" | "shared" | "none"
    spin_ops: list              # (step label, OpFacts)
    spin_shared_loop: list | None   # loop hammering a lock word, if any
    footprint: int
    footprint_regions: list

    @property
    def doorway_grade(self):
        # the op emitted *with* the arrive marker runs after the marker
        # fires, so a blocking op there (ticket's SPIN_EQ) is the first
        # waiting-phase op, not a doorway cost
        return self.doorway.grade()

    @property
    def release_grade(self):
        g = self.release.grade()
        return {"constant": "wait_free"}.get(g, g)


def analyze(author_or_spec, n_threads: int = 4,
            name: str | None = None) -> StructuralFacts:
    cfg = (author_or_spec if isinstance(author_or_spec, SpecCFG)
           else build_cfg(author_or_spec, n_threads, name))
    spec = cfg.spec

    # --- doorway: the pre-arrive path --------------------------------------
    dnodes = cfg.phase_nodes("doorway")
    dloop = cfg.subgraph_cycle(dnodes) if dnodes else None
    dbound = None
    dwaits = []
    if dnodes and dloop is None:
        entry = [cfg.entry] if cfg.phase.get(cfg.entry) == "doorway" \
            else dnodes[:1]
        # ops completed before `arrive` = doorway steps run minus the
        # arriving one (its op executes after the marker is recorded)
        dbound = max(cfg.longest_path(dnodes, entry) - 1, 0)
        for n in dnodes:
            for e in cfg.out(n):
                if (e.op is not None and e.op.blocking and not e.arrive
                        and e.dst in set(dnodes)):
                    dwaits.append(n)
    doorway = PhaseFacts(bool(dnodes), dloop, dbound, sorted(set(dwaits)))

    # --- release ------------------------------------------------------------
    rnodes = cfg.phase_nodes("release")
    rloop = cfg.subgraph_cycle(rnodes)
    rbound = cfg.longest_path(rnodes, rnodes) if rloop is None else None
    rwaits = sorted({n for n in rnodes for e in cfg.out(n)
                     if e.op is not None and e.op.blocking})
    release = PhaseFacts(bool(rnodes), rloop, rbound, rwaits)

    # --- spin locality ------------------------------------------------------
    spin_ops = [(e.src, e.op) for e in cfg.edges
                if e.op is not None and e.op.blocking]
    levels = set()
    for _, op in spin_ops:
        levels.add({"own": "own", "cell": "cell", "chase": "cell",
                    "word": "shared"}[op.addr.kind])
    # an active-spin loop (waiting/entry cycle re-issuing ops on a lock
    # word) is global spinning even without a SPIN op on the word itself
    wenodes = cfg.phase_nodes("waiting", "entry")
    shared_loop = None
    cyc = cfg.subgraph_cycle(wenodes)
    if cyc is not None:
        cycset = set(cyc)
        for n in cyc:
            for e in cfg.out(n):
                if (e.dst in cycset and e.op is not None
                        and e.op.addr.kind == "word"
                        and e.op.kind not in (M.DELAY, M.NOP)):
                    shared_loop = cyc
    if shared_loop is not None:
        levels.add("shared")
    order = ("shared", "cell", "own")
    spin_level = next((x for x in order if x in levels), "none")

    # --- waiting footprint: distinct sequestered per-thread words -----------
    regions = set()
    for e in cfg.edges:
        if e.op is None:
            continue
        for cls in (e.op.addr, e.op.value):
            if cls is not None and cls.kind == "own":
                regions.add(cls.detail.split("[")[0])
    facts = StructuralFacts(
        cfg=cfg, doorway=doorway, release=release, spin_level=spin_level,
        spin_ops=spin_ops, spin_shared_loop=shared_loop,
        footprint=len(regions), footprint_regions=sorted(regions))
    return facts


# ---------------------------------------------------------------------------
# Declared expectations vs proven facts (the compile-time gate)
# ---------------------------------------------------------------------------
EXPECT_KEYS = ("doorway", "release", "spin", "footprint", "bypass")
_DOORWAY_VALUES = ("constant", "none", "unbounded")
_RELEASE_VALUES = ("wait_free", "waits", "unbounded")
_SPIN_VALUES = ("own", "cell", "shared")


def validate_expectations(exp: dict, name: str = "spec") -> None:
    for k in exp:
        if k not in EXPECT_KEYS:
            raise SpecError(f"{name}: unknown expectation {k!r} "
                            f"(must be one of {EXPECT_KEYS})")
    if "doorway" in exp and exp["doorway"] not in _DOORWAY_VALUES:
        raise SpecError(f"{name}: doorway= must be one of "
                        f"{_DOORWAY_VALUES}, got {exp['doorway']!r}")
    if "release" in exp and exp["release"] not in _RELEASE_VALUES:
        raise SpecError(f"{name}: release= must be one of "
                        f"{_RELEASE_VALUES}, got {exp['release']!r}")
    if "spin" in exp and exp["spin"] not in _SPIN_VALUES:
        raise SpecError(f"{name}: spin= must be one of "
                        f"{_SPIN_VALUES}, got {exp['spin']!r}")
    if "footprint" in exp and not isinstance(exp["footprint"], int):
        raise SpecError(f"{name}: footprint= must be an int")
    if "bypass" in exp and not (exp["bypass"] is None
                                or isinstance(exp["bypass"], int)):
        raise SpecError(f"{name}: bypass= must be an int or None")


def check_spec(facts: StructuralFacts,
               expectations: dict | None = None) -> list:
    """Compare structural facts against the spec's declared expectations.

    Returns a list of violation strings (each with phase/label
    provenance). Undeclared specs get only the safety floor: a loop in
    the doorway or release phase is an error unless explicitly declared
    ``doorway="unbounded"`` / ``release="unbounded"``. Declared keys are
    checked *two-sided* — a declaration weaker than what is proven is a
    stale declaration, also an error."""
    spec = facts.cfg.spec
    exp = dict(expectations if expectations is not None
               else getattr(spec, "expectations", {}) or {})
    validate_expectations(exp, spec.name)
    out = []

    # safety floor: constant-time doorway/release unless opted out
    if not facts.doorway.loop_free and exp.get("doorway") != "unbounded":
        out.append(
            "doorway phase has a loop ({}) — the paper's constant-time "
            "doorway is the default contract; declare "
            "s.expect(doorway=\"unbounded\") to opt out".format(
                " -> ".join(facts.doorway.loop)))
    if not facts.release.loop_free and exp.get("release") != "unbounded":
        out.append(
            "release phase has a loop ({}) — declare "
            "s.expect(release=\"unbounded\") to opt out".format(
                " -> ".join(facts.release.loop)))

    # two-sided declaration checks
    if "doorway" in exp and exp["doorway"] != facts.doorway_grade:
        out.append(
            f"declared doorway={exp['doorway']!r} but analysis proves "
            f"{facts.doorway_grade!r}"
            + (f" (loop {' -> '.join(facts.doorway.loop)})"
               if facts.doorway.loop else ""))
    if "release" in exp and exp["release"] != facts.release_grade:
        detail = ""
        if facts.release.loop:
            detail = f" (loop {' -> '.join(facts.release.loop)})"
        elif facts.release.waits:
            detail = f" (waits at {', '.join(facts.release.waits)})"
        out.append(
            f"declared release={exp['release']!r} but analysis proves "
            f"{facts.release_grade!r}{detail}")
    if "spin" in exp and facts.spin_level != "none" \
            and exp["spin"] != facts.spin_level:
        culprits = [f"{lab}: {op.describe()}" for lab, op in facts.spin_ops
                    if {"own": "own", "cell": "cell", "chase": "cell",
                        "word": "shared"}[op.addr.kind] == facts.spin_level]
        if facts.spin_shared_loop and facts.spin_level == "shared":
            culprits.append("active-spin loop "
                            + " -> ".join(facts.spin_shared_loop))
        out.append(
            f"declared spin={exp['spin']!r} but analysis proves "
            f"{facts.spin_level!r} ({'; '.join(culprits)})")
    if "footprint" in exp and exp["footprint"] != facts.footprint:
        out.append(
            f"declared footprint={exp['footprint']} but the spec touches "
            f"{facts.footprint} sequestered per-thread word(s) "
            f"({', '.join(facts.footprint_regions) or 'none'})")
    return out


def gate(author_or_spec, n_threads: int = 4,
         name: str | None = None) -> StructuralFacts:
    """The eager compile-time pass: analyze and raise ``SpecError`` on
    the first violation, with the spec name as provenance prefix."""
    facts = analyze(author_or_spec, n_threads, name)
    violations = check_spec(facts)
    if violations:
        raise SpecError(f"{facts.cfg.spec.name}: " + violations[0])
    return facts
