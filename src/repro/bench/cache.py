"""Content-addressed experiment cache for the sweep engine.

A grid cell — one (lock program, machine, scheduler, workload, seed
ensemble) simulation — is a pure function of its inputs, so its
``BenchResult`` can be cached on a canonical hash of those inputs and
replayed on later runs without touching XLA. This is what lets
``repro.bench run --suite paper`` skip every unchanged experiment on a
warm re-run (``--no-cache`` forces regeneration; ``BENCH_trend.json``
reports the hit rate).

The key has two layers:

* ``program_fingerprint(prog)`` — the *semantic* identity of a compiled
  ``Program``: per-handler jaxprs (traced on the machine's abstract
  state probe) plus closed-over constant bytes, the memory layout
  (``n_mem``/``home``/``init_mem``), register count, and the jax
  version. Step *labels* resolve to declaration-order program counters
  at compile time and docstrings never reach the jaxpr, so renaming a
  label or editing prose does NOT change the fingerprint — while any
  semantic edit (a different delta, a reordered step, a new memory
  word) does. Jaxprs are hashed *structurally* (primitive names,
  dataflow via first-encounter variable numbering, params with nested
  jaxprs expanded recursively) rather than via ``str(jaxpr)``: the
  pretty-printer collapses a repeated sub-jaxpr to a by-name reference
  (``jaxpr=_where``) whenever jax's internal trace caches happen to
  share the object, so the printed form depends on process history —
  the structural walk does not.
* ``cell_key(...)`` — the fingerprint plus everything else the
  simulation consumes: thread count, workload semantics (``ncs_max``,
  ``cs_mode``, ``n_steps`` — the display ``label`` is excluded), the
  raw bytes of the lowered topology matrices (``LoweredCost``) and
  scheduler scalars (``LoweredSched``), and the seed tuple. Topology
  and scheduler *names* are likewise excluded: two presets lowering to
  the same matrices are the same machine.

Sharding is deliberately NOT part of the key: sharded and unsharded
grids are bit-identical (``tests/test_sweep_cache.py`` pins this), so a
cell computed on a 4-device mesh may be served to a single-device run.

``CACHE_KEY_VERSION`` is the suite-version component of the key — bump
it whenever key semantics or the result encoding change, and every old
entry silently misses.

Storage is one JSON file per cell under ``<root>/<key[:2]>/<key>.json``
(root defaults to ``.bench_cache/``, overridable via ``--cache-dir`` or
``$REPRO_BENCH_CACHE_DIR``; ``$REPRO_BENCH_NO_CACHE=1`` disables the
cache entirely). ``BenchResult`` round-trips through
``result_to_doc``/``result_from_doc`` with explicit dtypes on the
ndarray fields, so a cache hit is bit-identical to the fresh run that
stored it.
"""
from __future__ import annotations

import contextlib
import hashlib
import json
import os
import re
import tempfile
import weakref
from dataclasses import dataclass, field

import numpy as np

from repro.core.sim.api import BenchResult

__all__ = ["CACHE_KEY_VERSION", "program_fingerprint", "cell_key",
           "result_to_doc", "result_from_doc", "ExperimentCache",
           "CacheStats", "get_cache", "configure"]

#: Suite-version component of every key; bump on key/encoding changes.
CACHE_KEY_VERSION = 1

DEFAULT_ROOT = ".bench_cache"


# --- hashing ------------------------------------------------------------------

def _feed(h, *parts) -> None:
    for p in parts:
        h.update(str(p).encode())
        h.update(b"\x00")


def _feed_array(h, a) -> None:
    a = np.asarray(a)
    _feed(h, a.dtype.str, a.shape)
    h.update(a.tobytes())
    h.update(b"\x00")


_ADDR = re.compile(r"0x[0-9a-fA-F]+")


def _feed_jaxpr(h, jaxpr, ids) -> None:
    """Structural, sharing-insensitive jaxpr hash. ``str(jaxpr)`` is NOT
    stable across process history: the pretty-printer prints a repeated
    sub-jaxpr as ``jaxpr=<name>`` when jax's weakref trace caches make
    the two call sites share one object, and inline otherwise. Walking
    the structure and always recursing into nested jaxprs removes that
    dependence. ``ids`` numbers variables in first-encounter order so
    dataflow (not object identity) is what's hashed."""
    from jax.extend.core import Literal

    def ref(v):
        if isinstance(v, Literal):
            _feed(h, "lit", v.aval)
            _feed_array(h, v.val)
            return
        if v not in ids:
            ids[v] = len(ids)
        _feed(h, "v", ids[v], v.aval)

    _feed(h, "jaxpr", len(jaxpr.constvars), len(jaxpr.invars))
    for v in jaxpr.constvars:
        ref(v)
    for v in jaxpr.invars:
        ref(v)
    for eqn in jaxpr.eqns:
        _feed(h, "eqn", eqn.primitive.name, len(eqn.invars))
        for v in eqn.invars:
            ref(v)
        for k in sorted(eqn.params, key=str):
            _feed(h, "param", k)
            _feed_jaxpr_param(h, eqn.params[k], ids)
        for v in eqn.outvars:
            ref(v)
    _feed(h, "out")
    for v in jaxpr.outvars:
        ref(v)


def _feed_jaxpr_param(h, p, ids) -> None:
    from jax.extend.core import ClosedJaxpr, Jaxpr
    if isinstance(p, ClosedJaxpr):
        _feed_jaxpr(h, p.jaxpr, dict(ids))
        for c in p.consts:
            _feed_array(h, c)
    elif isinstance(p, Jaxpr):
        _feed_jaxpr(h, p, dict(ids))
    elif isinstance(p, (tuple, list)):
        _feed(h, "seq", len(p))
        for x in p:
            _feed_jaxpr_param(h, x, ids)
    else:
        # Shardings etc. stringify stably; strip any embedded object
        # addresses so reprs like <obj at 0x...> can't leak identity.
        _feed(h, _ADDR.sub("0x", str(p)))


# Fingerprints are cached per Program *object* (frozen dataclass, so
# weakref-able); the per-(threads, workload) program cache in SimEngine
# makes this one trace of each handler per process.
_FP_CACHE: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def _handler_digests(prog) -> list:
    """Per-handler canonical digests — the fingerprint's hash inputs at
    handler granularity, kept separable for mismatch postmortems."""
    import jax
    import jax.numpy as jnp
    # The machine's abstract per-thread state: (t, regs, result, rng).
    probe = (jnp.int32(0), jnp.zeros((prog.n_regs,), jnp.int32),
             jnp.int32(0), jnp.uint32(1))
    out = []
    for handler in prog.handlers:
        h = hashlib.sha256()
        closed = jax.make_jaxpr(handler)(*probe)
        _feed_jaxpr(h, closed.jaxpr, {})
        # Constants lift to constvars whose values the jaxpr walk sees
        # only as avals — hash the consts themselves by bytes.
        for c in closed.consts:
            _feed_array(h, c)
        out.append(h.hexdigest())
    return out


def program_fingerprint(prog) -> str:
    """Canonical semantic hash of a compiled ``Program`` (see module
    docstring for what is and isn't captured)."""
    with contextlib.suppress(KeyError, TypeError):
        return _FP_CACHE[prog]
    import jax
    h = hashlib.sha256()
    _feed(h, "repro.bench.cache", CACHE_KEY_VERSION, jax.__version__,
          int(prog.n_mem), int(prog.n_regs),
          tuple(prog.home), tuple(prog.init_mem))
    for d in _handler_digests(prog):
        _feed(h, d)
    fp = h.hexdigest()
    with contextlib.suppress(TypeError):
        # non-weakrefable custom Program stand-in
        _FP_CACHE[prog] = fp
    return fp


def cell_key(prog_fp: str, n_threads: int, workload, lowered_cost,
             lowered_sched, seeds) -> str:
    """Content key of one grid cell: program fingerprint + thread count
    + workload semantics + lowered machine/scheduler bytes + seeds."""
    h = hashlib.sha256()
    _feed(h, "cell", CACHE_KEY_VERSION, prog_fp, int(n_threads),
          int(workload.ncs_max), workload.cs_mode, int(workload.n_steps))
    for a in lowered_cost:
        _feed_array(h, a)
    for a in lowered_sched:
        _feed_array(h, a)
    _feed(h, tuple(int(s) for s in seeds))
    return h.hexdigest()


# --- BenchResult <-> JSON -----------------------------------------------------

_ARRAY_FIELDS = ("admissions", "admission_counts")
_SCALAR_FIELDS = ("name", "n_threads", "throughput", "episodes",
                  "miss_per_episode", "inval_per_episode",
                  "remote_per_episode", "latency", "unfairness",
                  "aborts", "preempts")


def result_to_doc(r: BenchResult) -> dict:
    doc = {f: getattr(r, f) for f in _SCALAR_FIELDS}
    for f in _ARRAY_FIELDS:
        a = np.asarray(getattr(r, f))
        doc[f] = {"dtype": a.dtype.str, "shape": list(a.shape),
                  "data": a.ravel().tolist()}
    return doc


def result_from_doc(doc: dict) -> BenchResult:
    kw = {f: doc[f] for f in _SCALAR_FIELDS}
    for f in _ARRAY_FIELDS:
        spec = doc[f]
        kw[f] = np.asarray(spec["data"],
                           dtype=np.dtype(spec["dtype"])).reshape(
                               spec["shape"])
    return BenchResult(**kw)


# --- the store ----------------------------------------------------------------

@dataclass
class CacheStats:
    """Per-process counters, reset never — readers take snapshots and
    diff (``registry.run_suite`` does this per suite)."""
    hits: int = 0
    misses: int = 0
    stores: int = 0

    def snapshot(self) -> dict:
        return {"hits": self.hits, "misses": self.misses,
                "stores": self.stores}


@dataclass
class ExperimentCache:
    """One-JSON-file-per-cell content-addressed store.

    ``enabled`` is the master switch (off = no reads, no writes);
    ``read`` gates lookups only — ``--no-cache`` sets ``read=False`` so
    everything regenerates but the store stays fresh for the next run.
    """
    root: str = ""
    enabled: bool = True
    read: bool = True
    stats: CacheStats = field(default_factory=CacheStats)

    def __post_init__(self):
        if not self.root:
            self.root = os.environ.get("REPRO_BENCH_CACHE_DIR",
                                       DEFAULT_ROOT)
        if os.environ.get("REPRO_BENCH_NO_CACHE", "") in ("1", "true"):
            self.enabled = False

    def _path(self, key: str) -> str:
        return os.path.join(self.root, key[:2], key + ".json")

    def get(self, key: str) -> dict | None:
        if not (self.enabled and self.read):
            return None
        try:
            with open(self._path(key)) as f:
                return json.load(f)
        except (FileNotFoundError, json.JSONDecodeError, OSError):
            return None

    def put(self, key: str, doc: dict) -> None:
        if not self.enabled:
            return
        path = self._path(key)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        # atomic publish: concurrent runs never see half-written entries
        fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path),
                                   suffix=".tmp")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(doc, f)
            os.replace(tmp, path)
        except OSError:
            with contextlib.suppress(OSError):
                os.unlink(tmp)
        self.stats.stores += 1

    def entries(self) -> int:
        n = 0
        for _, _, files in os.walk(self.root):
            n += sum(1 for f in files if f.endswith(".json"))
        return n

    def total_bytes(self) -> int:
        total = 0
        for dirpath, _, files in os.walk(self.root):
            for f in files:
                if f.endswith(".json"):
                    with contextlib.suppress(OSError):
                        total += os.path.getsize(os.path.join(dirpath, f))
        return total

    def describe(self) -> dict:
        return {"root": os.path.abspath(self.root),
                "enabled": self.enabled, "read": self.read,
                "entries": self.entries(), "bytes": self.total_bytes(),
                **self.stats.snapshot()}


# --- process-wide instance ----------------------------------------------------

_CACHE: ExperimentCache | None = None


def get_cache() -> ExperimentCache:
    global _CACHE
    if _CACHE is None:
        _CACHE = ExperimentCache()
    return _CACHE


def configure(*, root: str | None = None, enabled: bool | None = None,
              read: bool | None = None) -> ExperimentCache:
    """(Re)configure the process-wide cache; the CLI calls this before
    running a suite (``--cache-dir`` -> ``root``, ``--no-cache`` ->
    ``read=False``). Counters survive reconfiguration only when the
    root is unchanged."""
    global _CACHE
    cur = get_cache()
    if root is not None and root != cur.root:
        cur = ExperimentCache(root=root)
    if enabled is not None:
        cur.enabled = enabled
    if read is not None:
        cur.read = read
    _CACHE = cur
    return cur
