"""Atomic words for the locks, on the host and inside the lock kernel.

The runtime lock ports (``core/runtime/reciprocating.py``) and the
measured Pallas backend (``core/locks/pallas_backend.py``) both need
atomic words, against very different substrates:

* **Host** (:class:`HostAtomics`) — CPython exposes no user-level HW
  atomics, so :class:`AtomicRef` emulates them with a per-ref internal
  mutex (documented deviation — see DESIGN.md §L1). The *algorithmic
  structure* of the locks built on top (single-word state, segments,
  zombie end-of-segment, bounded bypass) is exactly the paper's; these
  runtime ports synchronize the framework's data pipeline and
  checkpoint writer for real. A lock port takes an :class:`Atomics`
  and allocates its cells through ``ref()``.
* **Device** (:func:`rmw`) — the one generic read-modify-write the
  machine's op table needs, on a Pallas memory ref. The measured kernel
  runs on a ``(rounds, T)`` grid whose two axes are declared
  ``"arbitrary"``: a TPU TensorCore then executes the grid programs one
  at a time, in row-major order, and so does the interpreter. A plain
  read-modify-write is therefore linearizable on both, and it is the
  only body: there is no guard word and no mode switch.
"""
from __future__ import annotations

import threading


class AtomicRef:
    """A single shared word with wait-free-style primitives (host cell)."""
    __slots__ = ("_v", "_m")

    def __init__(self, value=None):
        self._v = value
        self._m = threading.Lock()

    def load(self):
        return self._v

    def store(self, value) -> None:
        with self._m:
            self._v = value

    def exchange(self, value):
        with self._m:
            old, self._v = self._v, value
            return old

    def compare_exchange(self, expect, value) -> bool:
        with self._m:
            if self._v is expect or self._v == expect:
                self._v = value
                return True
            return False

    def fetch_add(self, delta: int) -> int:
        with self._m:
            old = self._v
            self._v = old + delta
            return old


class Atomics:
    """The interface a runtime lock port is written against: ``ref()``
    allocates one atomic cell."""

    def ref(self, value=None) -> AtomicRef:
        raise NotImplementedError


class HostAtomics(Atomics):
    """Host implementation: mutex-emulated :class:`AtomicRef` cells."""

    def ref(self, value=None) -> AtomicRef:
        return AtomicRef(value)


_HOST = HostAtomics()


def host_atomics() -> HostAtomics:
    """The process-wide host implementation (stateless — one suffices)."""
    return _HOST


def rmw(ref, idx, kind, a, b):
    """Generic machine-op read-modify-write on a Pallas ref, with traced
    ``idx``, ``kind`` and values: the effect table of
    ``core/sim/machine.py`` (STORE/XCHG write ``a``, FAA adds ``a``, CAS
    writes ``b`` iff ``old == a``, loads/waits leave the word) selected
    data-flow-style. Returns the old word, mirroring the machine's op
    results. It is a plain read-modify-write, linearizable because the
    kernel's grid programs run one at a time (module docstring)."""
    import jax.numpy as jnp

    from repro.core.sim import machine as M
    old = ref[idx]
    cas_ok = (kind == M.CAS) & (old == a)
    newval = jnp.where(kind == M.STORE, a,
             jnp.where(kind == M.XCHG, a,
             jnp.where(kind == M.FAA, old + a,
             jnp.where(cas_ok, b, old))))
    ref[idx] = newval
    return old
