"""Pinned sim state digests for every spec in the lock zoo.

Each entry is the digest of the full ``MachineState`` after a run of
the sim backend, keyed by the run's settings. The states are integer
machine words, so a digest is the same on every device: the tests pin
it on the CPU and ``chip_smoke.py`` recomputes it on the TPU. Any drift
in the lowering, the scaffolding injection or the machine shows up as a
mismatch.
"""
from __future__ import annotations

import hashlib

import numpy as np

from repro.core.sim.machine import CostModel

# digest = sha256 over every MachineState field (declaration order,
# name + raw bytes), truncated to 16 hex chars.
GOLDEN = {
    "reciprocating|T=2|ncs=0|cs=True|steps=400|seed=0|default":
        "e2fc56ee3d17fb6f",
    "ticket|T=2|ncs=0|cs=True|steps=400|seed=0|default":
        "b42c869a2ca1cca5",
    "retrograde|T=2|ncs=0|cs=True|steps=400|seed=0|default":
        "79960f2ce27e9c2f",
    "mcs|T=2|ncs=0|cs=True|steps=400|seed=0|default":
        "8387d5506d68fc6a",
    "clh|T=2|ncs=0|cs=True|steps=400|seed=0|default":
        "cae27353224a9dc9",
    "hemlock|T=2|ncs=0|cs=True|steps=400|seed=0|default":
        "83eeeeb403745a43",
    "ttas|T=2|ncs=0|cs=True|steps=400|seed=0|default":
        "51eefc194c8050d8",
    "anderson|T=2|ncs=0|cs=True|steps=400|seed=0|default":
        "0843d215e9932d04",
    "hapax|T=2|ncs=0|cs=True|steps=400|seed=0|default":
        "ce0f7386390b478a",
    "fissile|T=2|ncs=0|cs=True|steps=400|seed=0|default":
        "287a7bdc2d709441",
    "spin_then_park|T=2|ncs=0|cs=True|steps=400|seed=0|default":
        "9210351668cdf6fa",
    "reciprocating_abortable|T=2|ncs=0|cs=True|steps=400|seed=0|default":
        "c6802f617dbac80a",
    "mcs_timeout|T=2|ncs=0|cs=True|steps=400|seed=0|default":
        "8f001e3d0607a9db",
    "reciprocating|T=3|ncs=5|cs=ro|steps=500|seed=1|uniform":
        "39ae02e13b9e5305",
    "hapax|T=4|ncs=17|cs=True|steps=800|seed=3|default":
        "54b5eb92cc257a1f",
    "spin_then_park|T=4|ncs=17|cs=True|steps=800|seed=3|default":
        "f20fa9e6637b559d",
    "mcs_timeout|T=3|ncs=5|cs=ro|steps=500|seed=1|uniform":
        "a7764ebca80d07ef",
}

COST_MODELS = {"default": CostModel(),
               "uniform": CostModel(hit=1, local_miss=1, remote_miss=1)}


def state_digest(state) -> str:
    h = hashlib.sha256()
    for f in state._fields:
        h.update(f.encode())
        h.update(np.asarray(getattr(state, f)).tobytes())
    return h.hexdigest()[:16]


def parse_key(key: str) -> tuple:
    """``(name, T, ncs, cs, steps, seed, cost model name)`` of a key."""
    name, Ts, ncss, css, stepss, seeds, cm = key.split("|")
    return (name, int(Ts[2:]), int(ncss[4:]),
            True if css[3:] == "True" else css[3:],
            int(stepss[6:]), int(seeds[5:]), cm)


def run_digest(key: str) -> str:
    """Run the sim for ``key``'s settings and digest the final state."""
    from repro.core.locks.programs import PROGRAMS
    from repro.core.sim.machine import run_machine

    name, T, ncs, cs, steps, seed, cm = parse_key(key)
    prog = PROGRAMS[name](T, ncs_max=ncs, cs_shared=cs)
    return state_digest(run_machine(prog, T, steps, cm=COST_MODELS[cm],
                                    seed=seed))
