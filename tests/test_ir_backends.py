"""The IR → two-backend pipeline (ISSUE 10 tentpole).

Four property groups:

* **Golden pinning** — sim output lowered through ``core/locks/ir.py``
  is bit-identical to the pre-IR one-shot compiler. The digests live in
  ``core/locks/goldens.py`` (full ``MachineState``, field-declaration
  order) for every spec in the zoo plus deeper/NUMA settings; any drift
  in the lowering, the scaffolding injection, or the machine shows up as
  a digest mismatch.
* **IR surface** — ``lower_spec`` metadata (labels/phases/release pc),
  the ``OP_TABLE`` contract, and the ``compile_spec`` façade.
* **Backend agreement** — the sim under a uniform cost model dispatches
  exactly the Pallas kernel's round-robin op schedule, so admission
  order and per-thread CS counts must agree across backends.
* **Pallas semantics** — mutual-exclusion stress (in-kernel guard, zero
  collisions), host atomics and the kernel's read-modify-write.
"""
import numpy as np
import pytest

from repro.core.locks import ir as irmod
from repro.core.locks.compile import compile_spec
from repro.core.locks.goldens import GOLDEN, run_digest
from repro.core.locks.ir import OP_TABLE, LockIR, lower_spec, to_sim_program
from repro.core.locks.programs import PROGRAMS
from repro.core.locks.specs import SPECS
from repro.core.sim import machine as M
from repro.core.sim.machine import CostModel, run_machine

# --- golden pinning -----------------------------------------------------------

@pytest.mark.parametrize("key", list(GOLDEN))
def test_sim_through_ir_bit_identical(key):
    assert run_digest(key) == GOLDEN[key], (
        f"{key}: sim output through the IR drifted from the pinned digest")


def test_golden_covers_every_spec():
    pinned = {k.split("|")[0] for k in GOLDEN}
    assert pinned == set(SPECS), "every spec in the zoo must be pinned"


# --- IR surface ---------------------------------------------------------------

def test_lower_spec_metadata():
    ir = lower_spec(SPECS["reciprocating"], 4, name="reciprocating")
    assert isinstance(ir, LockIR)
    labels = dict(ir.labels)
    assert labels["ncs"] == 0
    assert ir.phases[0] == "ncs" and ir.phases[-1] == "cs"
    assert len(ir.phases) == ir.n_handlers
    assert ir.cs2_pc == ir.n_handlers - 1
    assert ir.phases[ir.release_pc] == "release"
    assert ir.label_of(0) == "ncs"
    # the façade produces the same Program the IR wraps
    prog = to_sim_program(ir)
    facade = compile_spec(SPECS["reciprocating"], 4, name="reciprocating")
    assert prog.n_mem == facade.n_mem and prog.home == facade.home
    assert len(prog.handlers) == len(facade.handlers)


def test_op_table_matches_machine_contract():
    assert set(OP_TABLE) == {
        M.NOP, M.LOAD, M.STORE, M.XCHG, M.CAS, M.FAA, M.SPIN_EQ,
        M.SPIN_NE, M.DELAY, M.PARK_EQ, M.PARK_EQ_TIMEOUT,
        M.PARK_NE_TIMEOUT}
    assert OP_TABLE[M.CAS].result == "old2ok"
    assert OP_TABLE[M.CAS].is_store and OP_TABLE[M.CAS].is_load
    assert OP_TABLE[M.SPIN_EQ].is_wait and not OP_TABLE[M.SPIN_EQ].is_store
    assert OP_TABLE[M.PARK_EQ_TIMEOUT].result == "old2ok"
    assert not OP_TABLE[M.DELAY].is_load


def test_ir_fingerprintable():
    # bench/cache.py duck-types program_fingerprint over the IR directly
    from repro.bench.cache import program_fingerprint
    ir = lower_spec(SPECS["ticket"], 3, name="ticket")
    fp_ir = program_fingerprint(ir)
    fp_prog = program_fingerprint(to_sim_program(ir))
    assert fp_ir == fp_prog


# --- backend agreement --------------------------------------------------------

AGREE = ("reciprocating", "mcs", "ticket", "hapax")


@pytest.mark.parametrize("alg", AGREE)
def test_backend_agreement(alg):
    """Uniform-cost sim == Pallas round-robin schedule: identical
    admission order and, over the compared prefix, identical per-thread
    CS counts."""
    from repro.core.locks.pallas_backend import run_measured

    T = 3
    prog = PROGRAMS[alg](T, ncs_max=0, cs_shared=True)
    s = run_machine(prog, T, 1_000,
                    cm=CostModel(hit=1, local_miss=1, remote_miss=1),
                    seed=0)
    sim_order = np.asarray(s.adm_log)[:int(s.adm_cnt)].tolist()
    r = run_measured(alg, T, 150, interpret=True)
    assert r.collisions == 0
    pal_order = r.admissions[:r.admission_counts].tolist()
    n = min(len(sim_order), len(pal_order), 48)
    assert n >= 16, f"not enough admissions to compare ({n})"
    assert sim_order[:n] == pal_order[:n], (
        f"{alg}: admission order diverged\n sim {sim_order[:n]}\n "
        f"pallas {pal_order[:n]}")
    assert np.bincount(sim_order[:n], minlength=T).tolist() == \
        np.bincount(pal_order[:n], minlength=T).tolist()


# --- Pallas backend semantics -------------------------------------------------

def test_pallas_mutual_exclusion_stress():
    """The in-kernel guard counts any admit that lands while another
    thread is inside its admit..return window — across a long contended
    run it must stay zero, and every thread must make progress."""
    from repro.core.locks.pallas_backend import run_measured

    r = run_measured("reciprocating", 5, 600, interpret=True, seed=2)
    assert r.collisions == 0
    assert r.episodes > 100
    assert (r.per_thread > 0).all(), f"starved thread: {r.per_thread}"
    # every admitted episode eventually returns to the NCS (one episode
    # may still be in flight at the end of the schedule)
    assert abs(r.returns - r.episodes) <= 1


def test_pallas_timed_lock_runs():
    # a timed-park spec exercises the probe-budget path (PARK_*_TIMEOUT)
    from repro.core.locks.pallas_backend import run_measured

    r = run_measured("mcs_timeout", 3, 200, interpret=True)
    assert r.collisions == 0
    assert r.episodes > 0


def test_run_measured_compiles_for_a_tpu_or_raises():
    """Without ``interpret=True`` the kernel is compiled for the device,
    and a host without a TPU is an error, never a quiet interpreter
    run."""
    import jax

    from repro.core.locks.pallas_backend import run_measured

    if jax.devices()[0].platform == "tpu":
        pytest.skip("this host has a TPU")
    with pytest.raises(RuntimeError, match="interpret=True"):
        run_measured("ticket", 2, 16)


def test_measured_result_metrics():
    from repro.core.locks.pallas_backend import run_measured

    r = run_measured("ticket", 2, 100, interpret=True)
    assert r.slices == 200
    assert r.backend == "pallas-interpret"
    assert r.platform == "cpu" and r.device_count >= 1 and r.device_kind
    assert r.throughput_eps > 0 and r.episodes_per_kslice > 0
    assert r.latency_slices >= 0
    assert r.wall_s > 0 and r.compile_s > 0


def test_backends_catalogue():
    from repro.core.locks.pallas_backend import backends

    rows = backends()
    by = {r["name"]: r for r in rows}
    assert set(by) == {"sim", "pallas-interpret", "pallas-device"}
    assert by["sim"]["available"] is True
    assert by["pallas-interpret"]["available"] is True   # CPU fallback
    for r in rows:
        assert isinstance(r["available"], bool) and r["detail"]


# --- host atomics and the kernel read-modify-write ---------------------------

def test_host_atomics_ref():
    from repro.core.runtime.atomics import AtomicRef, host_atomics

    ref = host_atomics().ref(None)
    assert isinstance(ref, AtomicRef)
    assert ref.load() is None
    assert ref.exchange("a") is None and ref.load() == "a"
    assert ref.compare_exchange("a", "b") and ref.load() == "b"
    assert not ref.compare_exchange("zzz", "c") and ref.load() == "b"
    num = host_atomics().ref(5)
    assert num.fetch_add(3) == 5 and num.load() == 8


def test_pallas_atomics_rmw_contract():
    """The generic traced-kind RMW implements the machine's effect
    table: STORE/XCHG write, FAA adds, CAS writes iff old == expect,
    waits/loads leave the word — all returning the old value."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from repro.core.runtime.atomics import rmw

    ops = jnp.array([
        # (kind, idx, a, b, want_old, want_new)
        [M.LOAD, 0, 0, 0, 10, 10],
        [M.XCHG, 0, 77, 0, 10, 77],
        [M.FAA, 1, 5, 0, 20, 25],
        [M.CAS, 2, 30, 99, 30, 99],     # expect matches -> writes b
        [M.CAS, 3, 0, 55, 40, 40],      # expect misses -> unchanged
        [M.STORE, 1, 1, 0, 25, 1],
        [M.SPIN_EQ, 2, 99, 0, 99, 99],  # waits never write
    ], jnp.int32)

    def kernel(ops_ref, mem_in, mem, olds):
        i = pl.program_id(0)
        kind, idx = ops_ref[i, jnp.int32(0)], ops_ref[i, jnp.int32(1)]
        a, b = ops_ref[i, jnp.int32(2)], ops_ref[i, jnp.int32(3)]
        olds[i] = rmw(mem, idx, kind, a, b)

    mem0 = jnp.array([10, 20, 30, 40], jnp.int32)
    mem, olds = pl.pallas_call(
        kernel, grid=(ops.shape[0],),
        out_shape=[jax.ShapeDtypeStruct((4,), jnp.int32),
                   jax.ShapeDtypeStruct((ops.shape[0],), jnp.int32)],
        input_output_aliases={1: 0},
        interpret=True,
    )(ops, mem0)
    want = np.asarray(ops)[:, 4]
    assert np.asarray(olds).tolist() == want.tolist()
    assert np.asarray(mem).tolist() == [77, 1, 99, 40]


def test_reciprocating_lock_takes_injected_atomics():
    import threading

    from repro.core.runtime.atomics import HostAtomics
    from repro.core.runtime.reciprocating import ReciprocatingLock

    lock = ReciprocatingLock(atomics=HostAtomics())
    counter = [0]

    def worker():
        for _ in range(200):
            with lock:
                counter[0] += 1

    ts = [threading.Thread(target=worker) for _ in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert counter[0] == 800
    assert not lock.locked_hint()


def test_ir_module_all_exports():
    for name in irmod.__all__:
        assert hasattr(irmod, name)
