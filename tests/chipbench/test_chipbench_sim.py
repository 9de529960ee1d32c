"""The sim cell's plain reference against the program, its control, and
whole runs (the chip check skipped) with the timed path broken
underneath, at a size a test run holds: T=8, 1,500 micro-steps, four
seeds per topology."""
import time

import numpy as np
import pytest

from chipbench import harness as H
from chipbench.reference import lock_machine as ref

CELL = "sim.mutexbench.randncs64"
SEEDS = [5, 2**31 - 2, 77, 123456789]


def small_cell(locks=("reciprocating", "mcs")):
    cell = H.cell_from_files(CELL, "mutexbench-randncs-t64",
                             "round_robin_grid")
    cell.config.update(threads=8, steps_per_call=1500, locks=list(locks),
                       seeds_per_topology=len(SEEDS))
    return cell


def run(cell, seconds=1.0):
    ctx = H.RunContext(cell, 2**32 + 9, seconds, False, time.perf_counter(),
                       None)
    return cell.driver.run(ctx)


@pytest.mark.parametrize("lock", sorted(ref.LOCKS))
def test_reference_equals_program(lock):
    from repro.core.sim.engine import SimEngine, Workload
    cell = small_cell()
    cfg = cell.config
    topos = cell.driver.topologies(cfg)
    g = SimEngine(lock).grid(seeds=SEEDS, topologies=topos,
                             workloads=[Workload(cfg["ncs_max"], "rw",
                                                 1500)],
                             threads=[8])
    want = cell.driver.reference_cells(cfg, lock, SEEDS)
    for c in g.cells:
        assert cell.driver.differing(c.result, want[c.topology]) == []
    assert want["epyc-2s"]["episodes"] > 0


def test_every_point_of_a_call_is_distinct():
    cfg = small_cell().config
    for lock in sorted(ref.LOCKS):
        points = [ref.simulate(ref.LOCKS[lock](8), levels, 1500, seed=s,
                               ncs_max=cfg["ncs_max"])
                  for levels in cfg["topologies"].values() for s in SEEDS]
        states = {(tuple(p["adm_log"]), tuple(p["lat_sum"]), p["time"])
                  for p in points}
        assert len(states) == len(points), lock


def test_control_breaks_mutual_exclusion_and_is_caught():
    cell = small_cell()
    cfg = cell.config
    want = cell.driver.reference_cells(cfg, "reciprocating", SEEDS)
    got = cell.driver.reference_cells(cfg, "reciprocating", SEEDS,
                                      broken=True)
    levels = cfg["topologies"]["epyc-2s"]
    p = ref.simulate(ref.reciprocating(8, broken=True), levels, 1500,
                     seed=SEEDS[0], ncs_max=cfg["ncs_max"])
    assert p["me_violations"] > 0
    from types import SimpleNamespace
    ns = SimpleNamespace(**{k: np.asarray(v) if isinstance(v, list) else v
                            for k, v in got["epyc-2s"].items()})
    assert cell.driver.differing(ns, want["epyc-2s"])


def test_control_in_the_programs_place_is_not_correct():
    cell = small_cell(locks=("reciprocating",))
    ctx = H.RunContext(cell, 2**32 + 11, 0.5, False, time.perf_counter(),
                       None)
    out = cell.driver.run(ctx, engine=cell.driver.ControlEngine)
    checks = {c.name: c for c in out.checks}
    assert not out.correct
    assert checks["fields_differing"].value > 0
    assert checks["points_compared"].ok


def test_bypass_bound_by_hand():
    # thread 0 waits while 1 is admitted twice: bound 2
    assert ref.bypass_bound([[0, 1, 1, 0, 2, 0] + [-1] * 506], [6]) == 2
    assert ref.bypass_bound([[0, 1, 0, 1] + [-1] * 508], [4]) == 1


def test_run_without_chip_is_correct():
    out = run(small_cell())
    assert out.correct, [(c.name, c.value) for c in out.checks]
    assert out.end_to_end["lock_steps_per_s"] > 0
    assert out.attempted >= 2 and out.failed == 0


def _unchanged_step(monkeypatch):
    from repro.core.sim import machine
    monkeypatch.setattr(machine, "machine_step",
                        lambda s, *a, **k: s)


def _half_batch(monkeypatch):
    import jax

    from repro.core.sim import engine
    real = engine.summarize_ensemble

    def half(name, T, s):
        n = jax.tree_util.tree_leaves(s)[0].shape[0]
        return real(name, T, jax.tree_util.tree_map(
            lambda a: a[:max(n // 2, 1)], s))
    monkeypatch.setattr(engine, "summarize_ensemble", half)


def _tiled_lanes(monkeypatch):
    # half of the points computed and tiled over the rest: every shape
    # as the program's
    import jax.numpy as jnp

    from repro.core.sim import engine
    real = engine.summarize_ensemble

    def tiled(name, T, s):
        import jax
        n = jax.tree_util.tree_leaves(s)[0].shape[0]
        idx = jnp.arange(n) % max(n // 2, 1)
        return real(name, T, jax.tree_util.tree_map(lambda a: a[idx], s))
    monkeypatch.setattr(engine, "summarize_ensemble", tiled)


def _altered_answer(monkeypatch):
    from repro.core.sim import engine
    real = engine.summarize_ensemble

    def altered(name, T, s):
        r = real(name, T, s)
        r.admissions = r.admissions.copy()
        r.admissions[0, 0] = (r.admissions[0, 0] + 1) % T
        return r
    monkeypatch.setattr(engine, "summarize_ensemble", altered)


@pytest.mark.parametrize("fault", [_unchanged_step, _half_batch,
                                   _tiled_lanes, _altered_answer])
def test_broken_timed_path_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    out = run(small_cell(locks=("reciprocating",)), seconds=0.5)
    assert not out.correct
