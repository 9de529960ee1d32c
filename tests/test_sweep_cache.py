"""Differential harness for the sharded, content-cached sweep engine.

What PR-level claims these tests pin (extending the frozen-oracle
pattern of ``tests/_legacy_programs.py`` — two independent execution
paths must agree bit-for-bit, not approximately):

* **Sharded == unsharded.** ``SimEngine.grid(shard=True)`` routes the
  stacked point batch through ``shard_map`` over a device mesh;
  ``shard=False`` is the historical plain vmap. Every grid point is an
  independent element-wise simulation, so the two paths must produce
  bit-identical ``GridResult`` cells — on one device (forced mesh of 1,
  in-process) and on a real 4-device mesh including the batch-padding
  branch (subprocess, since ``XLA_FLAGS`` must be set before jax
  imports).
* **Cached == fresh.** ``bench/cache.py`` round-trips a ``BenchResult``
  through its content-addressed JSON store; a warm ``cached_grid`` must
  return cells equal field-for-field (ndarray dtypes included) to the
  cold run that stored them, with zero compiles.
* **The key is semantic.** Any change to the spec program, topology,
  scheduler, workload or seeds changes the cell key; renaming step
  labels, memory words, workload labels or scheduler presets — or
  editing docstrings — does not. Keys are pure content hashes, stable
  across processes. (Hypothesis drives the label/step invariance when
  installed; pinned parametrization otherwise, as in
  ``tests/test_hostile.py``.)
* **Compile accounting is exact, process-wide.** A session reused
  across two suites with different scheduler stacks pays exactly one
  trace per batch shape (regression: the counts below are pinned), and
  the module-level ``trace_count()`` also sees traces paid by throwaway
  engines that no session counter records — the under-count that made
  suite-level compile accounting unreliable.
"""
import json
import os
import subprocess
import sys
from dataclasses import replace

import jax
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:      # property tests degrade to fixed parametrization
    HAVE_HYPOTHESIS = False

from repro.bench import cache as cachemod
from repro.bench import sweep
from repro.bench.registry import BenchConfig
from repro.bench import schema
from repro.core.locks.compile import compile_spec
from repro.core.locks.dsl import FAA, LOAD, NCS, SPIN_EQ, STORE
from repro.core.sim.engine import (
    SimEngine, Workload, trace_count, _lower_host, _lower_sched_host,
)
from repro.core.sim.machine import CostModel
from repro.core.sim.sched import resolve as sched_resolve

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")

#: >= 5 locks x 2 topologies x 2 schedulers for the differential grid.
DIFF_LOCKS = ("reciprocating", "mcs", "ticket", "clh", "spin_then_park")
DIFF_TOPOLOGIES = ("smp:4", "numa:2x2")
DIFF_SCHEDULERS = ("dedicated", "fair-2x")
SEEDS = (0, 1)
WL = Workload(0, True, 600)

RESULT_SCALARS = ("name", "n_threads", "throughput", "episodes",
                  "miss_per_episode", "inval_per_episode",
                  "remote_per_episode", "latency", "unfairness",
                  "aborts", "preempts")
RESULT_ARRAYS = ("admissions", "admission_counts")


def assert_results_identical(a, b, ctx=""):
    for f in RESULT_SCALARS:
        assert getattr(a, f) == getattr(b, f), f"{ctx}: {f} diverged"
    for f in RESULT_ARRAYS:
        x, y = np.asarray(getattr(a, f)), np.asarray(getattr(b, f))
        assert x.dtype == y.dtype, f"{ctx}: {f} dtype diverged"
        assert np.array_equal(x, y), f"{ctx}: {f} diverged"


@pytest.fixture
def own_cache(tmp_path):
    """A private cache store, restoring the process-wide one after."""
    prev = cachemod._CACHE
    store = cachemod.configure(root=str(tmp_path / "cache"))
    yield store
    cachemod._CACHE = prev


# --- sharded vs unsharded ----------------------------------------------------

@pytest.mark.parametrize("lock", DIFF_LOCKS)
def test_sharded_grid_bit_identical(lock):
    """shard=True (forced shard_map, mesh of >= 1 device) against
    shard=False (plain vmap) over the full 2-topology x 2-scheduler
    grid: every cell bit-identical on pinned seeds."""
    eng = SimEngine(lock, n_threads=4, workload=WL)
    kw = {"seeds": SEEDS, "topologies": list(DIFF_TOPOLOGIES),
          "schedulers": list(DIFF_SCHEDULERS)}
    g0 = eng.grid(**kw, shard=False)
    g1 = eng.grid(**kw, shard=True)
    assert len(g0.cells) == len(g1.cells) == 4
    assert (g0.shards, g1.shards) == (0, jax.device_count())
    for c0, c1 in zip(g0.cells, g1.cells):
        assert (c0.topology, c0.scheduler) == (c1.topology, c1.scheduler)
        assert_results_identical(
            c0.result, c1.result,
            ctx=f"{lock}/{c0.topology}/{c0.scheduler}")


_MULTI_DEV_SCRIPT = r"""
import json
import numpy as np
import jax
from repro.core.sim.engine import SimEngine, Workload
checks, shards = [], []
for lock in ("reciprocating", "mcs"):
    eng = SimEngine(lock, n_threads=4, workload=Workload(0, True, 600))
    # 3 seeds x 2 topologies = 6 points on 4 devices: pads to 8, trims
    kw = dict(seeds=[0, 1, 2], topologies=["smp:4", "numa:2x2"])
    g0 = eng.grid(**kw, shard=False)
    g1 = eng.grid(**kw, shard="auto")
    shards.append((g0.shards, g1.shards))
    for c0, c1 in zip(g0.cells, g1.cells):
        a, b = c0.result, c1.result
        same = all(getattr(a, f) == getattr(b, f) for f in (
            "throughput", "episodes", "miss_per_episode",
            "inval_per_episode", "remote_per_episode", "latency",
            "unfairness", "aborts", "preempts"))
        same = same and np.array_equal(a.admissions, b.admissions)
        same = same and np.array_equal(a.admission_counts,
                                       b.admission_counts)
        checks.append(bool(same))
print(json.dumps({"devices": jax.device_count(), "shards": shards,
                  "n_cells": len(checks), "all_equal": all(checks)}))
"""


def test_sharded_multi_device_bit_identical():
    """Real 4-device host mesh (forced via XLA_FLAGS, so it needs a
    fresh process) — ``shard="auto"`` splits the batch across devices,
    pads 6 points to 8, and must still match vmap bit-for-bit."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    p = subprocess.run([sys.executable, "-c", _MULTI_DEV_SCRIPT],
                       capture_output=True, text=True, timeout=600,
                       env=env, cwd=REPO)
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["devices"] == 4
    assert out["shards"] == [[0, 4], [0, 4]]   # read from the output
    assert out["n_cells"] == 4
    assert out["all_equal"]


# --- cached vs fresh ---------------------------------------------------------

def test_cached_grid_warm_equals_fresh(own_cache):
    kw = {"seeds": SEEDS,
          "topologies": [CostModel(n_nodes=1), CostModel(n_nodes=2)],
          "workloads": [WL], "threads": [4]}
    cold = sweep.cached_grid("reciprocating", **kw)
    assert own_cache.stats.misses == len(cold.cells)
    assert own_cache.stats.stores == len(cold.cells)
    warm = sweep.cached_grid("reciprocating", **kw)
    assert warm.compiles == 0                    # no simulation at all
    assert own_cache.stats.hits == len(cold.cells)
    for c0, c1 in zip(cold.cells, warm.cells):
        assert (c0.lock, c0.n_threads, c0.topology, c0.workload,
                c0.scheduler) == (c1.lock, c1.n_threads, c1.topology,
                                  c1.workload, c1.scheduler)
        assert_results_identical(c0.result, c1.result,
                                 ctx=f"cached {c0.topology}")


def test_bench_cell_cached_equality(own_cache):
    """The bench-harness entry point: a warm ``bench_cell`` must return
    a BenchResult equal field-for-field to the cold one."""
    cfg = BenchConfig(threads=(2,), n_steps=300, n_replicas=2,
                      verbose=False)
    cold = sweep.bench_cell("mcs", 2, cfg)
    warm = sweep.bench_cell("mcs", 2, cfg)
    assert own_cache.stats.hits >= 1
    assert_results_identical(cold, warm, ctx="bench_cell mcs")


def test_partial_hit_reruns_whole_grid(own_cache):
    """Losing one cell's entry degrades to a full (one-jit) grid rerun
    that re-stores every cell — never a partial mixed-source grid."""
    kw = {"seeds": SEEDS,
          "topologies": [CostModel(n_nodes=1), CostModel(n_nodes=2)],
          "workloads": [WL], "threads": [4]}
    sweep.cached_grid("ticket", **kw)
    # evict one of the two entries
    victims = [os.path.join(dp, f) for dp, _, fs in
               os.walk(own_cache.root) for f in fs if f.endswith(".json")]
    os.unlink(sorted(victims)[0])
    h0, s0 = own_cache.stats.hits, own_cache.stats.stores
    g = sweep.cached_grid("ticket", **kw)
    assert own_cache.stats.hits == h0           # no partial credit
    assert own_cache.stats.stores == s0 + len(g.cells)
    # and now it's fully warm again
    warm = sweep.cached_grid("ticket", **kw)
    assert warm.compiles == 0
    for c0, c1 in zip(g.cells, warm.cells):
        assert_results_identical(c0.result, c1.result, ctx="re-stored")


def test_disabled_cache_bypasses_store(own_cache):
    own_cache.enabled = False
    kw = {"seeds": (0,), "workloads": [WL], "threads": [2]}
    sweep.cached_grid("mcs", **kw)
    assert own_cache.stats.snapshot() == {"hits": 0, "misses": 0,
                                          "stores": 0}
    assert own_cache.entries() == 0


def test_no_read_still_stores(own_cache):
    """--no-cache semantics: lookups off, the store stays fresh."""
    kw = {"seeds": (0,), "workloads": [WL], "threads": [2]}
    sweep.cached_grid("clh", **kw)
    own_cache.read = False
    h0 = own_cache.stats.hits
    sweep.cached_grid("clh", **kw)
    assert own_cache.stats.hits == h0            # regenerated
    assert own_cache.entries() >= 1              # but re-stored
    own_cache.read = True
    warm = sweep.cached_grid("clh", **kw)
    assert warm.compiles == 0


# --- the cache key is semantic -----------------------------------------------

def _cell_key(lock="mcs", T=4, ncs=0, cs=True, n_steps=500,
              topology=CostModel(), sched="dedicated",  # noqa: B008
              seeds=(0, 1),
              wl_label=""):
    eng = SimEngine(lock, n_threads=T)
    wl = Workload(ncs, cs, n_steps, label=wl_label)
    fp = cachemod.program_fingerprint(eng.program(T, wl))
    return cachemod.cell_key(fp, T, wl, _lower_host(topology, T),
                             _lower_sched_host(sched, T), seeds)


SEMANTIC_MUTATIONS = [
    ("lock", "clh"),                             # different program
    ("T", 5),                                    # thread count
    ("ncs", 64),                                 # workload NCS bound
    ("cs", "local"),                             # workload CS profile
    ("n_steps", 501),                            # horizon
    ("topology", CostModel(n_nodes=2)),          # NUMA split
    ("topology", replace(CostModel(), local_miss=41)),   # one cost cycle
    ("sched", "fair-2x"),                        # scheduler family
    ("sched", "fair:2501x2"),                    # one quantum cycle
    ("seeds", (0, 2)),                           # seed value
    ("seeds", (0, 1, 2)),                        # ensemble size
]


@pytest.mark.parametrize("fld,value", SEMANTIC_MUTATIONS,
                         ids=[f"{f}={v}" for f, v in SEMANTIC_MUTATIONS])
def test_semantic_change_changes_key(fld, value):
    assert _cell_key() != _cell_key(**{fld: value})


def _check_label_invariance(wl_label, sched_rename):
    base = _cell_key()
    assert _cell_key(wl_label=wl_label) == base
    ded = sched_resolve("dedicated")
    assert _cell_key(sched=replace(ded, name=sched_rename or "x")) == base


if HAVE_HYPOTHESIS:
    @settings(max_examples=20, deadline=None)
    @given(st.text(max_size=12), st.text(min_size=1, max_size=12))
    def test_key_ignores_labels(wl_label, sched_rename):
        _check_label_invariance(wl_label, sched_rename)
else:
    @pytest.mark.parametrize("wl_label,sched_rename",
                             [("max_contention", "pinned"),
                              ("x", "dedicated2"), ("", "y")])
    def test_key_ignores_labels(wl_label, sched_rename):
        _check_label_invariance(wl_label, sched_rename)


def _check_seed_sensitivity(seeds_a, seeds_b):
    ka, kb = _cell_key(seeds=seeds_a), _cell_key(seeds=seeds_b)
    assert (ka == kb) == (tuple(seeds_a) == tuple(seeds_b))


if HAVE_HYPOTHESIS:
    @settings(max_examples=20, deadline=None)
    @given(st.lists(st.integers(0, 2**20), min_size=1, max_size=4),
           st.lists(st.integers(0, 2**20), min_size=1, max_size=4))
    def test_key_seed_sensitivity(seeds_a, seeds_b):
        _check_seed_sensitivity(seeds_a, seeds_b)
else:
    @pytest.mark.parametrize("seeds_a,seeds_b",
                             [((0,), (0,)), ((0,), (1,)),
                              ((0, 1), (1, 0)), ((3, 3), (3,))])
    def test_key_seed_sensitivity(seeds_a, seeds_b):
        _check_seed_sensitivity(seeds_a, seeds_b)


# Three ticket-lock authors: A and B are the same algorithm with every
# step, memory word and docstring renamed; C changes one FAA delta.

def _ticket_a(s):
    tk, gr = s.word("ticket"), s.word("grant")

    @s.step("doorway")
    def take(c):
        """Grab the next ticket."""
        return c.op(FAA(tk, 1))

    @s.step("doorway")
    def wait(c):
        return c.op(SPIN_EQ(gr, c.res), arrive=True)

    @s.step("entry")
    def granted(c):
        return c.enter_cs(admit=True)

    @s.step("release")
    def bump(c):
        return c.op(LOAD(gr))

    @s.step("release")
    def done(c):
        return c.op(STORE(gr, c.res + 1), to=NCS)


def _ticket_b(s):
    serving, now = s.word("serving_counter"), s.word("now_serving")

    @s.step("doorway")
    def acquire_ticket(c):
        """Completely different prose, same semantics."""
        return c.op(FAA(serving, 1))

    @s.step("doorway")
    def spin_on_grant(c):
        return c.op(SPIN_EQ(now, c.res), arrive=True)

    @s.step("entry")
    def admitted(c):
        return c.enter_cs(admit=True)

    @s.step("release")
    def read_grant(c):
        return c.op(LOAD(now))

    @s.step("release")
    def publish_next(c):
        return c.op(STORE(now, c.res + 1), to=NCS)


def _ticket_c(s):
    tk, gr = s.word("ticket"), s.word("grant")

    @s.step("doorway")
    def take(c):
        return c.op(FAA(tk, 2))      # semantic change: stride-2 tickets

    @s.step("doorway")
    def wait(c):
        return c.op(SPIN_EQ(gr, c.res), arrive=True)

    @s.step("entry")
    def granted(c):
        return c.enter_cs(admit=True)

    @s.step("release")
    def bump(c):
        return c.op(LOAD(gr))

    @s.step("release")
    def done(c):
        return c.op(STORE(gr, c.res + 1), to=NCS)


def test_fingerprint_ignores_labels_catches_semantics():
    fa = cachemod.program_fingerprint(compile_spec(_ticket_a, 4))
    fb = cachemod.program_fingerprint(compile_spec(_ticket_b, 4))
    fc = cachemod.program_fingerprint(compile_spec(_ticket_c, 4))
    assert fa == fb      # renames + docstrings are invisible
    assert fa != fc      # one constant differs -> new fingerprint


def test_fingerprint_distinguishes_zoo():
    fps = {lock: cachemod.program_fingerprint(
               SimEngine(lock, n_threads=4).program(4, WL))
           for lock in DIFF_LOCKS}
    assert len(set(fps.values())) == len(DIFF_LOCKS)


_KEY_SCRIPT = r"""
import json
from repro.bench import cache as cachemod
from repro.core.sim.engine import (
    SimEngine, Workload, _lower_host, _lower_sched_host,
)
eng = SimEngine("mcs", n_threads=4)
wl = Workload(0, True, 500)
prog = eng.program(4, wl)
fp = cachemod.program_fingerprint(prog)
key = cachemod.cell_key(fp, 4, wl, _lower_host("smp:4", 4),
                        _lower_sched_host("fair-2x", 4), (0, 1))
print(json.dumps({"fp": fp, "key": key,
                  "parts": cachemod._handler_digests(prog)}))
"""


def test_key_stable_across_processes():
    """The key must be a pure content hash: a fresh interpreter derives
    the same fingerprint and cell key as this one. Regression: the
    fingerprint once hashed ``str(jaxpr)``, whose sub-jaxpr inlining
    depends on jax's process-wide trace caches (a warmed ``_where``
    cache prints as ``jaxpr=_where``), so the in-process value drifted
    mid-session away from what fresh interpreters compute."""
    eng = SimEngine("mcs", n_threads=4)
    wl = Workload(0, True, 500)
    prog = eng.program(4, wl)
    fp = cachemod.program_fingerprint(prog)
    key = cachemod.cell_key(fp, 4, wl, _lower_host("smp:4", 4),
                            _lower_sched_host("fair-2x", 4), (0, 1))
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    p = subprocess.run([sys.executable, "-c", _KEY_SCRIPT],
                       capture_output=True, text=True, timeout=300,
                       env=env, cwd=REPO)
    assert p.returncode == 0, p.stderr[-2000:]
    other = json.loads(p.stdout.strip().splitlines()[-1])
    here = {"fp": fp, "key": key,
            "parts": cachemod._handler_digests(prog)}
    diffs = [i for i, (a, b) in enumerate(zip(here["parts"],
                                              other["parts"])) if a != b]
    assert other == here, f"handlers differing: {diffs}"


def test_result_roundtrip_preserves_dtypes():
    r = SimEngine("reciprocating", n_threads=4, workload=WL).run(0)
    back = cachemod.result_from_doc(
        json.loads(json.dumps(cachemod.result_to_doc(r))))
    assert_results_identical(r, back, ctx="json roundtrip")


# --- compile accounting ------------------------------------------------------

def test_two_suite_session_exact_compiles():
    """Regression: one session serving two suites with different
    scheduler stacks. Each new batch shape is exactly one trace; the
    per-session counter and the process-wide ``trace_count()`` agree —
    until a throwaway engine re-traces, which only the process-wide
    counter sees (the historical under-count in suite accounting)."""
    wl = Workload(0, True, 400)
    t0 = trace_count()
    eng = SimEngine("hemlock", n_threads=4, workload=wl)
    # suite 1: topology grid (4-point batch), dedicated scheduler
    g1 = eng.grid(seeds=SEEDS, topologies=["smp:4", "numa:2x2"])
    assert g1.compiles == 1
    # suite 2, same session: 3-scheduler stack -> 6-point batch shape
    g2 = eng.grid(seeds=SEEDS,
                  schedulers=["dedicated", "fair-2x", "fair-4x"])
    assert g2.compiles == 1
    # re-running the wider stack is free: schedulers are data
    g3 = eng.grid(seeds=SEEDS,
                  schedulers=["dedicated", "fair-2x", "fair-4x"])
    assert g3.compiles == 0
    assert eng.compiles == 2
    assert trace_count() - t0 == 2
    # a fresh engine for the same lock re-traces: invisible to any
    # session counter, visible to the process-wide one
    eng2 = SimEngine("hemlock", n_threads=4, workload=wl)
    eng2.grid(seeds=SEEDS, topologies=["smp:4", "numa:2x2"])
    assert eng.compiles == 2
    assert eng2.compiles == 1
    assert trace_count() - t0 == 3


def test_shard_toggle_never_reuses_wrong_jit():
    """The shard count is part of the jit key: toggling modes on one
    session retraces rather than reusing the other path's executable."""
    eng = SimEngine("ticket", n_threads=4, workload=WL)
    eng.grid(seeds=SEEDS, shard=False)
    assert eng.compiles == 1
    eng.grid(seeds=SEEDS, shard=True)
    assert eng.compiles == 2
    eng.grid(seeds=SEEDS, shard=False)
    eng.grid(seeds=SEEDS, shard=True)
    assert eng.compiles == 2      # both paths now cached


# --- harness block + trend log -----------------------------------------------

def test_run_suite_harness_block(own_cache):
    from repro.bench import run_suite
    cfg = BenchConfig(threads=(2,), n_steps=250, n_replicas=1,
                      verbose=False, quick=True)
    doc = run_suite("fairness", cfg)
    h = doc["harness"]
    assert set(h) >= {"wall_s", "xla_traces", "cache_hits",
                      "cache_misses", "cache_stores", "cache_hit_rate"}
    assert h["wall_s"] >= 0
    assert schema.validate_result(doc) == []


def test_trend_append_and_tolerant_load(tmp_path, own_cache):
    from repro.bench import run_suite
    cfg = BenchConfig(threads=(2,), n_steps=250, n_replicas=1,
                      verbose=False, quick=True)
    doc = run_suite("fairness", cfg)
    path = str(tmp_path / "trend.json")
    schema.append_trend(path, schema.trend_entry(doc))
    schema.append_trend(path, schema.trend_entry(doc))
    trend = schema.load_trend(path)
    assert trend["schema"] == schema.TREND_SCHEMA_VERSION
    assert len(trend["entries"]) == 2
    e = trend["entries"][0]
    assert e["suite"] == "fairness"
    assert e["quick"] is True
    assert e["wall_s"] == doc["harness"]["wall_s"]
    assert e["experiments"] == len(doc["experiments"])
    # a corrupt trend file restarts the log instead of failing the run
    with open(path, "w") as f:
        f.write("{not json")
    assert schema.load_trend(path)["entries"] == []


def test_cli_run_emits_trend(tmp_path):
    from repro.bench.cli import main
    prev = cachemod._CACHE
    try:
        out = tmp_path / "r.json"
        rc = main(["run", "--suite", "fairness", "--out", str(out),
                   "--quick", "--no-progress",
                   "--cache-dir", str(tmp_path / "cache")])
        assert rc == 0
        doc = json.loads(out.read_text())
        assert "harness" in doc
        trend = json.loads((tmp_path / "BENCH_trend.json").read_text())
        assert trend["schema"] == schema.TREND_SCHEMA_VERSION
        assert trend["entries"][-1]["suite"] == "fairness"
    finally:
        cachemod._CACHE = prev


def test_cli_list_cache_status(tmp_path, capsys):
    from repro.bench.cli import main
    prev = cachemod._CACHE
    try:
        cachemod.configure(root=str(tmp_path / "cache"))
        rc = main(["list", "--cache",
                   "--trend", str(tmp_path / "trend.json")])
        assert rc == 0
        out = capsys.readouterr().out
        assert "experiment cache" in out
        assert "entries" in out
    finally:
        cachemod._CACHE = prev
