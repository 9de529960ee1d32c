"""``repro.tracing`` on spans timed by a clock worked out by hand, and the
spans and counters one ``SimEngine.grid`` call leaves in it."""
import sys
import threading

import numpy as np
import pytest

from repro import tracing
from repro.core.sim.engine import SimEngine, Workload, trace_count


@pytest.fixture
def clock(monkeypatch):
    """``perf_counter`` reads the given instants in turn."""
    def set_ticks(*ticks):
        it = iter(ticks)
        monkeypatch.setattr(tracing, "perf_counter", lambda: next(it))
    return set_ticks


def test_nested_spans_share_the_call_and_self_time(clock):
    rec = tracing.Recorder()
    # root [0, 10] holds a [1, 3] and b [4, 7]; b holds c [5, 6]
    clock(0, 1, 3, 4, 5, 6, 7, 10)
    with rec.span("root"):
        with rec.span("a"):
            pass
        with rec.span("b"):
            with rec.span("c"):
                pass
    ring = {r.name: r for r in rec.snapshot()["ring"]}
    root = ring["root"]
    assert root.parent is None and root.call == root.id
    assert ring["a"].parent == ring["b"].parent == root.id
    assert ring["c"].parent == ring["b"].id
    assert {r.call for r in ring.values()} == {root.id}
    assert (ring["c"].start_s, ring["c"].end_s) == (5, 6)

    def self_s(name):
        r = ring[name]
        return (r.end_s - r.start_s) - sum(
            c.end_s - c.start_s for c in ring.values() if c.parent == r.id)
    assert self_s("root") == 10 - 2 - 3
    assert self_s("b") == 3 - 1
    assert self_s("a") == 2
    # the ring is in order of the spans' ends
    assert [r.name for r in rec.snapshot()["ring"]] == ["a", "c", "b",
                                                        "root"]
    # a second root is a call of its own
    clock(20, 21)
    with rec.span("root"):
        pass
    last = rec.snapshot()["ring"][-1]
    assert last.call == last.id != root.id


def test_a_span_that_raises_is_recorded_and_closed(clock):
    rec = tracing.Recorder()
    clock(0, 1, 2, 3)
    with pytest.raises(ValueError):
        with rec.span("root"):
            with rec.span("inner"):
                raise ValueError("from inside")
    ring = rec.snapshot()["ring"]
    assert [(r.name, r.start_s, r.end_s) for r in ring] == [
        ("inner", 1, 2), ("root", 0, 3)]
    # the failed call left no span open: the next is a root of its own
    clock(5, 6)
    with rec.span("next"):
        pass
    last = rec.snapshot()["ring"][-1]
    assert last.parent is None and last.call == last.id


def test_ring_stays_bounded(monkeypatch):
    assert tracing.RECORDER._ring.maxlen == tracing.RING
    monkeypatch.setattr(tracing, "RING", 8)
    rec = tracing.Recorder()
    for i in range(20):
        with rec.span(f"s{i}"):
            pass
    ring = rec.snapshot()["ring"]
    assert [r.name for r in ring] == [f"s{i}" for i in range(12, 20)]


def test_counters_snapshot_and_reset():
    rec = tracing.Recorder()
    rec.count("a")
    rec.count("a", 4)
    rec.count("b", 2.5)
    assert rec.counter("a") == 5 and rec.counter("never") == 0
    with rec.span("s"):
        pass
    snap = rec.snapshot()
    assert snap["counters"] == {"a": 5, "b": 2.5}
    assert [r.name for r in snap["ring"]] == ["s"]
    # a snapshot is a copy
    snap["counters"]["a"] = 0
    snap["ring"].clear()
    assert rec.counter("a") == 5
    assert len(rec.snapshot()["ring"]) == 1
    rec.reset()
    assert rec.snapshot() == {"counters": {}, "ring": []}


def test_trace_count_is_the_sim_traces_counter():
    assert trace_count() == tracing.counter("sim.traces")
    before = trace_count()
    SimEngine("ticket", n_threads=2).run(seed=3, workload=Workload(0, True,
                                                                    50))
    assert trace_count() == tracing.counter("sim.traces") == before + 1


def _after(mark: int) -> list:
    return [r for r in tracing.snapshot()["ring"] if r.id > mark]


def _mark() -> int:
    return max((r.id for r in tracing.snapshot()["ring"]), default=0)


def test_one_grid_call_is_one_span_tree_with_its_counts():
    T, steps, seeds, topos = 4, 400, [3, 8], ["smp:4", "numa:2x2"]
    eng = SimEngine("reciprocating", n_threads=T)
    for compiles in (1, 0):
        mark, c0 = _mark(), tracing.snapshot()["counters"]
        g = eng.grid(seeds=seeds, topologies=topos,
                     workloads=[Workload(0, True, steps)], threads=[T])
        assert g.compiles == compiles
        ring = _after(mark)
        roots = [r for r in ring if r.parent is None]
        assert [r.name for r in roots] == ["sim.grid"]
        root = roots[0]
        assert [r.name for r in ring if r.parent == root.id] == [
            "sim.lower", "sim.dispatch", "sim.wait", "sim.summarize"]
        assert {r.call for r in ring} == {root.id}
        assert all(root.start_s <= r.start_s <= r.end_s <= root.end_s
                   for r in ring)
        c1 = tracing.snapshot()["counters"]
        assert c1 == {**c0, "sim.traces": c0.get("sim.traces", 0)
                      + compiles}
        assert len(g) == len(topos)


def test_workloads_share_one_lowered_batch():
    """A grid over two workloads lowers and uploads its batch once and
    gives the cells that one grid call per workload gives."""
    eng = SimEngine("mcs", n_threads=4)
    wls = [Workload(0, True, 300), Workload(40, "ro", 300)]
    kw = {"seeds": [1, 6], "topologies": ["smp:4", "numa:2x2"]}
    mark = _mark()
    both = eng.grid(workloads=wls, **kw)
    assert [r.name for r in _after(mark) if r.parent is not None] == [
        "sim.lower"] + ["sim.dispatch", "sim.wait", "sim.summarize"] * 2
    apart = [c for wl in wls for c in eng.grid(workloads=[wl], **kw)]
    assert len(both) == len(apart) == 4
    for a, b in zip(both, apart):
        assert (a.topology, a.workload) == (b.topology, b.workload)
        for f in ("throughput", "episodes", "latency", "unfairness",
                  "aborts", "preempts"):
            assert getattr(a.result, f) == getattr(b.result, f)
        np.testing.assert_array_equal(a.result.admissions,
                                      b.result.admissions)


def test_concurrent_spans_keep_their_own_parents():
    """Spans opened in threads nest within their own thread only, and no
    count is lost."""
    rec = tracing.Recorder()
    gate = threading.Barrier(8, timeout=30)

    def work():
        gate.wait()
        for _ in range(200):
            with rec.span("outer"):
                with rec.span("inner"):
                    rec.count("n")
    threads = [threading.Thread(target=work) for _ in range(8)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    snap = rec.snapshot()
    assert snap["counters"]["n"] == 1600
    assert sum(r.name == "outer" for r in snap["ring"]) == 1600
    by_id = {r.id: r for r in snap["ring"]}
    for r in snap["ring"]:
        if r.name == "inner" and r.parent in by_id:
            assert by_id[r.parent].name == "outer"
            assert by_id[r.parent].call == r.call
        if r.name == "outer":
            assert r.parent is None

