"""The sim engine's own spans (``repro.tracing``) as the benchmark reads
them: ``program_span_per`` and ``phases.py`` on spans worked out by hand,
the reader after a whole run (the chip check skipped), and the spans
beside the benchmark's ``cb.*`` spans in a CPU profiler trace."""
import json
import sys
import time
from pathlib import Path

import pytest

from chipbench import harness as H

ROOT = Path(__file__).resolve().parents[2]
PHASES = ("sim.lower", "sim.dispatch", "sim.wait", "sim.summarize")
METRICS = ("sim.host_ms_per_call", "sim.lower_ms_per_call",
           "sim.summarize_ms_per_call")


def reader():
    return H.load_module(ROOT / "chipbench" / "readers"
                         / "program_span_per.py")


def metric_args(name: str) -> dict:
    return json.loads((ROOT / "chipbench" / "metrics"
                       / f"{name}.json").read_text())["args"]


def calls_by_hand():
    """A warm-up call [0, 10], then the window's calls [20, 30], [40, 52]
    and [60, 80], each split lower, dispatch, wait, summarize."""
    from repro.tracing import SpanRecord as R
    ring, i = [], 0
    for root, cuts in ((0, (1, 2, 9, 10)), (20, (21, 22, 28, 30)),
                       (40, (42, 43, 49, 52)), (60, (70, 71, 77, 80))):
        call, i = i + 1, i + 1
        t = root
        for name, end in zip(PHASES, cuts):
            i += 1
            ring.append(R(name, t, end, i, call, call))
            t = end
        ring.append(R("sim.grid", root, t, call, None, call))
    return ring


def test_program_span_per_by_hand():
    rd = reader()
    ring = calls_by_hand()
    got = rd.window(ring, 3, "sim.grid", kept=4096)
    assert sorted(got) == [6, 11, 16]
    assert all(len(rs) == 5 for rs in got.values())
    # medians of (10 - 6, 12 - 6, 20 - 6), of (1, 2, 10), of (2, 3, 3)
    assert rd.per_call(got, ["sim.grid"], ["sim.wait"]) == 6
    # the host's own time: medians of (10 - 6 - 1, 12 - 6 - 1, 20 - 6 - 1)
    host = metric_args("sim.host_ms_per_call")
    assert rd.per_call(got, host["spans"], host["minus"]) == 5
    assert rd.per_call(got, ["sim.lower"]) == 2
    assert rd.per_call(got, ["sim.summarize"]) == 3
    assert rd.per_call(rd.window(ring, 2, "sim.grid", 4096),
                       ["sim.lower"]) == (2 + 10) / 2
    # more calls than the ring's roots: nothing to read
    assert rd.window(ring, 5, "sim.grid", kept=4096) is None
    # a full ring that dropped the window's first lower span
    assert rd.window(ring[6:], 3, "sim.grid", kept=len(ring) - 6) is None
    # a full ring whose oldest span ended before the window began
    assert rd.window(ring[4:], 3, "sim.grid", kept=len(ring) - 4) == got


def test_reader_finds_nothing_in_a_program_without_spans(monkeypatch):
    import repro
    monkeypatch.delattr(repro, "tracing", raising=False)
    monkeypatch.setitem(sys.modules, "repro.tracing", None)
    record = {"counters": {"grid_calls": 2}}
    for m in METRICS:
        assert reader().read(record, **metric_args(m)) is None


def small_cell():
    cell = H.cell_from_files("sim.mutexbench.randncs64",
                             "mutexbench-randncs-t64", "round_robin_grid")
    cell.config.update(threads=8, steps_per_call=1500,
                       locks=["reciprocating", "mcs"], seeds_per_topology=4)
    return cell


def test_run_without_chip_reads_the_programs_spans():
    from repro import tracing
    cell = small_cell()
    out = cell.driver.run(H.RunContext(cell, 2**33 + 5, 1.0, False,
                                       time.perf_counter(), None))
    assert out.correct
    n = out.record["counters"]["grid_calls"]
    calls = reader().window(tracing.snapshot()["ring"], n, "sim.grid",
                            tracing.RING)
    assert len(calls) == n
    for spans in calls.values():
        assert sorted(r.name for r in spans) == sorted(("sim.grid",)
                                                       + PHASES)
    got = {m: reader().read(out.record, **metric_args(m)) for m in METRICS}
    assert all(v > 0 for v in got.values()), got
    assert got["sim.host_ms_per_call"] >= max(
        got["sim.lower_ms_per_call"], got["sim.summarize_ms_per_call"])


def test_program_spans_land_in_a_profiler_trace_beside_the_benchmarks(
        tmp_path):
    import jax
    from jax.profiler import ProfileData

    from chipbench import trace as T
    from repro.core.sim.engine import SimEngine, Workload
    eng = SimEngine("ticket", n_threads=4)
    kw = {"seeds": [2, 9], "topologies": ["smp:4"],
          "workloads": [Workload(0, True, 200)]}
    eng.grid(**kw)                      # compile outside the trace
    spans = H.Spans(annotate=True)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with spans.span("sim.grid"):
            eng.grid(**kw)
    finally:
        jax.profiler.stop_trace()
    path = T.find_xplane(str(tmp_path))
    assert [s[0] for s in T.load(path).spans] == ["cb.sim.grid"]
    host = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    host[ev.name] = (ev.start_ns, ev.start_ns
                                     + ev.duration_ns)
    outer = host["cb.sim.grid"]
    inner = host["repro.sim.grid"]
    assert outer[0] <= inner[0] <= inner[1] <= outer[1]
    for name in PHASES:
        a, b = host["repro." + name]
        assert inner[0] <= a <= b <= inner[1], name


def test_phases_split_calls_and_name_idle_time_by_hand():
    from chipbench import phases
    rows = phases.split(calls_by_hand())
    assert [r["call"] for r in rows] == [10e3, 10e3, 12e3, 20e3]
    assert rows[3]["sim.lower"] == 10e3 and rows[3]["sim.wait"] == 6e3
    assert all(abs(r["other"]) < 1e-9 for r in rows)
    # one call [0, 10]: lower [0, 2], dispatch [2, 3], wait [3, 8],
    # summarize [8, 10]; the device runs [1.5, 8.2] and [9, 9.1]
    spans = [("repro.sim.grid", 0, 10), ("repro.sim.lower", 0, 2),
             ("repro.sim.dispatch", 2, 3), ("repro.sim.wait", 3, 8),
             ("repro.sim.summarize", 8, 10)]
    devices = {"/device:TPU:0": [(1.5, 8.2), (9, 9.1)]}
    (row,) = phases.idle_by_phase(devices, spans, min_gap_s=0.5)
    assert row["call_ms"] == 10e3
    assert row["busy_ms"] == pytest.approx(6.8e3)
    want = {"repro.sim.grid": 3.2e3, "repro.sim.lower": 1.5e3,
            "repro.sim.dispatch": 0, "repro.sim.wait": 0,
            "repro.sim.summarize": 1.7e3}
    assert row["idle_ms"] == pytest.approx(want)
    assert row["gaps_ms"] == [["repro.sim.lower", 1500.0],
                              ["repro.sim.summarize", 900.0],
                              ["repro.sim.summarize", 800.0]]
