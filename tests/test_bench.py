"""Tests for the repro.bench harness: schema round-trip, registry
coverage of every lock program, bypass instrumentation bounds, CLI, and a
tiny end-to-end `paper` sweep."""
import json
import os

import pytest

from repro.bench import (
    BenchConfig, SCHEMA_VERSION, load_result, names, run_suite, save_result,
    validate_result,
)
from repro.bench import schema, sweep
from repro.bench.cli import main as cli_main
from repro.bench.report import render_markdown
from repro.bench.suites import FIG1_ALGS
from repro.core.locks.programs import PROGRAMS


def _sample_doc():
    doc = schema.new_result("unit", config={"quick": True})
    doc["experiments"] = [
        schema.sweep_experiment(
            "s", "a sweep", "threads",
            [{"label": "mcs",
              "points": [{"threads": 1, "throughput": 2.5},
                         {"threads": 2, "throughput": 1.5}]}]),
        schema.table_experiment("t", "a table", ["lock", "miss"],
                                [{"lock": "clh", "miss": 5.0}]),
        schema.scalars_experiment("v", "scalars", {"cycle": "ABBA",
                                                   "unfair": 2.0}),
        schema.hist_experiment("h", "hist", ["0", "1", "2+"],
                               [{"label": "fifo", "counts": [10, 0, 0]}]),
    ]
    return doc


def test_schema_roundtrip(tmp_path):
    doc = _sample_doc()
    assert validate_result(doc) == []
    p = str(tmp_path / "r.json")
    save_result(doc, p)
    back = load_result(p)
    assert back == json.loads(json.dumps(doc))   # float-safe equality
    assert back["schema"] == SCHEMA_VERSION


@pytest.mark.parametrize("mutate", [
    lambda d: d.pop("schema"),
    lambda d: d.__setitem__("experiments", "nope"),
    lambda d: d["experiments"][0].__setitem__("kind", "mystery"),
    lambda d: d["experiments"][0]["series"][0]["points"].clear(),
    lambda d: d["experiments"][3]["series"][0].__setitem__("counts", [1]),
    lambda d: d["experiments"].append(dict(d["experiments"][1])),  # dup name
])
def test_schema_rejects_invalid(mutate, tmp_path):
    doc = _sample_doc()
    mutate(doc)
    assert validate_result(doc) != []
    with pytest.raises(ValueError):
        save_result(doc, str(tmp_path / "bad.json"))


def test_registry_exposes_every_lock_program():
    # the paper suite's Fig. 1 sweeps must cover the full program roster
    assert set(FIG1_ALGS) == set(PROGRAMS)
    for suite in ("paper", "mutexbench", "coherence", "fairness",
                  "atomics", "kvstore", "residency", "scheduler",
                  "serve", "kernels", "roofline", "locks-ext",
                  "topology"):
        assert suite in names()


def test_cli_list_programs_and_suites(capsys):
    assert cli_main(["list", "--programs"]) == 0
    out = capsys.readouterr().out
    assert "# lock programs" in out and "# suites" not in out
    for name in PROGRAMS:
        assert name in out
    assert "doorway:" in out and "(new variant)" in out
    # default stays suites-only (backwards compatible)
    assert cli_main(["list"]) == 0
    out = capsys.readouterr().out
    assert "# suites" in out and "# lock programs" not in out
    # both flags => both catalogues
    assert cli_main(["list", "--suites", "--programs"]) == 0
    out = capsys.readouterr().out
    assert "# suites" in out and "# lock programs" in out
    assert "locks-ext" in out


def test_cli_list_properties_matrix(capsys):
    # structural-only verified-property matrix (no model check => fast)
    assert cli_main(["list", "--properties"]) == 0
    out = capsys.readouterr().out
    assert "model_check" in out
    for name in PROGRAMS:
        assert name in out
    assert "✓ own cell" in out          # reciprocating's spin column
    assert "✗ declared shared" in out   # ticket's declared opt-out


def test_locks_ext_suite_tiny():
    doc = run_suite("locks-ext", TINY)
    assert validate_result(doc) == []
    by = {e["name"]: e for e in doc["experiments"]}
    labels = {s["label"] for s in by["locksext_sweep"]["series"]}
    assert {"hapax", "fissile", "spin_then_park"} <= labels
    prof = {r["lock"]: r for r in by["locksext_profile"]["rows"]}
    assert prof["ticket"]["bypass_bound"] <= 2       # FIFO stays bounded
    assert all("spec_steps" in r for r in by["locksext_profile"]["rows"])
    assert len(by["locksext_park"]["rows"]) >= 3
    assert "| lock |" in render_markdown(doc)


def test_topology_suite_tiny():
    doc = run_suite("topology", TINY)
    assert validate_result(doc) == []
    by = {e["name"]: e for e in doc["experiments"]}
    rows = by["topology_grid"]["rows"]
    assert {r["lock"] for r in rows} == set(PROGRAMS)
    machines = {r["topology"] for r in rows}
    assert any(m.startswith("smp") for m in machines)
    assert any(m.startswith("numa") for m in machines)
    assert any(m.startswith("ccx") for m in machines)
    # SMP never produces remote misses; NUMA machines do for queue locks
    for r in rows:
        if r["topology"].startswith("smp"):
            assert r["remote_per_episode"] == 0.0, r
    # the batching contract rides in the document itself
    stats = by["topology_compile"]["values"]
    assert stats["compiles_per_grid"] <= 1.0
    assert by["topology_remote_scaling"]["series"]
    assert {r["placement"] for r in by["topology_placement"]["rows"]} \
        == {"contiguous", "interleaved"}


def test_cli_list_topologies(capsys):
    assert cli_main(["list", "--topologies"]) == 0
    out = capsys.readouterr().out
    assert "# machine topologies" in out and "# suites" not in out
    for name in ("epyc-2s", "xeon-4s", "m2-ultra", "smp:N", "numa:KxP"):
        assert name in out


def test_cli_list_backends(capsys):
    assert cli_main(["list", "--backends"]) == 0
    out = capsys.readouterr().out
    assert "# execution backends" in out and "# suites" not in out
    for name in ("sim", "pallas-interpret", "pallas-device"):
        assert name in out
    # CPU CI: the interpret fallback must probe as available
    assert "pallas-interpret  available" in out


def test_measured_suite_tiny():
    cfg = BenchConfig(threads=(2, 3), n_steps=250, n_replicas=1,
                      verbose=False, quick=True, interpret=True,
                      algs=("reciprocating", "ticket"))
    doc = run_suite("measured", cfg)
    assert validate_result(doc) == []
    by = {e["name"]: e for e in doc["experiments"]}
    backs = {r["name"] for r in by["measured_backends"]["rows"]}
    assert backs == {"sim", "pallas-interpret", "pallas-device"}
    series = {s["label"]: s for s in by["measured_fig1a"]["series"]}
    assert set(series) == {"reciprocating", "ticket"}
    for s in series.values():
        for p in s["points"]:
            assert p["collisions"] == 0
            assert p["episodes"] > 0
    # the agreement gate: both order and CS counts, zero ME violations
    for r in by["measured_agreement"]["rows"]:
        assert r["order_match"] and r["cs_counts_match"], r
        assert r["collisions"] == 0
    fit = by["measured_calibration_fit"]["values"]
    assert fit["scale_kslice_per_kcycle"] > 0
    assert by["measured_calibration"]["rows"]
    assert "measured" in render_markdown(doc)


def test_measured_cells_cache_under_measured_kind():
    """Measured cells are content-addressed under a distinct key kind:
    a second identical call replays from the store, and the key never
    collides with a sim cell of the same program."""
    from repro.bench import cache as cachemod
    from repro.bench.measured import _measured_key, measured_cell
    from repro.core.locks.pallas_backend import resolve_ir
    from repro.core.sim.engine import Workload

    store = cachemod.get_cache()
    if not store.enabled:
        pytest.skip("experiment cache disabled")
    c1 = measured_cell("ticket", 2, 64, seed=11, interpret=True)
    s0 = store.stats.snapshot()
    c2 = measured_cell("ticket", 2, 64, seed=11, interpret=True)
    s1 = store.stats.snapshot()
    assert c2 == c1
    assert s1["hits"] == s0["hits"] + 1
    ir = resolve_ir("ticket", 2)
    key = _measured_key(ir, 2, 64, 11, True, c1["device_kind"])
    fp = cachemod.program_fingerprint(ir)
    assert key != cachemod.cell_key(fp, 2, Workload(0, True, 64),
                                    [], [], [11])


def test_bypass_bounds_match_paper():
    bins, series, stats = sweep.bypass_histograms(
        ("fifo", "lifo", "reciprocating"), n_threads=6, n_events=600)
    by = {r["policy"]: r for r in stats}
    assert by["fifo"]["max_bypass_per_wait"] == 0
    # paper §2: any single later arrival overtakes a waiter at most once
    assert by["reciprocating"]["max_bypass_by_single_thread"] <= 1
    assert by["reciprocating"]["theoretical_single_thread_bound"] == 1
    # raw LIFO starves: a waiter is still outstanding after many bypasses
    assert by["lifo"]["max_outstanding_unserved"] > 100
    labels = [s["label"] for s in series]
    assert labels == ["fifo", "lifo", "reciprocating"]
    assert all(len(s["counts"]) == len(bins) for s in series)


TINY = BenchConfig(threads=(2,), n_steps=250, n_replicas=1, verbose=False,
                   quick=True, interpret=True)


def test_paper_suite_tiny_sweep():
    doc = run_suite("paper", TINY)
    assert validate_result(doc) == []
    by_name = {e["name"]: e for e in doc["experiments"]}
    # per-lock throughput-vs-threads curves for every program
    fig1a = by_name["fig1a_max_contention"]
    assert {s["label"] for s in fig1a["series"]} == set(PROGRAMS)
    for s in fig1a["series"]:
        for p in s["points"]:
            assert p["threads"] == 2
            assert p["throughput"] >= 0
    assert {e["kind"] for e in doc["experiments"]} \
        == {"sweep", "table", "scalars", "hist"}
    # coherence table has one row per Table-1 lock
    assert len(by_name["table1_coherence"]["rows"]) == 8
    # the renderer accepts the real document
    md = render_markdown(doc)
    assert "GENERATED" in md and "fig" not in md.split("\n")[0]
    assert "| lock |" in md


def test_cli_run_report_validate(tmp_path, capsys):
    out = str(tmp_path / "BENCH_residency.json")
    rep = str(tmp_path / "RESULTS.md")
    assert cli_main(["run", "--suite", "residency", "--out", out,
                     "--quick", "--no-progress", "--report", rep]) == 0
    assert os.path.exists(out) and os.path.exists(rep)
    doc = load_result(out)
    assert doc["suite"] == "residency"
    assert cli_main(["validate", "--in", out]) == 0
    # re-render from disk
    rep2 = str(tmp_path / "R2.md")
    assert cli_main(["report", "--in", out, "--out", rep2]) == 0
    with open(rep2) as f:
        assert "Appendix C" in f.read()
