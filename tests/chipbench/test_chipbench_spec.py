"""``BENCHMARK.json`` keeps to the benchmark's contract, and every name
in it resolves to the files the harness loads by that name."""
import json
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
WIDTH = ("size", "dim", "rank", "head", "expert", "factor", "hidden",
         "intermediate", "latent", "state", "proj")
E2E = {m["name"] for m in BENCH["end_to_end"]}


def test_top_level_keys_and_run_length():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "chipbench/run.py"]
    rs = BENCH["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    # a full check of 24 cells fits its time
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200
    for p in BENCH["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p) and ".." not in p
        assert (ROOT / p).is_dir()


@pytest.mark.parametrize("c", BENCH["configs"], ids=lambda c: c["name"])
def test_config(c):
    assert NAME.match(c["name"]) and len(c["reduced"]) <= 16
    assert any(w["config"] == c["name"] for w in BENCH["workloads"])
    assert c["file"].startswith("chipbench/configs/")
    cfg = json.loads((ROOT / c["file"]).read_text())
    assert cfg["name"] == c["name"]
    for key in c["reduced"]:
        assert NAME.match(key) and key in cfg
        assert not any(w in key for w in WIDTH), key
    ref = ROOT / "chipbench" / "reference" / f"{cfg['reference']}.py"
    assert ref.exists()


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_workload(w):
    from chipbench import harness as H
    assert NAME.match(w["name"]) and w["chips"] in (1, 4)
    assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    cell = H.find_cell(w["name"], ROOT)
    assert (ROOT / "chipbench" / "drivers"
            / f"{cell.traffic['driver']}.py").exists()
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer


@pytest.mark.parametrize("m", BENCH["end_to_end"] + BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_metric(m):
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    if m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    else:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert m["moves"] in E2E and "\n" not in m["layer"]
        spec = json.loads((ROOT / "chipbench" / "metrics"
                           / f"{m['name']}.json").read_text())
        assert (ROOT / "chipbench" / "readers"
                / f"{spec['reader']}.py").exists()
    if m["name"].endswith("_roofline") or "mfu" in m["name"]:
        assert m["unit"] == "%"


@pytest.mark.parametrize("m", BENCH["per_layer"], ids=lambda m: m["name"])
def test_reader_finds_nothing_in_an_empty_record(m):
    from chipbench import harness as H
    spec = json.loads((ROOT / "chipbench" / "metrics"
                       / f"{m['name']}.json").read_text())
    reader = H.load_module(ROOT / "chipbench" / "readers"
                           / f"{spec['reader']}.py")
    assert reader.read({}, **spec.get("args", {})) is None


SERVE_PER_LAYER = {"queue_wait_p95_ms", "prefix_hit_share",
                   "prefill_step_roofline", "host_ms_per_step",
                   "compiles_in_window.serve", "decode_step_roofline",
                   "step_mfu.online", "step_mfu.ttft", "device_idle.online"}
SIM_PER_LAYER = {"compiles_in_window.sim", "sim.scan_iter_us",
                 "sim_scan_roofline", "device_idle.sim",
                 "sim.host_ms_per_call", "sim.lower_ms_per_call",
                 "sim.summarize_ms_per_call"}


@pytest.mark.parametrize("cell,e2e,per_layer", [
    ("serve.sc2-3b.repo_prefix", {"ttft_p90_ms", "itl_p95_ms", "setup_s"},
     SERVE_PER_LAYER),
    ("sim.mutexbench.randncs64", {"lock_steps_per_s", "setup_s"},
     SIM_PER_LAYER),
])
def test_each_cell_reports_its_own_metrics(cell, e2e, per_layer):
    from chipbench import harness as H
    found = H.find_cell(cell, ROOT)
    assert {m["name"] for m in found.end_to_end} == e2e
    assert {m["name"] for m in found.per_layer} == per_layer
