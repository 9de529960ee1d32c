"""Whole serving runs (the chip check skipped) at smoke widths: the
open-loop and saturated modes, the control, and the timed path broken
underneath."""
import time

import numpy as np
import pytest

from chipbench import harness as H

CELL = "serve.sc2-3b.repo_prefix"


def small_cell(**traffic):
    cell = H.cell_from_files(CELL, "starcoder2-3b", "repo_prefix_burst")
    cell.config.update(hidden_size=128, num_hidden_layers=2,
                       num_attention_heads=4, num_key_value_heads=2,
                       head_dim=32, intermediate_size=256, vocab_size=512)
    cell.config["serving"].update(max_batch=4, max_seq=256, pool_blocks=200)
    cell.traffic.update(rate_per_s=10.0, shared_blocks=[1, 3],
                        unique_blocks=[1, 2], output_median=4, output_cap=8,
                        warmup_requests=8, reference_tokens=40,
                        reference_requests=12, reference_min_tokens=10,
                        served_logit_error_limit=0.02)
    cell.traffic.update(traffic)
    return cell


def run(cell, seconds=2.0):
    ctx = H.RunContext(cell, 2**32 + 3, seconds, False, time.perf_counter(),
                       None)
    return cell.driver.run(ctx)


@pytest.mark.parametrize("mode", ["open_loop", "saturated"])
def test_run_without_chip_is_correct(mode):
    out = run(small_cell(mode=mode, saturated_requests=24))
    assert out.correct, [(c.name, c.value) for c in out.checks]
    e = out.end_to_end
    assert e["ttft_p95_ms"] > 0 and e["itl_p95_ms"] > 0
    assert e["tokens_per_s"] > 0 and out.failed == 0
    assert out.record["counters"]["compiles_in_window"] == 0


def test_fp8_control_reads_wider_gaps_than_the_program():
    import chipbench.readings as R
    cell = small_cell()
    rows = R.serve_control(cell, R.seeds_for(CELL, 2), 1.5, ["fp8"])
    for row in rows:
        assert row["tokens"] > 0 and row["unfinished"] == 0
        assert row["control_error.fp8"] > row["served_error"]


def _kv_unchanged(monkeypatch):
    from repro.models import decode as D_
    real = D_.paged_decode_step

    def keep(params, k_pool, v_pool, *a, **k):
        logits, _, _ = real(params, k_pool, v_pool, *a, **k)
        return logits, k_pool, v_pool
    monkeypatch.setattr(D_, "paged_decode_step", keep)


def _half_batch(monkeypatch):
    from repro.serve.engine import PagedModelExecutor
    real = PagedModelExecutor._decode_batch

    def half(self, toks, poss):
        logits = np.array(real(self, toks, poss))
        B = len(logits)
        logits[B // 2:] = logits[:B - B // 2]
        return logits
    monkeypatch.setattr(PagedModelExecutor, "_decode_batch", half)


def _token_altered(monkeypatch):
    from repro.serve.engine import PagedModelExecutor
    real = PagedModelExecutor._decode_batch

    def shifted(self, toks, poss):
        return np.roll(real(self, toks, poss), 1, axis=-1)
    monkeypatch.setattr(PagedModelExecutor, "_decode_batch", shifted)


@pytest.mark.parametrize("fault", [_kv_unchanged, _half_batch,
                                   _token_altered])
def test_broken_timed_path_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    # short prompts and long outputs, so that the generated tokens' KV
    # is much of every context
    out = run(small_cell(mode="saturated", saturated_requests=24,
                         shared_blocks=[1, 1], unique_blocks=[1, 1],
                         output_median=16, output_cap=24))
    assert not out.correct


@pytest.mark.parametrize("unset", ["rate_per_s", "served_logit_error_limit"])
def test_a_traffic_without_chip_readings_is_refused(unset):
    cell = small_cell()
    del cell.traffic[unset]
    with pytest.raises(H.BenchError, match=unset):
        run(cell)


def test_reset_drops_the_requests_a_cut_drain_left():
    from chipbench import gen
    cell = small_cell(mode="saturated", saturated_requests=24,
                      output_median=8, output_cap=8)
    drv, cfg, tr = cell.driver, cell.config, cell.traffic
    srv = drv.Server(cfg, tr, 5, H.Spans(), block_admits=False)
    window = gen.schedule(tr, cfg["vocab_size"], 5, 0.5)
    srv.set_up(5, window)
    srv.tr = dict(tr, drain_limit_s=0)
    srv.serve(window, 0.05)
    ex = srv.eng.executor
    assert any(s is not None for s in ex.slots)
    srv.reset(6)
    srv.tr = tr
    assert all(s is None for s in ex.slots) and not ex._of
    window = gen.schedule(tr, cfg["vocab_size"], 6, 0.5)
    srv.set_up(6, window)
    w = srv.serve(window, 0.5)
    assert all(len(g.out) == g.max_new for g in w["reqs"].values())


def test_a_kind_added_as_two_files_serves_the_cell(tmp_path, monkeypatch):
    # a kind and a reference that no file of chipbench/ names, found by
    # the configuration's keys alone
    for folder, attr in (("kinds", "KINDS"), ("reference", "REFERENCES")):
        (tmp_path / folder).mkdir()
        (tmp_path / folder / "dense_lm_copy.py").write_text(
            (H.HERE / folder / "dense_lm.py").read_text())
        monkeypatch.setattr(H, attr, tmp_path / folder)
    cell = small_cell()
    cell.config.update(kind="dense_lm_copy", reference="dense_lm_copy")
    out = run(cell)
    assert out.correct, [(c.name, c.value) for c in out.checks]
    assert out.record["counters"]["decode_flops"] > 0
    for load in (H.load_kind, H.load_reference):
        assert load(cell.config).__file__.startswith(str(tmp_path))


@pytest.mark.parametrize("key", ["kind", "reference"])
def test_a_kind_or_reference_with_no_file_is_refused(key):
    cell = small_cell()
    cell.config[key] = "no_such_" + key
    with pytest.raises(H.BenchError, match=f"no_such_{key}.py"):
        run(cell)
