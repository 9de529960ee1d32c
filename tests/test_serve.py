"""Serving-stack tests (docs/SERVING.md): paged-KV pool invariants,
engine-vs-reference decode equivalence, the shared scheduler core,
per-policy starvation bounds, and the ``serve`` bench suite round-trip."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.serve.core import DrainStalled, ServeCore
from repro.serve.kv_cache import KVPoolExhausted, PagedKVPool


# ---------------------------------------------------------------------------
# paged KV pool
# ---------------------------------------------------------------------------
def test_pool_alloc_release_accounting():
    pool = PagedKVPool(8, reserve_null=True)
    assert pool.null_block == 0
    a = pool.alloc("r1", 3)
    b = pool.alloc("r2", 2)
    assert 0 not in a + b and len(set(a + b)) == 5
    assert pool.n_pinned == 5 and pool.n_free == 2
    pool.release("r1")                       # no prefix: blocks freed
    assert pool.n_pinned == 2 and pool.n_free == 5 and pool.n_cached == 0
    pool.release("r2", prefix_id=9, keep_blocks=1)
    assert pool.n_pinned == 0 and pool.n_cached == 1
    assert pool.lookup(9, 4) == [b[0]]       # first table block retained
    pool.check()


def test_pool_lru_eviction_order():
    pool = PagedKVPool(4)
    pool.insert("a", 2)
    pool.insert("b", 2)                      # pool now full
    assert pool.hit_fraction("a", 2) == 1.0  # touch: a becomes MRU
    pool.insert("c", 2)                      # evicts LRU = b's blocks
    assert pool.hit_fraction("b", 2) == 0.0
    assert pool.hit_fraction("a", 2) == 1.0
    assert pool.stats.evictions == 2
    pool.check()


def test_pool_pinned_never_evicted_and_exhaustion():
    pool = PagedKVPool(4)
    ids = pool.alloc("r1", 3)
    pool.insert("p", 3)                      # needs 3, only 1 free: evicts
    assert pool.hit_fraction("p", 3) < 1.0   # its own earlier entries
    for bid in ids:                          # pinned ids never recycled
        assert bid in pool.table_of("r1")
    with pytest.raises(KVPoolExhausted):
        pool.alloc("r2", 3)                  # 3 pinned + <=1 evictable
    assert pool.table_of("r2") == []         # failed alloc left no state
    pool.check()


def test_pool_prefix_sharing_refcounts():
    pool = PagedKVPool(8)
    a = pool.alloc("r1", 2)
    pool.release("r1", prefix_id=7, keep_blocks=2)
    got = pool.share("r2", 7, 2)
    assert got == a                          # copy-free: same physical ids
    pool.insert("x", 6)                      # churn: shared ids survive
    assert pool.lookup(7, 2) == a
    pool.release("r2", prefix_id=7, keep_blocks=2)
    assert pool.n_pinned == 0
    assert pool.stats.shared_hits == 2
    pool.check()


# ---------------------------------------------------------------------------
# model engine (smoke config shared across tests)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def smoke_lm():
    from repro.configs import get_config, smoke_config
    from repro.models import model as M_
    cfg = smoke_config(get_config("starcoder2-3b")).replace(
        n_layers=2, d_model=128, d_ff=256, vocab_size=256)
    params = M_.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _reference_greedy(cfg, params, prompt, n, max_seq=64):
    """Dense-cache greedy decode with full headroom (prefill right-padded
    to ``max_seq`` so generated positions never ring-wrap): the oracle
    the paged and dense-slot engines must reproduce token-for-token."""
    from repro.models import decode as D_
    from repro.sharding.ctx import trivial_ctx
    ctx = trivial_ctx()
    L = len(prompt)
    toks = np.zeros((1, max_seq), np.int32)
    toks[0, :L] = prompt
    logits, cache = jax.jit(
        lambda p, b, li: D_.prefill_step(p, b, cfg, ctx, last_index=li))(
        params, {"tokens": jnp.asarray(toks)},
        jnp.asarray([L - 1], jnp.int32))
    cache["pos"] = jnp.asarray([L], jnp.int32)   # pads are future slots
    out, tok = [], jnp.argmax(logits, -1).astype(jnp.int32)
    step = jax.jit(lambda p, c, t: D_.decode_step(p, c, t, cfg, ctx))
    for _ in range(n):
        out.append(int(tok[0]))
        logits, cache = step(params, cache, tok)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
    return out


@pytest.mark.parametrize("mode", ["paged", "paged_chunked", "dense"])
def test_engine_matches_reference(smoke_lm, mode):
    from repro.serve.engine import GenRequest, InferenceEngine
    cfg, params = smoke_lm
    prompt = np.random.default_rng(7).integers(1, 97, 11, dtype=np.int32)
    ref = _reference_greedy(cfg, params, prompt, 6)
    kw = {"max_batch": 2, "max_seq": 64, "block_size": 8}
    if mode == "paged_chunked":
        kw["prefill_chunk"] = 4              # prefill rides the decode loop
    if mode == "dense":
        kw["paged"] = False                  # force the fallback executor
    eng = InferenceEngine(cfg, params, **kw)
    assert eng.paged == (mode != "dense")
    eng.submit(GenRequest(rid=0, tokens=prompt, max_new=6))
    done = eng.run()
    assert done[0].out == ref


def test_engine_early_exit_and_per_step_admission(smoke_lm):
    """A short request frees its slot mid-run; the queued request is
    admitted into it while the long request is still decoding."""
    from repro.serve.engine import GenRequest, InferenceEngine
    cfg, params = smoke_lm
    rng = np.random.default_rng(5)
    eng = InferenceEngine(cfg, params, policy="fifo", max_batch=2,
                          max_seq=64, block_size=8)
    long = GenRequest(rid=0, tokens=rng.integers(1, 97, 8, np.int32),
                      max_new=20)
    short = GenRequest(rid=1, tokens=rng.integers(1, 97, 8, np.int32),
                       max_new=2)
    queued = GenRequest(rid=2, tokens=rng.integers(1, 97, 8, np.int32),
                        max_new=2)
    for r in (long, short, queued):
        eng.submit(r)
    done = eng.run()
    assert [r.rid for r in done] == [1, 2, 0]
    assert queued.admitted < long.finished   # continuous, not segmented
    assert len(long.out) == 20 and len(short.out) == 2
    # early exit: finished slots stop burning decode compute
    assert eng.counters.slot_steps < 3 * 20


def test_engine_prefix_sharing_end_to_end(smoke_lm):
    from repro.serve.engine import GenRequest, InferenceEngine
    cfg, params = smoke_lm
    rng = np.random.default_rng(9)
    eng = InferenceEngine(cfg, params, max_batch=2, max_seq=64,
                          block_size=8)
    shared = rng.integers(1, 97, 16, dtype=np.int32)
    r1 = GenRequest(rid=0, tokens=shared, max_new=4, prefix_id=3)
    eng.submit(r1)
    first = eng.run()[0].out
    r2 = GenRequest(rid=1, tokens=shared, max_new=4, prefix_id=3)
    eng.submit(r2)
    second = eng.run()[0].out
    assert r1.prefill_hit == 0.0 and r2.prefill_hit == 1.0
    assert first == second                   # sharing never changes tokens
    eng.pool.check()


def test_misaligned_chunk_never_corrupts_shared_blocks(smoke_lm):
    """A sharer admitted with a chunk ending mid-block must not scatter
    its right-padding into the prefix blocks a concurrent request is
    still attending over."""
    from repro.serve.engine import GenRequest, InferenceEngine
    cfg, params = smoke_lm
    rng = np.random.default_rng(11)
    shared = rng.integers(1, 97, 16, dtype=np.int32)
    ref = _reference_greedy(cfg, params, shared, 12)
    eng = InferenceEngine(cfg, params, policy="fifo", max_batch=2,
                          max_seq=64, block_size=8, prefill_chunk=12)
    c = GenRequest(rid=0, tokens=shared, max_new=1, prefix_id=5,
                   arrival=0.0)           # seeds the prefix cache
    a = GenRequest(rid=1, tokens=shared, max_new=12, prefix_id=5,
                   arrival=8.0)           # pins the cached blocks
    b = GenRequest(rid=2, tokens=shared, max_new=2, prefix_id=5,
                   arrival=12.0)          # admitted while A is decoding
    for r in (c, a, b):
        eng.submit(r)
    eng.run()
    assert a.prefill_hit == 1.0 and b.prefill_hit == 1.0
    assert b.admitted < a.finished        # B's chunk landed mid-A
    assert a.out == ref                   # ...without perturbing A


def test_idle_slot_never_writes_released_blocks(smoke_lm):
    """A freed slot keeps decoding as a dummy row; its stale block table
    must not let it scatter garbage into the retiree's now-cached prefix
    blocks while the slot sits empty."""
    from repro.serve.engine import GenRequest, InferenceEngine
    cfg, params = smoke_lm
    rng = np.random.default_rng(13)
    shared = rng.integers(1, 97, 8, dtype=np.int32)
    eng = InferenceEngine(cfg, params, policy="fifo", max_batch=2,
                          max_seq=64, block_size=8)
    a = GenRequest(rid=0, tokens=shared, max_new=2, prefix_id=6,
                   arrival=0.0)
    filler = GenRequest(rid=1, tokens=rng.integers(1, 97, 8, np.int32),
                        max_new=16, arrival=0.0)   # keeps the run alive
    late = GenRequest(rid=2, tokens=shared, max_new=2, prefix_id=6,
                      arrival=10.0)                # slot idles 0..10
    for r in (a, filler, late):
        eng.submit(r)
    while a.finished < 0:               # drive until A retires...
        eng.core.step()
    bid = eng.pool.lookup(6, 1)[0]      # ...caching its prefix block
    snap = np.asarray(eng.executor.k_pool[bid])
    eng.core.step()                     # A's old slot decodes as a dummy
    eng.core.step()                     # row while it sits empty
    np.testing.assert_array_equal(       # cached block must be pristine
        snap, np.asarray(eng.executor.k_pool[bid]))
    eng.run()
    assert late.prefill_hit == 1.0      # served from A's cached block
    assert late.out == a.out


def test_duplicate_valued_requests_do_not_collide(smoke_lm):
    """Requests compare by identity, not field equality: two submissions
    with identical rid/prompt must both complete."""
    from repro.serve.engine import GenRequest, InferenceEngine
    cfg, params = smoke_lm
    prompt = np.arange(1, 9, dtype=np.int32)
    eng = InferenceEngine(cfg, params, max_batch=2, max_seq=64,
                          block_size=8)
    eng.submit(GenRequest(rid=0, tokens=prompt, max_new=3))
    eng.submit(GenRequest(rid=0, tokens=prompt.copy(), max_new=3))
    done = eng.run()
    assert len(done) == 2 and done[0].out == done[1].out


def test_sim_and_engine_share_scheduler_core(smoke_lm):
    """The acceptance property: both frontends drive serve.core."""
    from repro.serve.engine import InferenceEngine
    from repro.serve.scheduler import ContinuousBatcher
    cfg, params = smoke_lm
    sim = ContinuousBatcher(max_batch=2)
    eng = InferenceEngine(cfg, params, max_batch=2, max_seq=64)
    assert type(sim.core) is ServeCore and type(eng.core) is ServeCore
    assert type(sim.core.queue) is type(eng.core.queue)
    assert type(sim.pool) is type(eng.pool) is PagedKVPool


# ---------------------------------------------------------------------------
# starvation bounds + drain behaviour (sim frontend)
# ---------------------------------------------------------------------------
def test_starvation_bound_by_policy():
    """Reciprocating's bounded bypass keeps the worst wait near FIFO's;
    raw LIFO starves its tail (unbounded bypass)."""
    from repro.bench.suites import scheduler_drive
    waits = {p: scheduler_drive(p, n_req=200, mean_gap=8.0,
                                seed=0)["max_wait"]
             for p in ("fifo", "reciprocating", "lifo")}
    assert waits["fifo"] <= waits["reciprocating"] <= waits["lifo"]
    assert waits["lifo"] > 2.0 * waits["reciprocating"]


def test_drain_raises_instead_of_silent_return():
    from repro.serve.scheduler import ContinuousBatcher, Request
    sched = ContinuousBatcher(max_batch=1)
    sched.submit(Request(rid=0, arrival=0.0, prefix_id=0, prefix_blocks=2,
                         prompt_blocks=2, decode_tokens=500))
    with pytest.raises(DrainStalled):
        sched.drain(max_steps=10)


def test_request_work_fields_are_declared():
    """_prefill_left/_decode_left are dataclass fields, not step()-time
    attribute injection."""
    import dataclasses

    from repro.serve.scheduler import Request
    names = {f.name for f in dataclasses.fields(Request)}
    assert {"_prefill_left", "_decode_left"} <= names


# ---------------------------------------------------------------------------
# serve bench suite
# ---------------------------------------------------------------------------
def test_serve_suite_schema_roundtrip(tmp_path):
    from repro.bench import BenchConfig, load_result, run_suite, save_result
    from repro.bench.report import render_markdown
    doc = run_suite("serve", BenchConfig(quick=True, verbose=False))
    p = str(tmp_path / "serve.json")
    save_result(doc, p)                      # refuses invalid documents
    back = load_result(p)
    by_name = {e["name"]: e for e in back["experiments"]}
    sweep = by_name["serve_policy_load"]
    assert [s["label"] for s in sweep["series"]] == [
        "fifo", "lifo", "reciprocating", "reciprocating_mitigated"]
    for s in sweep["series"]:
        for pt in s["points"]:
            assert pt["throughput_rps"] > 0
            assert 0.0 <= pt["prefix_hit_rate"] <= 1.0
    assert {r["policy"] for r in by_name["serve_pool"]["rows"]} \
        == {s["label"] for s in sweep["series"]}
    md = render_markdown(back)
    assert "Serving" in md and "offered_load" in md


# ---------------------------------------------------------------------------
# serving launcher
# ---------------------------------------------------------------------------
def test_launch_serve_shared_prefix_traffic():
    """The launcher's engine and traffic at smoke widths: every request
    gets its max_new tokens, and every request after its family's first
    reuses the family's cached prefix blocks."""
    from repro.configs import get_config, smoke_config
    from repro.launch import serve

    cfg = smoke_config(get_config("starcoder2-3b"))
    eng = serve.build_engine(cfg, seed=0, policy="reciprocating",
                             max_batch=4, max_seq=128)
    reqs = serve.shared_prefix_requests(
        n=6, vocab=cfg.vocab_size, prefix_len=48, suffix_max=16,
        max_new=8, seed=0)
    for r in reqs:
        eng.submit(r)
    done = eng.run()
    assert sorted(r.rid for r in done) == list(range(6))
    for r in reqs:
        assert 4 <= r.max_new <= 8 and len(r.out) == r.max_new
        assert r.prefix_id == r.rid % 2 and r.prefix_len == 48
    assert [r.prefill_hit > 0 for r in reqs] == [False] * 2 + [True] * 4


def test_launch_serve_smoke_is_opt_in():
    from repro.launch.serve import build_parser
    assert build_parser().parse_args(["--arch", "x"]).smoke is False
    assert build_parser().parse_args(["--arch", "x", "--smoke"]).smoke
