"""Driver of serving traffic (``open_loop`` or ``saturated``) for a
serve kind's configuration, through the program's ``InferenceEngine``.
What it knows of the architecture comes from the kind
(``harness.load_kind``: the program's config, the work counts, the pool
arrays) and the plain reference (``harness.load_reference``).

Set-up draws the weights (``weights.draw``), builds the engine at the
configuration's serving settings, then warms every shape the run will
use: each prefill bucket of the window's and the warm-up's prompts, the
eager slices and pool scatters of their admissions, and the decode step.
It then serves the warm-up stretch (the same mix from a stream of its
own, each request cut to its first token), so that the prefix cache
holds what a running server holds.

The window submits each request when it falls due (``open_loop``) or
keeps ``2 x max_batch`` requests in the engine (``saturated``), and
calls ``ServeCore.step`` whenever there is work. A token's time is the
end of the step that put it on the host; a request's first token is
timed from when the request was due. After the window closes, the
engine serves what is left of the requests due in it. A sample of the
finished requests (drawn from the seed, the longest among them) is then
compared with the plain reference, teacher-forced over each prompt and
its served tokens: at every position that put a served token on the
host, the program's logits of ``LOGIT_IDS`` vocabulary ids drawn from
the seed against the reference's, as one relative L2 error over the
sample. The gaps by which served tokens' reference logits lie below the
reference's best are logged beside it: they read only where a near-tie
flips, so on a seed whose logits hold no near-tie the program and the
control both read 0 (PERF.md, section 2).

Spans (``harness.Spans``) wrap the engine's step, the executor's admit,
prefill and decode calls; counters of their work feed the per-layer
readers.
"""
from __future__ import annotations

import gc
import math
import time

import numpy as np

from chipbench import gen
from chipbench import harness as H
from chipbench import weights

#: vocabulary ids at which the program's logits are kept and compared
LOGIT_IDS = 1024


def build(cfg: dict, kind, seed: int):
    """(model config, weights, engine)."""
    from repro.models import model as M_
    from repro.serve.engine import InferenceEngine
    mcfg = kind.program_config(cfg)
    params = weights.draw(M_.abstract_params(mcfg), seed, mcfg.dtype)
    s = cfg["serving"]
    eng = InferenceEngine(mcfg, params, policy=s["policy"],
                          max_batch=s["max_batch"], max_seq=s["max_seq"],
                          block_size=s["block_size"],
                          pool_blocks=s["pool_blocks"], paged=s["paged"],
                          seed=seed % 2**31)
    return mcfg, params, eng


def logit_ids(vocab: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng([seed, 31])
    return np.sort(rng.choice(vocab, min(LOGIT_IDS, vocab), replace=False))


class Meter:
    """Wraps the executor's admit, prefill and decode calls in spans, and
    counts their work (by the kind's counts) and times while ``on``.
    With ``block`` an admit ends by waiting for its writes to the kind's
    ``POOLS``, so that their device time falls inside the admit span
    rather than the next decode's. Every prefill and decode keeps, per
    request, the logits of ``ids`` that predicted each of its tokens
    (``logits``)."""

    def __init__(self, ex, cfg: dict, kind, spans: H.Spans, block: bool,
                 ids: np.ndarray):
        self.on = False
        self.ids = ids
        self.logits: dict = {}              # rid -> [(len(ids),) ...]
        self.admitted_at: dict = {}         # rid -> host time of admit
        self.c = dict(prefills=0, prefill_tokens=0, prefill_flops=0.0,
                      prefill_bytes=0.0, decodes=0, decode_rows=0,
                      decode_flops=0.0, decode_bytes=0.0,
                      prompt_tokens=0, hit_tokens=0.0)
        admit, prefill, decode = ex.admit, ex._prefill_slot, ex._decode_batch

        def admit_(req, now):
            self.admitted_at[req.rid] = time.perf_counter()
            with spans.span("serve.admit"):
                admit(req, now)
                if block:
                    import jax
                    jax.block_until_ready(
                        [getattr(ex, p) for p in kind.POOLS])
            if self.on:
                n = len(req.tokens)
                self.c["prompt_tokens"] += n
                self.c["hit_tokens"] += req.prefill_hit * n

        def prefill_(s, n_tokens):
            with spans.span("serve.prefill"):
                out = prefill(s, n_tokens)
            self.logits[s.req.rid] = [np.asarray(out)[self.ids]]
            if self.on:
                # the prefix hit's tokens already have their KV
                cached = min(round(s.req.prefill_hit * len(s.prompt)),
                             n_tokens - 1)
                self.c["prefills"] += 1
                self.c["prefill_tokens"] += n_tokens
                self.c["prefill_flops"] += kind.prefill_flops(
                    cfg, n_tokens, cached)
                self.c["prefill_bytes"] += kind.prefill_bytes(cfg, n_tokens)
            return out

        def decode_(toks, poss):
            with spans.span("serve.decode"):
                out = decode(toks, poss)
            kept = np.asarray(out)[:, self.ids]
            for s in ex.slots:
                if s is not None:
                    self.logits[s.req.rid].append(kept[s.idx])
            if self.on:
                ctxs = [int(poss[s.idx]) for s in ex.slots if s is not None]
                self.c["decodes"] += 1
                self.c["decode_rows"] += len(ctxs)
                self.c["decode_flops"] += kind.decode_flops(cfg, ctxs)
                self.c["decode_bytes"] += kind.decode_bytes(cfg, ctxs)
            return out

        ex.admit, ex._prefill_slot, ex._decode_batch = admit_, prefill_, \
            decode_


def to_request(r: gen.Req, core_time: float):
    from repro.serve.engine import GenRequest
    return GenRequest(rid=r.rid, tokens=r.tokens, max_new=r.max_new,
                      prefix_id=r.tenant, prefix_len=r.prefix_len,
                      arrival=core_time)


def warm_shapes(eng, params, reqs, block: int, pools: tuple) -> dict:
    """Compile (or load from the compile cache) every program the
    requests' admissions use: the prefill of each bucket, and the eager
    slice and scatter into each of the executor's ``pools`` of each
    (prefilled blocks, shared blocks) pair, shared being none or the
    whole declared prefix. Scatters go to the null block. Returns how
    many of each were warmed."""
    import jax
    import jax.numpy as jnp
    ex = eng.executor
    buckets = sorted({math.ceil(len(r.tokens) / block) for r in reqs})
    pairs = sorted({(math.ceil(len(r.tokens) / block), skip)
                    for r in reqs for skip in (0, r.prefix_len // block)})
    for nb in buckets:
        toks = jnp.zeros((1, nb * block), jnp.int32)
        last = jnp.asarray([nb * block - 1], jnp.int32)
        jax.block_until_ready(ex._prefill(params, toks, last))
    # one block array per (bucket, block shape, dtype), as pools alike share
    blocks = {}
    for nb, skip in pairs:
        if skip >= nb:
            continue
        tgt = jnp.asarray(np.zeros(nb - skip, np.int32))
        for p in pools:
            pool = getattr(ex, p)
            key = (nb, pool.shape[1:], pool.dtype)
            if key not in blocks:
                blocks[key] = jnp.zeros((nb,) + key[1], key[2])
            setattr(ex, p, pool.at[tgt].set(blocks[key][skip:]))
    jax.block_until_ready([getattr(ex, p) for p in pools])
    return {"prefill_buckets": len(buckets), "admit_shapes": len(pairs)}


def drain(eng, limit_s: float) -> None:
    t0 = time.perf_counter()
    while eng.core.has_work():
        if time.perf_counter() - t0 > limit_s:
            raise H.BenchError(f"set-up traffic did not drain in {limit_s} s")
        eng.core.step()


def percentile(x, q: float) -> float:
    """The ``q``-th percentile, linear between order statistics."""
    return float(np.percentile(np.asarray(x, float), q)) if len(x) else 0.0


def sample(done: list, seed: int, tokens: int, most: int) -> list:
    """Requests to compare: the one with most served tokens, then others
    in an order drawn from the seed, until ``tokens`` served tokens or
    ``most`` requests."""
    if not done:
        return []
    rng = np.random.default_rng([seed, 30])
    first = max(done, key=lambda r: (len(r.out), -r.rid))
    rest = [r for r in done if r is not first]
    picked, n = [first], len(first.out)
    for i in rng.permutation(len(rest)):
        if n >= tokens or len(picked) >= most:
            break
        picked.append(rest[i])
        n += len(rest[i].out)
    return picked


class Server:
    """One engine with its weights, meter and compile counter; ``reset``
    puts fresh weights and an empty pool and queue under the same
    compiled programs (for scripts that read many seeds in one
    process)."""

    def __init__(self, cfg: dict, tr: dict, seed: int, spans: H.Spans,
                 block_admits: bool):
        self.cfg, self.tr, self.spans = cfg, tr, spans
        self.s = cfg["serving"]
        self.kind, self.ref = H.load_kind(cfg), H.load_reference(cfg)
        self.compiles = H.CompileCounter()
        self.mcfg, self.params, self.eng = build(cfg, self.kind, seed)
        self.meter = Meter(self.eng.executor, cfg, self.kind, spans,
                           block_admits, logit_ids(cfg["vocab_size"], seed))

    def reset(self, seed: int) -> None:
        from repro.models import model as M_
        from repro.serve.core import ServeCore
        from repro.serve.kv_cache import PagedKVPool
        ex = self.eng.executor
        self.params = weights.draw(M_.abstract_params(self.mcfg), seed,
                                   self.mcfg.dtype)
        self.meter.ids = logit_ids(self.cfg["vocab_size"], seed)
        self.eng.params = ex.params = self.params
        ex.pool = PagedKVPool(self.s["pool_blocks"], reserve_null=True)
        ex.table[:] = 0
        ex.slots[:] = [None] * len(ex.slots)   # requests the drain left
        ex._of.clear()
        self.eng.core = ServeCore(ex, policy=self.s["policy"],
                                  max_slots=self.s["max_batch"],
                                  seed=seed % 2**31)

    def set_up(self, seed: int, window: list) -> str:
        """Warm every shape, then serve the warm-up stretch and a few
        requests through the decode step."""
        tr, V = self.tr, self.cfg["vocab_size"]
        warm = gen.warmup(tr, V, seed)
        for r in warm:
            r.max_new = 1
        warmed = warm_shapes(self.eng, self.params, window + warm,
                             self.s["block_size"], self.kind.POOLS)
        for r in warm:
            self.eng.submit(to_request(r, self.eng.core.time))
        drain(self.eng, tr["drain_limit_s"])
        # the same seed: a prompt's prefix id names its tenant's tokens
        for r in gen.warmup(dict(tr, warmup_requests=2), V, seed):
            r.max_new = 3
            self.eng.submit(to_request(r, self.eng.core.time))
        drain(self.eng, tr["drain_limit_s"])
        return f"warmed {warmed}, served {len(warm)} warm-up requests"

    def serve(self, window: list, seconds: float,
              prof: H.Profiler | None = None) -> dict:
        """Drive the window, then serve what is left of the requests due
        in it. Returns host times and counters."""
        eng, tr, spans = self.eng, self.tr, self.spans
        B = self.s["max_batch"]
        reqs, due, submitted, times = {}, {}, {}, {}
        n_steps, step_wall, errors = 0, 0.0, 0
        queue = []                      # requests waiting, at each step
        meter = self.meter
        meter.c = {k: 0 for k in meter.c}
        meter.admitted_at = {}
        meter.logits = {}

        def submit(r: gen.Req, t_due: float):
            g = to_request(r, eng.core.time)
            reqs[r.rid], due[r.rid] = g, t_due
            submitted[r.rid] = time.perf_counter()
            times[r.rid] = []
            eng.submit(g)

        def step():
            nonlocal errors
            try:
                eng.core.step()
            except Exception as e:      # the core requeues the request
                errors += 1
                H.log(f"[window] step failed: {e!r}")
            t = time.perf_counter()
            for g in (list(eng.core._active.values())
                      + eng.core.stats.finished[-B:]):
                ts = times.get(g.rid)
                if ts is not None and len(ts) < len(g.out):
                    ts.extend([t] * (len(g.out) - len(ts)))

        if prof is not None:
            prof.start()
        self.compiles.count, self.compiles.names = 0, []
        self.compiles.counting = meter.on = True
        i = 0
        with spans.span("window"):
            t0 = time.perf_counter()
            while True:
                now = time.perf_counter() - t0
                if now >= seconds:
                    break
                if tr["mode"] == "saturated":
                    while i < len(window) and eng.core.backlog < 2 * B:
                        submit(window[i], time.perf_counter())
                        i += 1
                else:
                    while i < len(window) and window[i].due_s <= now:
                        submit(window[i], t0 + window[i].due_s)
                        i += 1
                if eng.core.has_work():
                    ts = time.perf_counter()
                    with spans.span("serve.step"):
                        step()
                    step_wall += time.perf_counter() - ts
                    n_steps += 1
                    queue.append(len(eng.core.queue) + len(eng.core._pending))
                else:
                    nxt = window[i].due_s if i < len(window) else seconds
                    time.sleep(max(0.0, min(nxt, seconds)
                                   - (time.perf_counter() - t0)))
            t_close = time.perf_counter()
        self.compiles.counting = meter.on = False
        if tr["mode"] == "open_loop":   # due in the window, not yet sent
            while i < len(window) and window[i].due_s < seconds:
                submit(window[i], t0 + window[i].due_s)
                i += 1
        t_drain = time.perf_counter()
        while eng.core.has_work() and (time.perf_counter() - t_drain
                                       < tr["drain_limit_s"]):
            step()
        # written after the drain, so that no request waits on the write
        reduced = prof.stop() if prof is not None else {}
        c = dict(meter.c, steps=n_steps, step_wall_s=step_wall,
                 compiles_in_window=self.compiles.count,
                 step_errors=errors)
        c["model_flops"] = c["prefill_flops"] + c["decode_flops"]
        return {"reqs": reqs, "due": due, "submitted": submitted,
                "times": times, "t0": t0, "t_close": t_close,
                "admitted_at": dict(meter.admitted_at), "counters": c,
                "trace": reduced, "queue": queue, "compiled": sorted(set(
                    self.compiles.names))}


def summarize(w: dict) -> dict:
    """End-to-end numbers over every request due in the window: TTFT from
    due time to the first token (a request with none counts at the
    drain's end), every gap between consecutive tokens, and the tokens
    on the host by the window's close over its seconds."""
    reqs, due, times = w["reqs"], w["due"], w["times"]
    horizon = time.perf_counter()
    ttft = [((times[r][0] if times[r] else horizon) - due[r]) * 1e3
            for r in reqs]
    gaps = [(b - a) * 1e3 for r in reqs
            for a, b in zip(times[r], times[r][1:])]
    window_s = w["t_close"] - w["t0"]
    in_window = sum(1 for r in reqs for t in times[r] if t <= w["t_close"])
    return {
        "ttft_ms": ttft, "itl_ms": gaps, "window_s": window_s,
        "tokens_in_window": in_window,
        "unfinished": [r for r in reqs
                       if len(reqs[r].out) < reqs[r].max_new],
        "late_ms": [(w["submitted"][r] - due[r]) * 1e3 for r in reqs],
        "wait_ms": [(w["admitted_at"][r] - due[r]) * 1e3 for r in reqs
                    if r in w["admitted_at"]],
    }


def compare(ref, params, cfg: dict, tr: dict, done: list, seed: int,
            meter: Meter, quant: str | None = None) -> dict:
    """The reference ``ref`` over a sample of the finished requests: the
    relative
    L2 error of the program's kept logits against the reference's, and
    the widest and mean gap below the reference's best logit of a served
    token; with ``quant`` the control's at the same positions. A request
    whose kept logits are not one per served token reads NaN."""
    picked = sample(done, seed, tr["reference_tokens"],
                    tr["reference_requests"])
    # one padded length fits the mix's longest prompt and output
    pad = -(-(tr["block_tokens"] * (tr["shared_blocks"][1]
                                    + tr["unique_blocks"][1])
              + tr["output_cap"]) // 128) * 128
    gaps, ctrls = [], []
    err, ctrl_err, norm = 0.0, 0.0, 0.0
    for g in picked:
        gap, ctrl, ref_at, low_at = ref.readings(
            params, np.asarray(g.tokens), np.asarray(g.out), cfg, pad,
            meter.ids, quant)
        gaps.append(gap)
        ctrls.append(ctrl)
        kept = meter.logits.get(g.rid, [])
        if len(kept) != len(g.out):
            err = float("nan")
        else:
            err += float(((np.stack(kept) - ref_at) ** 2).sum())
        ctrl_err += float(((low_at - ref_at) ** 2).sum())
        norm += float((ref_at ** 2).sum())
    gap = np.concatenate(gaps) if gaps else np.zeros(0)
    ctrl = np.concatenate(ctrls) if ctrls else np.zeros(0)
    out = {"requests": len(picked), "tokens": len(gap),
           "served_error": (err / norm) ** 0.5 if norm else float("nan"),
           "served_gap": float(gap.max(initial=0.0)),
           "served_gap_mean": float(gap.mean()) if len(gap) else 0.0}
    if quant:
        out.update(control_error=(ctrl_err / norm) ** 0.5 if norm
                   else float("nan"),
                   control_gap=float(ctrl.max(initial=0.0)),
                   control_gap_mean=float(ctrl.mean()) if len(ctrl) else 0.0)
    return out


def run(ctx: H.RunContext) -> H.RunOutput:
    cfg, tr = ctx.cell.config, ctx.cell.traffic
    unset = [k for k in ("rate_per_s", "served_logit_error_limit")
             if k not in tr and (k != "rate_per_s"
                                 or tr["mode"] == "open_loop")]
    if unset:
        raise H.BenchError(f"the traffic sets no {unset}: each is read on "
                           f"the chip first (readings.py)")
    srv = Server(cfg, tr, ctx.seed, ctx.spans, block_admits=ctx.trace)
    window = gen.schedule(tr, cfg["vocab_size"], ctx.seed, ctx.seconds)
    what = srv.set_up(ctx.seed, window)
    setup_s = time.perf_counter() - ctx.t_start
    H.log(f"[setup] setup_s={setup_s:.3f}; {what}; window schedule "
          f"{len(window)} requests ({tr['mode']})")

    w = srv.serve(window, ctx.seconds, H.Profiler(ctx.trace))
    device = ctx.device()
    reduced = w["trace"]
    if ctx.trace and reduced:
        device.update(busy_s=reduced["busy_s"],
                      window_s=reduced["window_s"])
    e = summarize(w)
    c = w["counters"]
    H.log(f"[window] {len(w['reqs'])} requests in {e['window_s']:.3f} s, "
          f"{c['steps']} steps, {e['tokens_in_window']} tokens in the "
          f"window, {len(e['unfinished'])} unfinished after the drain, "
          f"{c['compiles_in_window']} compiles in the window "
          f"{w['compiled'][:5]}")
    H.log(f"[generator] lateness ms: p50 {percentile(e['late_ms'], 50):.3f}"
          f" p95 {percentile(e['late_ms'], 95):.3f} max "
          f"{max(e['late_ms'], default=0.0):.3f}")
    H.log(f"[window] ttft p50 {percentile(e['ttft_ms'], 50):.3f} ms, itl "
          f"p50 {percentile(e['itl_ms'], 50):.3f} ms over "
          f"{len(e['itl_ms'])} gaps; counters {c}")

    # the reference, once the engine's state is gone
    ref, params, meter = srv.ref, srv.params, srv.meter
    done = [g for r, g in w["reqs"].items() if r not in e["unfinished"]]
    del srv
    gc.collect()
    t_ref = time.perf_counter()
    got = compare(ref, params, cfg, tr, done, ctx.seed, meter)
    H.log(f"[check] reference over {got['requests']} requests, "
          f"{got['tokens']} served tokens in "
          f"{time.perf_counter() - t_ref:.3f} s; served-token gap below "
          f"the reference's best: widest {got['served_gap']!r}, mean "
          f"{got['served_gap_mean']!r}")
    checks = [
        H.Check("requests_unfinished", len(e["unfinished"]), 0),
        H.Check("tokens_compared", got["tokens"],
                tr["reference_min_tokens"], at_least=True),
        H.Check("served_logit_error", got["served_error"],
                tr["served_logit_error_limit"]),
    ]
    record = {
        "trace": reduced, "counters": c,
        "peaks": ctx.peaks() if ctx.trace else None,
        "samples": {"queue_wait_ms": e["wait_ms"]},
        "breakdown": ({"device_ops": reduced["device_ops"],
                       "idle_gaps": reduced["idle_gaps"]}
                      if reduced else None),
    }
    return H.RunOutput(
        checks=checks, attempted=len(w["reqs"]),
        failed=len(e["unfinished"]),
        end_to_end={"ttft_p90_ms": percentile(e["ttft_ms"], 90),
                    "ttft_p95_ms": percentile(e["ttft_ms"], 95),
                    "itl_p95_ms": percentile(e["itl_ms"], 95),
                    "tokens_per_s": e["tokens_in_window"] / e["window_s"],
                    "setup_s": setup_s},
        device=device, record=record)
