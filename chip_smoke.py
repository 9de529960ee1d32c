"""Bring-up check: the lock simulator, the compiled lock kernel and a
full-width starcoder2-3b server on one TPU chip, in one process.

    python chip_smoke.py                # one chip: device, sim, kernel, serve
    python chip_smoke.py --four-chips   # four chips: sharded sim grid only

Phases of the one-chip run, each through the entry points a user calls:

* device  — the first JAX device must be a TPU;
* sim     — the pinned state digests of ``repro.core.locks.goldens``
  recomputed on the chip, then a MutexBench maximal-contention grid
  (T=64, 4 locks, seeds 0-7, ``epyc-2s`` and ``xeon-4s``, 20,000 steps)
  through ``repro.bench.sweep.cached_grid`` with the experiment cache
  off;
* kernel  — the Pallas lock kernel compiled for the chip
  (``run_measured``) for reciprocating and mcs at T=8 and T=64, checked
  against the uniform-cost sim;
* serve   — ``repro.launch.serve``'s engine on starcoder2-3b at its
  published widths with bf16 weights from the seed, 8 shared-prefix
  requests; the engine's logits at every generated token, prefill and
  paged decode (cached-prefix reads included), against a float32
  ``forward_lm`` reference teacher-forced over each request's prompt
  and output.

``--four-chips`` runs only the T=64 grid sharded over 4 TPU devices and
unsharded on device 0, and requires bit-identical results.

Earlier lines report set-up facts (compile and wall seconds, peak device
bytes). The last line is one JSON object: ``{"ok": true, "device":
{"platform", "kind", "count"}}``. Any failed phase, or a host without a
TPU, exits non-zero without it.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

GRID_LOCKS = ("reciprocating", "mcs", "clh", "ticket")
GRID_TOPOLOGIES = ("epyc-2s", "xeon-4s")
GRID_THREADS = 64
GRID_SEEDS = range(8)
GRID_STEPS = 20_000
KERNEL_LOCKS = ("reciprocating", "mcs")
KERNEL_THREADS = (8, 64)
KERNEL_ROUNDS = 2_000
#: uniform-cost sim steps behind the kernel agreement check: more
#: admissions than the kernel's 256-entry ring holds, fewer than the
#: sim's 512-entry ring (333 to 491 for these locks at T=8 and T=64)
KERNEL_SIM_STEPS = 4_096
ARCH = "starcoder2-3b"
SEED = 0
PREFIX_LEN = 256
FOUR_CHIPS = 4


class SmokeFailure(RuntimeError):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


# --- phases ----------------------------------------------------------------

def phase_device(n_chips: int):
    import jax
    devs = jax.devices()
    check(devs[0].platform == "tpu",
          f"no TPU: jax.devices()[0].platform is {devs[0].platform!r}")
    check(len(devs) >= n_chips,
          f"{n_chips} TPU devices needed, {len(devs)} visible")
    log(f"[device] {devs[0].device_kind} x{len(devs)} "
        f"(platform {devs[0].platform})")
    return devs


def phase_goldens() -> None:
    from repro.core.locks.goldens import GOLDEN, run_digest
    t0 = time.perf_counter()
    bad = {k: (got, want) for k, want in GOLDEN.items()
           if (got := run_digest(k)) != want}
    check(not bad, f"sim state digests differ from the pinned ones: {bad}")
    log(f"[sim] {len(GOLDEN)} pinned state digests equal "
        f"(wall {time.perf_counter() - t0:.3f} s, compiles included)")


def grid_workload(steps: int):
    from repro.core.sim.engine import Workload
    return Workload(0, "rw", steps, label="max_contention")


def grid_kw(steps: int) -> dict:
    return dict(seeds=GRID_SEEDS, topologies=list(GRID_TOPOLOGIES),
                workloads=[grid_workload(steps)], threads=[GRID_THREADS])


def phase_grid(steps: int = GRID_STEPS) -> None:
    """The grid through the bench harness's own grid entry, cache off."""
    from repro.bench import cache as cachemod
    from repro.bench import sweep

    cachemod.configure(enabled=False)
    kw = grid_kw(steps)
    for alg in GRID_LOCKS:
        t0 = time.perf_counter()
        g = sweep.cached_grid(alg, **kw)
        first = time.perf_counter() - t0
        t0 = time.perf_counter()
        again = sweep.cached_grid(alg, **kw)
        warm = time.perf_counter() - t0
        check(g.compiles <= 1, f"{alg}: {g.compiles} compiles for one grid")
        check(again.compiles == 0, f"{alg}: the warm grid recompiled")
        eps = [c.result.episodes for c in g.cells]
        check(len(eps) == len(GRID_TOPOLOGIES)
              and all(e > 0 for e in eps),
              f"{alg}: a cell admitted no episode: {eps}")
        bypass = max(c.result.bypass_bound for c in g.cells)
        if alg == "reciprocating":
            check(bypass <= 2, f"reciprocating bypass bound {bypass} > 2")
        log(f"[sim] {alg} T={GRID_THREADS} grid {len(g.cells)} cells x "
            f"{len(GRID_SEEDS)} seeds x {steps} steps: compiles="
            f"{g.compiles} episodes={eps} bypass<={bypass} "
            f"first_call_s={first:.3f} warm_call_s={warm:.3f} "
            f"compile_s~{first - warm:.3f}")


def phase_kernel(interpret: bool = False) -> None:
    from repro.bench.measured import sim_agreement
    from repro.core.locks.pallas_backend import ADM_LOG_M, run_measured

    want = "pallas-interpret" if interpret else "pallas-device"
    for alg in KERNEL_LOCKS:
        for T in KERNEL_THREADS:
            r = run_measured(alg, T, KERNEL_ROUNDS, interpret=interpret)
            check(r.backend == want, f"{alg} T={T}: backend {r.backend}")
            check(r.collisions == 0,
                  f"{alg} T={T}: {r.collisions} mutual-exclusion collisions")
            check(r.episodes > 0, f"{alg} T={T}: no episode admitted")
            a = sim_agreement(alg, T, r.admissions, r.admission_counts,
                              sim_steps=KERNEL_SIM_STEPS, limit=ADM_LOG_M)
            check(a["compared"] >= min(r.admission_counts, ADM_LOG_M, 64),
                  f"{alg} T={T}: only {a['compared']} admissions compared")
            check(a["order_match"] and a["cs_counts_match"],
                  f"{alg} T={T}: kernel and uniform-cost sim disagree {a}")
            log(f"[kernel] {alg} T={T} rounds={KERNEL_ROUNDS} "
                f"backend={r.backend} ({r.device_kind}) "
                f"episodes={r.episodes} collisions={r.collisions} "
                f"sim_agreement=exact over {a['compared']} "
                f"admissions compile_s={r.compile_s:.3f} "
                f"wall_s={r.wall_s:.6f}")


def reference_logits(params, seqs, first: int, cfg, ctx):
    """Float32 ``forward_lm`` logits of every row of ``seqs`` (B, S) at
    positions ``first`` to ``S - 1``, shape (B, S - first, V). The bf16
    weights are promoted layer by layer inside the scan, so no f32 copy
    of the whole model is held."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import model as M_

    cfg32 = cfg.replace(dtype=jnp.float32)

    @jax.jit
    def ref(p, toks):
        h, _, _ = M_.forward_lm(p, {"tokens": toks}, cfg32, ctx)
        return h[:, first:] @ M_.unembed_matrix(p, cfg32).astype(jnp.float32)

    with jax.default_matmul_precision("highest"):
        return np.asarray(ref(params, jnp.asarray(seqs)), np.float32)


#: Relative L2 error allowed between the engine's bf16 logits and the
#: float32 reference, at every prefill and decode position. bf16 keeps 8
#: significant bits (unit roundoff 2^-9, about 0.2%), and the engine
#: rounds the residual stream, every matmul output and the KV cache to
#: bf16 in each of the 30 layers, so rounding errors that add up like a
#: random walk leave a few percent of relative error in the logits. 5%
#: admits that; a wrong weight or position gives an error of order 1,
#: and one cached prefix block read in place of its neighbour, 7-9% at
#: the reduced widths of ``configs.smoke_config`` (CPU).
LOGITS_RTOL = 0.05


def record_logits(ex) -> dict:
    """Wrap the executor's prefill and decode calls so that the host
    logits they return are kept, keyed by (request id, position of the
    token they follow)."""
    seen = {}
    prefill, decode = ex._prefill_slot, ex._decode_batch

    def prefill_slot(s, n_tokens):
        logits = prefill(s, n_tokens)
        seen[(s.req.rid, s.base + n_tokens - 1)] = logits
        return logits

    def decode_batch(toks, poss):
        logits = decode(toks, poss)
        for s in ex.slots:
            if s is not None:
                seen[(s.req.rid, int(poss[s.idx]))] = logits[s.idx]
        return logits

    ex._prefill_slot, ex._decode_batch = prefill_slot, decode_batch
    return seen


def phase_serve(cfg, *, max_seq: int = 1024) -> None:
    import jax
    import numpy as np

    from repro.launch import serve

    t0 = time.perf_counter()
    eng = serve.build_engine(cfg, seed=SEED, policy="reciprocating",
                             max_batch=8, max_seq=max_seq)
    jax.block_until_ready(eng.params)
    init_s = time.perf_counter() - t0
    reqs = serve.shared_prefix_requests(
        n=8, vocab=cfg.vocab_size, prefix_len=PREFIX_LEN, suffix_max=16,
        max_new=32, seed=SEED)
    for r in reqs:
        eng.submit(r)
    seen = record_logits(eng.executor)
    t0 = time.perf_counter()
    done = eng.run()
    serve_s = time.perf_counter() - t0
    check(len(done) == len(reqs), f"{len(done)} of {len(reqs)} finished")
    for r in reqs:
        check(len(r.out) == r.max_new,
              f"request {r.rid}: {len(r.out)} of {r.max_new} tokens")
    later = [r for r in reqs if r.rid >= serve.FAMILIES]
    check(all(r.prefill_hit > 0 for r in later),
          f"no prefix hit: {[(r.rid, r.prefill_hit) for r in later]}")

    # the float32 reference, teacher-forced over each request's prompt
    # and the tokens it generated (the last one is never fed back); rows
    # are padded at the end, which a causal model never reads
    block = eng.executor.block
    seqs = [np.concatenate([r.tokens, r.out[:-1]]) for r in reqs]
    S = -(-max(len(q) for q in seqs) // block) * block
    toks = np.zeros((len(reqs), S), np.int32)
    for i, q in enumerate(seqs):
        toks[i, :len(q)] = q
    first = min(len(r.tokens) for r in reqs) - 1
    t0 = time.perf_counter()
    want = reference_logits(eng.params, toks, first, cfg, eng.ctx)
    ref_s = time.perf_counter() - t0
    check(np.isfinite(want).all(), "float32 reference logits not finite")

    # every generated token is the argmax of the engine's logits at its
    # position, and those logits, prefill and decode alike (decode reads
    # the paged cache, shared prefix blocks included), match the
    # reference there
    rels, agree = {}, 0
    for i, r in enumerate(reqs):
        L = len(r.tokens)
        for j, t in enumerate(r.out):
            got = np.asarray(seen[(r.rid, L - 1 + j)], np.float32)
            check(np.isfinite(got).all() and got.shape == (cfg.vocab_size,),
                  f"request {r.rid} position {L - 1 + j}: logits shape "
                  f"{got.shape}, finite {bool(np.isfinite(got).all())}")
            check(t == int(np.argmax(got)),
                  f"request {r.rid} token {j} is not the engine's argmax")
            w = want[i, L - 1 + j - first]
            rels[(r.rid, j)] = float(np.linalg.norm(got - w)
                                     / np.linalg.norm(w))
            agree += int(np.argmax(w)) == t
    bad = {k: round(v, 4) for k, v in rels.items() if v > LOGITS_RTOL}
    check(not bad, f"logits rel L2 error above {LOGITS_RTOL} at (request, "
                   f"token): {bad}")
    prefill_rel = [v for (_, j), v in rels.items() if j == 0]
    decode_rel = [v for (_, j), v in rels.items() if j > 0]

    stats = jax.devices()[0].memory_stats() or {}
    c = eng.counters
    log(f"[serve] {cfg.name} L={cfg.n_layers} d={cfg.d_model} "
        f"vocab={cfg.vocab_size} {cfg.dtype.__name__}: "
        f"{len(done)} requests, {sum(len(r.out) for r in done)} tokens, "
        f"{c.decode_batches} decode batches, prefill_hit="
        f"{[round(r.prefill_hit, 3) for r in reqs]}")
    log(f"[serve] logits vs teacher-forced float32 reference, rel L2 "
        f"(tolerance {LOGITS_RTOL}): prefill max {max(prefill_rel):.6f} "
        f"over {len(prefill_rel)}, decode max {max(decode_rel):.6f} over "
        f"{len(decode_rel)}; reference argmax agrees at {agree} of "
        f"{len(rels)} tokens")
    log(f"[serve] init_s={init_s:.3f} run_s={serve_s:.3f} ref_s={ref_s:.3f}"
        f" (compiles included) peak_bytes_in_use="
        f"{stats.get('peak_bytes_in_use')}")


def phase_four_chips(steps: int = GRID_STEPS) -> None:
    """The T=64 grid sharded over all devices and unsharded on device 0:
    results must be bit-identical."""
    import jax

    from repro.bench.cache import result_to_doc
    from repro.core.sim.engine import session

    check(jax.device_count() == FOUR_CHIPS,
          f"{FOUR_CHIPS} devices needed, {jax.device_count()} visible")
    kw = grid_kw(steps)
    for alg in GRID_LOCKS:
        eng = session(alg)
        t0 = time.perf_counter()
        sharded = eng.grid(shard="auto", **kw)
        t1 = time.perf_counter()
        plain = eng.grid(shard=False, **kw)
        t2 = time.perf_counter()
        check(sharded.shards == FOUR_CHIPS,
              f"{alg}: the sharded output lives on {sharded.shards} of "
              f"{FOUR_CHIPS} devices")
        check(plain.shards == 0, f"{alg}: the unsharded grid sharded")
        same = ([result_to_doc(c.result) for c in sharded.cells]
                == [result_to_doc(c.result) for c in plain.cells])
        check(same, f"{alg}: sharded and unsharded grids differ")
        log(f"[four-chips] {alg} T={GRID_THREADS} "
            f"{len(sharded.cells)} cells x "
            f"{len(GRID_SEEDS)} seeds: sharded over {sharded.shards} "
            f"devices == unsharded on device 0, bit-identical "
            f"(sharded_s={t1 - t0:.3f} unsharded_s={t2 - t1:.3f}, "
            f"compiles included)")


# --- entry -----------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded-vs-unsharded grid on 4 "
                         "TPU devices")
    args = ap.parse_args(argv)

    from repro.compile_cache import configure_compile_cache
    log(f"[setup] compile cache: {configure_compile_cache()}")
    t_start = time.perf_counter()
    n_chips = FOUR_CHIPS if args.four_chips else 1
    devs = phase_device(n_chips)
    if args.four_chips:
        phase_four_chips()
    else:
        from repro.configs import get_config
        phase_goldens()
        phase_grid()
        phase_kernel()
        phase_serve(get_config(ARCH))
    log(f"[setup] total wall {time.perf_counter() - t_start:.3f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        sys.exit(1)
