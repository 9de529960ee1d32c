"""Serving launcher: ``PYTHONPATH=src python -m repro.launch.serve
--arch starcoder2-3b``.

Runs the continuous-batching engine (docs/SERVING.md) at the
architecture's published widths, with weights drawn on the device from
``--seed``: paged KV on the supported families, dense slot fallback
elsewhere; per-step admission under the chosen policy and per-request
early exit either way. ``--smoke`` swaps in the reduced config of
``configs.smoke_config`` for CPU runs.

The traffic is two shared-prefix families: every request's prompt is
its family's ``--prefix-len``-token prefix plus 1 to ``--suffix-max``
tokens of its own, and it asks for between ``--max-new // 2`` and
``--max-new`` tokens. The first request of each family arrives at step
0 and the rest at step ``STAGGER``: a family's prefix blocks enter the
prefix cache when its first request retires, so a stagger longer than
that request lets the later ones hit.
``--prefix-len 0`` gives unshared prompts of 1 to ``--suffix-max``
tokens.
"""
from __future__ import annotations

import argparse

import numpy as np

#: shared-prefix families in the traffic
FAMILIES = 2
#: arrival step of every request after its family's first; longer than
#: a first request at the default ``--max-new``
STAGGER = 40


def build_engine(cfg, *, seed: int, policy: str, max_batch: int,
                 max_seq: int):
    """Weights from ``seed`` on the default device, and an engine over
    them."""
    import jax

    from repro.models import model as M_
    from repro.serve.engine import InferenceEngine

    params = M_.init_params(cfg, jax.random.PRNGKey(seed))
    return InferenceEngine(cfg, params, policy=policy, max_batch=max_batch,
                           max_seq=max_seq, seed=seed)


def shared_prefix_requests(*, n: int, vocab: int, prefix_len: int,
                           suffix_max: int, max_new: int,
                           seed: int) -> list:
    """``n`` requests, round-robin over ``FAMILIES`` shared prefixes."""
    from repro.serve.engine import GenRequest

    rng = np.random.default_rng(seed)
    prefixes = [rng.integers(1, vocab, prefix_len, dtype=np.int32)
                for _ in range(FAMILIES)]
    reqs = []
    for i in range(n):
        fam = i % FAMILIES
        own = rng.integers(1, vocab, int(rng.integers(1, suffix_max + 1)),
                           dtype=np.int32)
        reqs.append(GenRequest(
            rid=i, tokens=np.concatenate([prefixes[fam], own]),
            prefix_id=fam if prefix_len else -1,
            prefix_len=prefix_len if prefix_len else -1,
            max_new=int(rng.integers(max(max_new // 2, 1), max_new + 1)),
            arrival=0.0 if i < FAMILIES else float(STAGGER)))
    return reqs


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(prog="python -m repro.launch.serve")
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced widths for CPU runs (configs."
                         "smoke_config); default: published widths")
    ap.add_argument("--seed", type=int, default=0,
                    help="weights and traffic are drawn from this seed")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--policy", default="reciprocating")
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--max-seq", type=int, default=1024)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--prefix-len", type=int, default=256)
    ap.add_argument("--suffix-max", type=int, default=16)
    return ap


def main(argv=None) -> None:
    args = build_parser().parse_args(argv)

    from repro.compile_cache import configure_compile_cache
    from repro.configs import get_config, smoke_config

    configure_compile_cache()
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_config(cfg)
    eng = build_engine(cfg, seed=args.seed, policy=args.policy,
                       max_batch=args.max_batch, max_seq=args.max_seq)
    for r in shared_prefix_requests(
            n=args.requests, vocab=cfg.vocab_size,
            prefix_len=args.prefix_len, suffix_max=args.suffix_max,
            max_new=args.max_new, seed=args.seed):
        eng.submit(r)
    done = eng.run()
    for r in done:
        print(f"req {r.rid}: prompt_len={len(r.tokens)} "
              f"admitted@{r.admitted:.0f} finished@{r.finished:.0f} "
              f"prefill_hit={r.prefill_hit:.3f} out={r.out}")
    c = eng.counters
    print(f"[serve] completed {len(done)} requests "
          f"(arch={cfg.name}, policy={args.policy}, paged={eng.paged}, "
          f"{int(eng.core.time)} steps, {c.slot_steps} slot-steps)")


if __name__ == "__main__":
    main()
