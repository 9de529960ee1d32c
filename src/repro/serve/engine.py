"""Model-backed continuous-batching inference engine (SERVING.md §1-§4).

The engine is the model frontend over the shared scheduler core
(`serve/core.py`): the same per-step admission loop the discrete-time
simulator runs, with an executor that computes real tokens. One core
``step()`` is one batched ``decode_step`` for every occupied slot:

* **per-step admission** — a freed slot is refilled from the
  ``AdmissionQueue`` on the next step, not when the whole batch drains;
* **per-request early exit** — a request leaves its slot the step its
  ``max_new`` tokens are done; finished slots never burn decode compute;
* **chunked prefill interleaved with decode** — the first
  ``prefill_chunk`` prompt tokens go through ``prefill_step``; any
  remainder is fed through the decode path one token per step alongside
  the other slots' decode (SERVING.md §4);
* **paged KV** — on the supported families the cache is a block pool
  indexed by per-slot block tables (``serve/kv_cache.py``), decoded with
  ``models/decode.py::paged_decode_step`` and with copy-free sharing of
  full prompt-prefix blocks between requests that declare the same
  ``prefix_id`` (SERVING.md §3). Other families use a dense per-slot
  cache with the identical scheduling behaviour.

Both executors compute each batch row independently of its neighbours,
so the admission policy changes completion *order* only, never token
values (property-tested in tests/test_system.py).
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import decode as D_
from repro.serve.core import Executor, ServeCore, ServeStats  # noqa: F401
from repro.serve.kv_cache import PagedKVPool
from repro.sharding.ctx import MeshCtx, trivial_ctx


@dataclass(eq=False)              # identity semantics: the core keys its
class GenRequest:                 # slot dict on id(req), so two requests
                                  # with equal fields never collide
    rid: int
    tokens: np.ndarray            # prompt (1-D int32)
    max_new: int = 16
    prefix_id: int = -1           # shared-prompt family; -1 = no sharing
    prefix_len: int = -1          # tokens of the prompt that ARE the shared
    #                               prefix (-1 = the whole prompt)
    out: list = field(default_factory=list)
    # scheduling state (set by the core; times are in scheduler steps)
    arrival: float = 0.0
    admitted: float = -1.0
    finished: float = -1.0
    prefill_hit: float = 0.0      # fraction of prompt served from shared
    #                               prefix blocks (paged executor only)


@dataclass
class _Slot:
    req: GenRequest
    idx: int                      # batch row
    prompt: np.ndarray
    base: int                     # position offset (vlm patch prefix)
    pos: int                      # next position to feed
    next_tok: int                 # token to feed at ``pos``
    kb: object = None             # prefilled KV blocks in transit between
    vb: object = None             # _prefill_slot and admit (paged only)

    @property
    def end(self) -> int:         # first generated-token position
        return self.base + len(self.prompt)


@dataclass
class EngineCounters:
    """Observability for the continuous batcher (SERVING.md §4)."""
    decode_batches: int = 0       # batched decode_step launches
    slot_steps: int = 0           # occupied-slot decode iterations
    prefill_calls: int = 0
    prefill_tokens: int = 0

    def to_dict(self) -> dict:
        return {"decode_batches": self.decode_batches,
                "slot_steps": self.slot_steps,
                "prefill_calls": self.prefill_calls,
                "prefill_tokens": self.prefill_tokens}


class _ModelExecutor(Executor):
    """Token plumbing shared by the paged and dense executors: chunked
    prefill at admission, then one decode token per step per slot."""

    def __init__(self, cfg: ModelConfig, params, ctx: MeshCtx,
                 max_batch: int, max_seq: int, prefill_chunk: int):
        self.cfg = cfg
        self.params = params
        self.ctx = ctx
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.chunk = prefill_chunk
        self.slots: list = [None] * max_batch
        self._of: dict = {}                 # id(req) -> _Slot
        self.counters = EngineCounters()

    # subclass interface -----------------------------------------------------
    def _prefill_slot(self, s: _Slot, n_tokens: int):
        """Prefill ``prompt[:n_tokens]`` into slot ``s``'s cache; return
        host logits (V,) for position ``base + n_tokens - 1``."""
        raise NotImplementedError

    def _decode_batch(self, toks: np.ndarray, poss: np.ndarray):
        """One decode step for all rows; return host logits (B, V)."""
        raise NotImplementedError

    # Executor hooks ---------------------------------------------------------
    def admit(self, req: GenRequest, now: float) -> None:
        idx = self.slots.index(None)
        prompt = np.asarray(req.tokens, np.int32).reshape(-1)
        s = _Slot(req=req, idx=idx, prompt=prompt,
                  base=self.cfg.n_patches, pos=0, next_tok=int(prompt[0]))
        self.slots[idx] = s
        self._of[id(req)] = s
        c = min(len(prompt), self.chunk)
        try:
            logits = self._prefill_slot(s, c)
        except BaseException:
            self._drop(s)               # a failed admit must not wedge
            raise                       # the slot (core requeues req)
        self.counters.prefill_calls += 1
        self.counters.prefill_tokens += c
        s.pos = s.base + c
        if s.pos >= s.end:                  # prompt fully prefilled:
            t = int(np.argmax(logits))      # logits predict 1st output
            req.out.append(t)
            s.next_tok = t
        else:                               # chunked: keep feeding prompt
            s.next_tok = int(prompt[c])

    def work(self, active: list, now: float) -> list:
        done = []
        live = []
        for s in self.slots:
            if s is None:
                continue
            if len(s.req.out) >= s.req.max_new:   # finished at admission
                done.append(s.req)
                self._drop(s)
            else:
                live.append(s)
        if not live:
            return done
        toks = np.zeros((self.max_batch,), np.int32)
        poss = np.zeros((self.max_batch,), np.int32)
        for s in live:
            toks[s.idx] = s.next_tok
            poss[s.idx] = s.pos
        logits = self._decode_batch(toks, poss)
        self.counters.decode_batches += 1
        self.counters.slot_steps += len(live)
        for s in live:
            s.pos += 1
            if s.pos >= s.end:              # a generated-token position
                t = int(np.argmax(logits[s.idx]))
                s.req.out.append(t)
                s.next_tok = t
                if len(s.req.out) >= s.req.max_new:
                    done.append(s.req)      # early exit: slot freed now
                    self._drop(s)
            else:                           # still consuming the prompt
                s.next_tok = int(s.prompt[s.pos - s.base])
        return done

    def _drop(self, s: _Slot) -> None:
        self.slots[s.idx] = None
        del self._of[id(s.req)]
        self._on_drop(s)

    def _on_drop(self, s: _Slot) -> None:
        """Subclass hook: slot-level state to clear when a slot frees."""


class PagedModelExecutor(_ModelExecutor):
    """Block-table paged KV executor (SERVING.md §3).

    Pools are ``decode.pool_shape(P, L, block, KV, hd)`` device arrays,
    (P, L, block*KV*hd/128, 128), indexed by block id; the host-side
    ``PagedKVPool`` owns allocation, pinning and the prefix-cache LRU.
    Full prompt-prefix blocks are shared copy-free between
    same-``prefix_id`` requests and retained (LRU) after release.
    """

    def __init__(self, cfg, params, ctx, max_batch, max_seq, prefill_chunk,
                 block_size: int, pool_blocks: int):
        super().__init__(cfg, params, ctx, max_batch, max_seq, prefill_chunk)
        assert D_.paged_supported(cfg, max_seq), cfg.name
        assert max_seq % block_size == 0, (max_seq, block_size)
        self.block = block_size
        self.nb = max_seq // block_size
        self.pool = PagedKVPool(pool_blocks, reserve_null=True)
        shape = D_.pool_shape(pool_blocks, cfg.n_layers, block_size,
                              cfg.n_kv_heads, cfg.hd)
        self.k_pool = jnp.zeros(shape, cfg.dtype)
        self.v_pool = jnp.zeros(shape, cfg.dtype)
        self.table = np.zeros((max_batch, self.nb), np.int32)

        def _prefill(p, toks, last):
            logits, cache = D_.prefill_step(p, {"tokens": toks}, cfg, ctx,
                                            last_index=last)
            kb, vb = D_.cache_to_blocks(cache, block_size)
            return logits[0], kb, vb
        # one jit; per-bucket shapes compile on first use
        self._prefill = jax.jit(_prefill)
        self._decode = jax.jit(
            lambda p, kp, vp, tb, po, tk: D_.paged_decode_step(
                p, kp, vp, tb, po, tk, cfg, ctx),
            donate_argnums=(1, 2))

    def _prefix_blocks(self, req: GenRequest, L: int) -> int:
        """Full blocks covered by the request's declared shared prefix —
        the only blocks that may be shared or cached under its
        ``prefix_id`` (SERVING.md §3)."""
        pl = L if req.prefix_len < 0 else min(req.prefix_len, L)
        return pl // self.block

    def admit(self, req: GenRequest, now: float) -> None:
        L = len(np.asarray(req.tokens).reshape(-1))
        # last written position is L + max_new - 2: the final generated
        # token is appended, never fed back
        total = math.ceil((L + req.max_new - 1) / self.block)
        total = max(total, 1)
        owner = id(req)
        shared = (self.pool.share(owner, req.prefix_id,
                                  self._prefix_blocks(req, L))
                  if req.prefix_id >= 0 else [])
        try:
            ids = shared + self.pool.alloc(owner, total - len(shared))
            req.prefill_hit = len(shared) * self.block / max(L, 1)
            super().admit(req, now)         # prefill + token plumbing
        except BaseException:
            self.pool.release(owner)        # unpin this attempt's blocks
            raise
        s = self._of[id(req)]
        row = np.zeros((self.nb,), np.int32)        # null block padding
        row[:total] = ids
        self.table[s.idx] = row
        # Scatter the prefilled chunk's blocks into the pools — but never
        # the shared ones: those already hold the correct prefix KV, and
        # when the chunk ends mid-block the chunk's right padding would
        # overwrite real positions a concurrent sharer is attending over.
        nbp = s.kb.shape[0]
        skip = min(len(shared), nbp)
        if skip < nbp:
            tgt = jnp.asarray(row[skip:nbp])
            self.k_pool = self.k_pool.at[tgt].set(s.kb[skip:])
            self.v_pool = self.v_pool.at[tgt].set(s.vb[skip:])
        s.kb = s.vb = None

    def _prefill_slot(self, s: _Slot, n_tokens: int):
        Lp = math.ceil(n_tokens / self.block) * self.block
        toks = np.zeros((1, Lp), np.int32)
        toks[0, :n_tokens] = s.prompt[:n_tokens]
        last = np.asarray([n_tokens - 1], np.int32)
        logits, s.kb, s.vb = self._prefill(
            self.params, jnp.asarray(toks), jnp.asarray(last))
        return np.asarray(logits)

    def _decode_batch(self, toks, poss):
        logits, self.k_pool, self.v_pool = self._decode(
            self.params, self.k_pool, self.v_pool,
            jnp.asarray(self.table), jnp.asarray(poss), jnp.asarray(toks))
        return np.asarray(logits)

    def _on_drop(self, s: _Slot) -> None:
        # An empty slot keeps decoding as a dummy row and scatters its
        # garbage block every step: point it back at the null block so
        # the stale ids (now cached prefix blocks, or reallocated) are
        # never written again.
        self.table[s.idx] = 0

    def retire(self, req: GenRequest) -> None:
        L = len(np.asarray(req.tokens).reshape(-1))
        keep = self._prefix_blocks(req, L) if req.prefix_id >= 0 else 0
        self.pool.release(id(req),
                          prefix_id=req.prefix_id if keep else None,
                          keep_blocks=keep)


# cache keys whose axis 2 is the (sliced) sequence axis
_SEQ_KEYS = ("k", "v", "ak", "av", "ckv", "kr", "d_ckv", "d_kr")


class DenseSlotExecutor(_ModelExecutor):
    """Dense per-slot cache fallback for families the paged path does not
    cover (MLA / SSM / hybrid / encdec / vlm / sliding-window rings —
    SERVING.md §3). One persistent ``init_cache(max_batch, max_seq)``
    tree; each admission prefills B=1 and writes its leaves into the
    slot's row, so scheduling behaviour (per-step admission, early exit,
    chunked prefill) is identical to the paged executor."""

    def __init__(self, cfg, params, ctx, max_batch, max_seq, prefill_chunk):
        super().__init__(cfg, params, ctx, max_batch, max_seq, prefill_chunk)
        self.cache = D_.init_cache(cfg, max_batch, max_seq)
        self._decode = jax.jit(
            lambda p, c, t: D_.decode_step(p, c, t, cfg, ctx),
            donate_argnums=(1,))
        # one jit; per-bucket prefill shapes compile on first use
        self._prefill = jax.jit(
            lambda p, b, li: D_.prefill_step(p, b, cfg, ctx, last_index=li))

    def _extras(self, B: int) -> dict:
        cfg = self.cfg
        ex = {}
        if cfg.n_patches:
            ex["patches"] = jnp.zeros((B, cfg.n_patches, cfg.d_model),
                                      cfg.dtype)
        if cfg.is_encoder_decoder:
            ex["frames"] = jnp.zeros((B, cfg.enc_frames, cfg.d_model),
                                     cfg.dtype)
        return ex

    @staticmethod
    def padded_len(cfg: ModelConfig, n_tokens: int) -> int:
        """Prefill length for ``n_tokens``: exact for SSM/hybrid (right
        padding would pollute the order-dependent state recurrence),
        bucketed to 8 elsewhere (fewer jit compiles)."""
        if cfg.family in ("ssm", "hybrid"):
            return n_tokens
        return math.ceil(n_tokens / 8) * 8

    def _prefill_slot(self, s: _Slot, n_tokens: int):
        cfg = self.cfg
        Lp = self.padded_len(cfg, n_tokens)
        Sc = D_.cache_len(cfg, self.max_seq)
        if s.base + Lp > Sc:
            raise ValueError(
                f"prompt chunk {s.base + Lp} exceeds cache window {Sc} "
                f"({cfg.name}); shrink prefill_chunk or raise max_seq")
        toks = np.zeros((1, Lp), np.int32)
        toks[0, :n_tokens] = s.prompt[:n_tokens]
        batch = {"tokens": jnp.asarray(toks), **self._extras(1)}
        last = jnp.asarray([s.base + n_tokens - 1], np.int32)
        logits, c1 = self._prefill(self.params, batch, last)
        self._insert_slot(s.idx, c1, real_pos=s.base + n_tokens)
        return np.asarray(logits[0])

    def _insert_slot(self, i: int, c1: dict, real_pos: int) -> None:
        """Write a B=1 prefill cache into row ``i`` of the global cache."""
        g = dict(self.cache)
        if "slot_pos" in g:                 # stale entries of the slot's
            g["slot_pos"] = g["slot_pos"].at[i].set(-1)   # previous tenant
        for key, leaf in c1.items():
            if key == "pos":
                g["pos"] = g["pos"].at[i].set(real_pos)
            elif key == "slot_pos":
                S = leaf.shape[1]
                g[key] = g[key].at[i, :S].set(leaf[0])
            elif key in _SEQ_KEYS:
                S = leaf.shape[2]
                g[key] = g[key].at[:, i, :S].set(leaf[:, 0])
            else:                           # state / conv / xk / xv
                g[key] = g[key].at[:, i].set(leaf[:, 0])
        self.cache = g

    def _decode_batch(self, toks, poss):
        # cache["pos"] is authoritative on-device; ``poss`` (host mirror)
        # is only used by the shared token plumbing.
        logits, self.cache = self._decode(self.params, self.cache,
                                          jnp.asarray(toks))
        return np.asarray(logits)


class InferenceEngine:
    """Continuous-batching serving engine over the shared core.

    ``run()`` drives the core until idle and returns the requests that
    finished during this call, in completion order. ``submit()`` may be
    called before or between runs; ``arrival`` (in scheduler steps) may
    be set on the request to stagger availability."""

    def __init__(self, cfg: ModelConfig, params, ctx: MeshCtx | None = None,
                 policy: str = "reciprocating", max_batch: int = 4,
                 max_seq: int = 128, *, block_size: int = 16,
                 pool_blocks: int | None = None,
                 prefill_chunk: int | None = None,
                 paged: bool | None = None, seed: int = 0):
        self.cfg = cfg
        self.params = params
        self.ctx = ctx or trivial_ctx()
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.paged = (D_.paged_supported(cfg, max_seq) if paged is None
                      else paged)
        chunk = prefill_chunk or max_seq
        if self.paged:
            nb = max_seq // block_size
            pool_blocks = pool_blocks or 1 + nb * (max_batch + 2)
            self.executor: _ModelExecutor = PagedModelExecutor(
                cfg, params, self.ctx, max_batch, max_seq, chunk,
                block_size, pool_blocks)
        else:
            self.executor = DenseSlotExecutor(
                cfg, params, self.ctx, max_batch, max_seq, chunk)
        self.core = ServeCore(self.executor, policy=policy,
                              max_slots=max_batch, seed=seed)

    @property
    def queue(self):
        return self.core.queue

    @property
    def stats(self) -> ServeStats:
        return self.core.stats

    @property
    def counters(self) -> EngineCounters:
        return self.executor.counters

    @property
    def pool(self) -> PagedKVPool | None:
        return getattr(self.executor, "pool", None)

    def submit(self, req: GenRequest) -> None:
        L = len(np.asarray(req.tokens).reshape(-1))
        if L < 1:
            raise ValueError(f"request {req.rid}: empty prompt")
        if req.max_new < 1:
            raise ValueError(f"request {req.rid}: max_new must be >= 1")
        room = self.max_seq - self.cfg.n_patches
        if L + req.max_new > room:
            raise ValueError(
                f"request {req.rid}: prompt {L} + max_new {req.max_new} "
                f"exceeds max_seq budget {room}")
        if not self.paged:
            # the dense fallback prefills into a cache_len window (< max_seq
            # on sliding-window archs); reject synchronously what admission
            # would only discover at prefill time (and retry forever)
            chunk = min(L, self.executor.chunk)
            need = (self.cfg.n_patches
                    + DenseSlotExecutor.padded_len(self.cfg, chunk))
            window = D_.cache_len(self.cfg, self.max_seq)
            if need > window:
                raise ValueError(
                    f"request {req.rid}: prefill chunk {need} exceeds "
                    f"cache window {window} ({self.cfg.name}); shorten "
                    f"the prompt or set prefill_chunk <= {window}")
        self.core.submit(req)

    def run(self) -> list:
        """Serve everything queued; returns the requests finished by this
        call in completion order."""
        n0 = len(self.core.stats.finished)
        while self.core.has_work():
            self.core.step()
        return self.core.stats.finished[n0:]
