"""KV-cache structures, prefill and single-token decode for every family.

Cache layouts (leading dim = stacked layers, scanned):
  gqa    : k/v (L, B, Sc, KV, hd), Sc = sliding_window or max_seq (ring)
  mla    : ckv (L, B, S, R) + kr (L, B, S, dr)   — compressed latent cache
  ssm    : state (L, B, H, N, P) f32 + conv (L, B, K-1, conv_dim)
  hybrid : ssm caches + shared-attn k/v (n_attn, B, S, KV, hd)
  encdec : decoder self k/v (L,...) + frozen cross k/v (L, B, F, KV, hd)

Caches are sequence-sharded over the ``model`` axis (flash-decoding): the
attention softmax reductions over the sharded seq dim become all-reduces.
``slot_pos`` maps cache slots to absolute positions (-1 = empty) and makes
ring buffers (sliding window) and partially-filled caches uniform.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import (
    decode_attention, moe_decode, mlp_swiglu, rope, rmsnorm, tree_index,
)
from repro.models.model import (
    F32, cs, embed_tokens, mlp_forward, scan_or_unroll, unembed_matrix,
    forward_lm, forward_encdec,
)
from repro.models.ssm import mamba2_mixer
from repro.sharding.ctx import MeshCtx


def _n_attn(cfg: ModelConfig) -> int:
    """zamba2: number of shared-attn invocations."""
    k = cfg.shared_attn_every
    return (cfg.n_layers + k - 1) // k if k else 0


def cache_len(cfg: ModelConfig, max_seq: int) -> int:
    return min(cfg.sliding_window, max_seq) if cfg.sliding_window else max_seq


def padded_frames(cfg: ModelConfig) -> int:
    """Cross-attn cache length, padded so the seq dim shards (1500->1536)."""
    return (cfg.enc_frames + 255) // 256 * 256


# ---------------------------------------------------------------------------
# cache construction
# ---------------------------------------------------------------------------
def init_cache(cfg: ModelConfig, B: int, max_seq: int, abstract: bool = False):
    """Zeroed (or ShapeDtypeStruct) cache pytree for ``decode_step``."""
    mk = ((lambda s, d: jax.ShapeDtypeStruct(s, d)) if abstract
          else (lambda s, d: jnp.zeros(s, d)))
    # empty cache slots must read as position -1 (invalid)
    mk_slots = ((lambda s: jax.ShapeDtypeStruct(s, jnp.int32)) if abstract
                else (lambda s: jnp.full(s, -1, jnp.int32)))
    L, KV, hd = cfg.n_layers, cfg.n_kv_heads, cfg.hd
    Sc = cache_len(cfg, max_seq)
    c: dict = {"pos": mk((B,), jnp.int32)}
    fam = cfg.family

    if fam in ("ssm", "hybrid"):
        H, N, Pd = cfg.ssm_heads, cfg.ssm_state, cfg.ssm_head_dim
        cd = cfg.d_inner + 2 * N
        c["state"] = mk((L, B, H, N, Pd), F32)
        c["conv"] = mk((L, B, cfg.ssm_conv - 1, cd), cfg.dtype)
        if fam == "hybrid":
            na = _n_attn(cfg)
            c["ak"] = mk((na, B, Sc, KV, hd), cfg.dtype)
            c["av"] = mk((na, B, Sc, KV, hd), cfg.dtype)
            c["slot_pos"] = mk_slots((B, Sc))
        return c

    if cfg.attention == "mla":
        R, dr = cfg.kv_lora_rank, cfg.qk_rope_dim
        n_scan = cfg.n_layers - cfg.first_dense_layers
        c["ckv"] = mk((n_scan, B, Sc, R), cfg.dtype)
        c["kr"] = mk((n_scan, B, Sc, dr), cfg.dtype)
        if cfg.first_dense_layers:
            c["d_ckv"] = mk((cfg.first_dense_layers, B, Sc, R), cfg.dtype)
            c["d_kr"] = mk((cfg.first_dense_layers, B, Sc, dr), cfg.dtype)
        c["slot_pos"] = mk_slots((B, Sc))
        return c

    c["k"] = mk((L, B, Sc, KV, hd), cfg.dtype)
    c["v"] = mk((L, B, Sc, KV, hd), cfg.dtype)
    c["slot_pos"] = mk_slots((B, Sc))
    if cfg.is_encoder_decoder:
        Fp = padded_frames(cfg)
        c["xk"] = mk((L, B, Fp, KV, hd), cfg.dtype)
        c["xv"] = mk((L, B, Fp, KV, hd), cfg.dtype)
    return c


def cache_pspecs(cfg: ModelConfig, ctx: MeshCtx, ba=...):
    """PartitionSpec tree matching init_cache (seq -> model axis).
    ``ba`` overrides the batch axes (None for non-divisible batches)."""
    from jax.sharding import PartitionSpec as P
    m = ctx.model_axis
    if ba is ...:
        ba = ctx.batch_axes
    specs = {"pos": P(ba)}
    fam = cfg.family
    if fam in ("ssm", "hybrid"):
        specs["state"] = P(None, ba, None, None, None)
        specs["conv"] = P(None, ba, None, None)
        if fam == "hybrid":
            specs["ak"] = specs["av"] = P(None, ba, m, None, None)
            specs["slot_pos"] = P(ba, m)
        return specs
    if cfg.attention == "mla":
        specs["ckv"] = P(None, ba, m, None)
        specs["kr"] = P(None, ba, m, None)
        if cfg.first_dense_layers:
            specs["d_ckv"] = P(None, ba, m, None)
            specs["d_kr"] = P(None, ba, m, None)
        specs["slot_pos"] = P(ba, m)
        return specs
    specs["k"] = specs["v"] = P(None, ba, m, None, None)
    specs["slot_pos"] = P(ba, m)
    if cfg.is_encoder_decoder:
        specs["xk"] = specs["xv"] = P(None, ba, m, None, None)
    return specs


# ---------------------------------------------------------------------------
# per-layer decode helpers
# ---------------------------------------------------------------------------
def _gqa_decode(x1, ap, cfg, ctx, kc, vc, slot_pos, pos, slot, *,
                kv_cache_only=False):
    """x1: (B,1,D). kc/vc: (B,Sc,KV,hd). Returns (attn_out, kc, vc)."""
    B = x1.shape[0]
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = jnp.einsum("bsd,dh->bsh", x1, ap["wq"]).reshape(B, 1, H, hd)
    kn = jnp.einsum("bsd,dh->bsh", x1, ap["wk"]).reshape(B, 1, KV, hd)
    vn = jnp.einsum("bsd,dh->bsh", x1, ap["wv"]).reshape(B, 1, KV, hd)
    if cfg.rope_theta:
        q = rope(q, pos[:, None], cfg.rope_theta)
        kn = rope(kn, pos[:, None], cfg.rope_theta)
    kc = kc.at[jnp.arange(B), slot].set(kn[:, 0])
    vc = vc.at[jnp.arange(B), slot].set(vn[:, 0])
    out = decode_attention(q, kc, vc, slot_pos, pos)
    out = jnp.einsum("bsh,hd->bsd", out.reshape(B, 1, H * hd), ap["wo"])
    return out, kc, vc


def _cross_decode(x1, ap, cfg, xk, xv, enc_len):
    """Cross-attention over the (padded) frozen encoder cache; slots beyond
    enc_len are masked via slot_pos > pos."""
    B = x1.shape[0]
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = jnp.einsum("bsd,dh->bsh", x1, ap["wq"]).reshape(B, 1, H, hd)
    slot_pos = jnp.broadcast_to(jnp.arange(xk.shape[1]), (B, xk.shape[1]))
    out = decode_attention(q, xk, xv, slot_pos,
                           jnp.full((B,), enc_len - 1, jnp.int32))
    return jnp.einsum("bsh,hd->bsd", out.reshape(B, 1, H * hd), ap["wo"])


def _mla_decode(x1, ap, cfg, ckv_c, kr_c, slot_pos, pos, slot):
    """Absorbed-form MLA decode. ckv_c: (B,Sc,R); kr_c: (B,Sc,dr)."""
    B = x1.shape[0]
    H = cfg.n_heads
    R, dn, dr, dv = (cfg.kv_lora_rank, cfg.qk_nope_dim, cfg.qk_rope_dim,
                     cfg.v_head_dim)
    q = rmsnorm(jnp.einsum("bsd,dq->bsq", x1, ap["wq_a"]), ap["q_norm"],
                cfg.norm_eps)
    q = jnp.einsum("bsq,qh->bsh", q, ap["wq_b"]).reshape(B, 1, H, dn + dr)
    qn, qr = q[..., :dn], rope(q[..., dn:], pos[:, None], cfg.rope_theta)

    new = jnp.einsum("bsd,dr->bsr", x1, ap["wkv_a"])
    ckv_n = rmsnorm(new[..., :R], ap["kv_norm"], cfg.norm_eps)
    kr_n = rope(new[:, :, None, R:], pos[:, None], cfg.rope_theta)[:, :, 0]
    ckv_c = ckv_c.at[jnp.arange(B), slot].set(ckv_n[:, 0])
    kr_c = kr_c.at[jnp.arange(B), slot].set(kr_n[:, 0])

    wkv_b = ap["wkv_b"].reshape(R, H, dn + dv)
    wk, wv = wkv_b[..., :dn], wkv_b[..., dn:]
    q_lat = jnp.einsum("bhd,rhd->bhr", qn[:, 0].astype(F32),
                       wk.astype(F32))                       # absorb W_uk
    s = (jnp.einsum("bhr,bsr->bhs", q_lat, ckv_c.astype(F32))
         + jnp.einsum("bhd,bsd->bhs", qr[:, 0].astype(F32),
                      kr_c.astype(F32))) / jnp.sqrt(float(dn + dr))
    valid = (slot_pos >= 0) & (slot_pos <= pos[:, None])
    s = jnp.where(valid[:, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o_lat = jnp.einsum("bhs,bsr->bhr", p, ckv_c.astype(F32))
    out = jnp.einsum("bhr,rhd->bhd", o_lat, wv.astype(F32))  # absorb W_uv
    out = out.reshape(B, 1, H * dv).astype(x1.dtype)
    return jnp.einsum("bsh,hd->bsd", out, ap["wo"]), ckv_c, kr_c


# ---------------------------------------------------------------------------
# decode_step (the serve_step lowered for decode_* / long_* shapes)
# ---------------------------------------------------------------------------
def decode_step(params, cache, tokens, cfg: ModelConfig, ctx: MeshCtx):
    """One new token per sequence. tokens: (B,). Returns (logits, cache)."""
    B = tokens.shape[0]
    pos = cache["pos"]                              # (B,)
    x = embed_tokens(params, tokens[:, None], cfg, None)
    fam = cfg.family
    cache = dict(cache)

    if fam in ("ssm", "hybrid"):
        if fam == "hybrid":
            Sc = cache["ak"].shape[2]
            slot = pos % Sc
            slot_pos = cache["slot_pos"].at[jnp.arange(B), slot].set(pos)
            cache["slot_pos"] = slot_pos
            ak_all, av_all = cache["ak"], cache["av"]

        def step(carry, xs):
            x1, ak, av = carry
            i, lp, st, cv = xs
            if fam == "hybrid" and cfg.shared_attn_every:
                static = isinstance(i, int)
                def with_attn(op):
                    x1, ak, av = op
                    ai = i // cfg.shared_attn_every
                    bp = params["shared_block"]["attn"]
                    y, k2, v2 = _gqa_decode(
                        rmsnorm(x1, bp["ln"], cfg.norm_eps), bp, cfg, ctx,
                        ak[ai], av[ai], slot_pos, pos, slot)
                    x1 = x1 + y
                    mp = params["shared_block"]["mlp"]
                    x1 = x1 + mlp_forward(
                        rmsnorm(x1, mp["ln"], cfg.norm_eps), mp, cfg)
                    ak = jax.lax.dynamic_update_index_in_dim(ak, k2, ai, 0)
                    av = jax.lax.dynamic_update_index_in_dim(av, v2, ai, 0)
                    return x1, ak, av
                if static:
                    if i % cfg.shared_attn_every == 0:
                        x1, ak, av = with_attn((x1, ak, av))
                else:
                    x1, ak, av = jax.lax.cond(
                        i % cfg.shared_attn_every == 0, with_attn,
                        lambda op: op, (x1, ak, av))
            mp = lp["mamba"]
            h = rmsnorm(x1, mp["ln"], cfg.norm_eps)
            y, (st, cv) = mamba2_mixer(h, mp, cfg, ctx, state=st,
                                       conv_state=cv, decode=True)
            return (x1 + y, ak, av), (st, cv)

        na = _n_attn(cfg)
        dummy = (jnp.zeros((max(na, 1), B, 1, 1, 1), cfg.dtype),) * 2
        carry0 = (x, cache.get("ak", dummy[0]), cache.get("av", dummy[1]))
        if cfg.scan_layers:
            (x, ak, av), (st, cv) = jax.lax.scan(
                step, carry0,
                (jnp.arange(cfg.n_layers), params["layers"], cache["state"],
                 cache["conv"]))
        else:   # unrolled: python layer index -> static shared-attn branch
            carry, ys = carry0, []
            for i in range(cfg.n_layers):
                xs_i = tree_index((params["layers"], cache["state"],
                                   cache["conv"]), i)
                carry, y = step(carry, (i,) + xs_i)
                ys.append(y)
            (x, ak, av) = carry
            st, cv = jax.tree.map(lambda *zs: jnp.stack(zs), *ys)
        cache["state"], cache["conv"] = st, cv
        if fam == "hybrid":
            cache["ak"], cache["av"] = ak, av

    elif cfg.attention == "mla":
        Sc = cache["ckv"].shape[2]
        slot = pos % Sc
        slot_pos = cache["slot_pos"].at[jnp.arange(B), slot].set(pos)
        cache["slot_pos"] = slot_pos

        if cfg.first_dense_layers:
            def dstep(x1, xs):
                lp, ckv_l, kr_l = xs
                y, ckv_l, kr_l = _mla_decode(
                    rmsnorm(x1, lp["attn"]["ln"], cfg.norm_eps), lp["attn"],
                    cfg, ckv_l, kr_l, slot_pos, pos, slot)
                x1 = x1 + y
                x1 = x1 + mlp_forward(
                    rmsnorm(x1, lp["mlp"]["ln"], cfg.norm_eps),
                    lp["mlp"], cfg)
                return x1, (ckv_l, kr_l)
            x, (dckv, dkr) = scan_or_unroll(
                dstep, x, (params["dense_layers"], cache["d_ckv"],
                           cache["d_kr"]), scan=cfg.scan_layers)
            cache["d_ckv"], cache["d_kr"] = dckv, dkr

        def step(x1, xs):
            lp, ckv_l, kr_l = xs
            y, ckv_l, kr_l = _mla_decode(
                rmsnorm(x1, lp["attn"]["ln"], cfg.norm_eps), lp["attn"],
                cfg, ckv_l, kr_l, slot_pos, pos, slot)
            x1 = x1 + y
            if "moe" in lp:
                hn = rmsnorm(x1, lp["moe"]["ln"], cfg.norm_eps)
                y2, _ = moe_decode(hn, lp["moe"], cfg, ctx)
                if cfg.n_shared_experts:
                    y2 = y2 + mlp_swiglu(hn, lp["moe"]["sh_wg"],
                                         lp["moe"]["sh_wu"],
                                         lp["moe"]["sh_wd"])
                x1 = x1 + y2
            else:
                x1 = x1 + mlp_forward(
                    rmsnorm(x1, lp["mlp"]["ln"], cfg.norm_eps),
                    lp["mlp"], cfg)
            return x1, (ckv_l, kr_l)

        x, (ckv, kr) = scan_or_unroll(
            step, x, (params["layers"], cache["ckv"], cache["kr"]),
            scan=cfg.scan_layers)
        cache["ckv"], cache["kr"] = ckv, kr

    else:  # gqa families (dense / moe / vlm / encdec)
        Sc = cache["k"].shape[2]
        slot = pos % Sc
        slot_pos = cache["slot_pos"].at[jnp.arange(B), slot].set(pos)
        cache["slot_pos"] = slot_pos

        def step(x1, xs):
            lp, kc, vc, *xkv = xs
            ap = lp["attn"]
            y, kc, vc = _gqa_decode(rmsnorm(x1, ap["ln"], cfg.norm_eps), ap,
                                    cfg, ctx, kc, vc, slot_pos, pos, slot)
            x1 = x1 + y
            if cfg.is_encoder_decoder:
                xp = lp["xattn"]
                x1 = x1 + _cross_decode(rmsnorm(x1, xp["ln"], cfg.norm_eps),
                                        xp, cfg, xkv[0], xkv[1],
                                        cfg.enc_frames)
            if "moe" in lp:
                hn = rmsnorm(x1, lp["moe"]["ln"], cfg.norm_eps)
                y2, _ = moe_decode(hn, lp["moe"], cfg, ctx)
                x1 = x1 + y2
            else:
                x1 = x1 + mlp_forward(
                    rmsnorm(x1, lp["mlp"]["ln"], cfg.norm_eps),
                    lp["mlp"], cfg)
            return x1, (kc, vc)

        layer_p = (params["dec_layers"] if cfg.is_encoder_decoder
                   else params["layers"])
        xs = ((layer_p, cache["k"], cache["v"], cache["xk"], cache["xv"])
              if cfg.is_encoder_decoder
              else (layer_p, cache["k"], cache["v"]))
        x, (k, v) = scan_or_unroll(step, x, xs, scan=cfg.scan_layers)
        cache["k"], cache["v"] = k, v

    cache["pos"] = pos + 1
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = jnp.einsum("bsd,dv->bsv", x, unembed_matrix(params, cfg))
    logits = cs(logits[:, 0], ctx, "B", "M")
    return logits, cache


# ---------------------------------------------------------------------------
# prefill_step (the serve_step lowered for prefill_* shapes)
# ---------------------------------------------------------------------------
def prefill_step(params, batch, cfg: ModelConfig, ctx: MeshCtx,
                 last_index=None):
    """Full-sequence prefill: returns (last-token logits, populated cache).

    ``last_index`` (int32 (B,), optional) selects which position's hidden
    state feeds the logits — the last *real* token when the batch is
    right-padded to a block boundary (paged serving, SERVING.md §3).
    Right padding never perturbs earlier positions (causal attention), so
    the default ``h[:, -1]`` remains exact for unpadded prompts."""
    fwd = forward_encdec if cfg.is_encoder_decoder else forward_lm
    h, _, kvs = fwd(params, batch, cfg, ctx, collect_kv=True)
    B, S, _ = h.shape
    h_last = (h[:, -1] if last_index is None
              else h[jnp.arange(B), last_index])
    logits = jnp.einsum("bd,dv->bv", h_last, unembed_matrix(params, cfg))
    logits = cs(logits, ctx, "B", "M")

    cache = init_cache(cfg, B, S)
    cache["pos"] = jnp.full((B,), S, jnp.int32)
    fam = cfg.family

    if fam in ("ssm", "hybrid"):
        state, conv = kvs["layers"]
        cache["state"], cache["conv"] = state, conv
        # hybrid shared-attn kv is recomputed at decode start (documented)
        return logits, cache

    def ring(t):  # (L,B,S,KV,hd)->(L,B,Sc,·) ring layout for sliding window
        Sc = cache_len(cfg, S)
        if Sc == S:
            return t
        shift = (S - Sc) % Sc
        return jnp.roll(t[:, :, -Sc:], shift, axis=2)

    Sc = cache_len(cfg, S)
    if Sc == S:
        slot_pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    else:
        shift = (S - Sc) % Sc
        slot_pos = jnp.broadcast_to(
            S - Sc + (jnp.arange(Sc) - shift) % Sc, (B, Sc))
    cache["slot_pos"] = slot_pos.astype(jnp.int32)

    if cfg.attention == "mla":
        assert Sc == S, "MLA archs have no sliding window"
        ckv, kr = kvs["layers"]
        cache["ckv"], cache["kr"] = ckv, kr
        if kvs.get("dense") is not None:
            dckv, dkr = kvs["dense"]
            cache["d_ckv"], cache["d_kr"] = dckv, dkr
        return logits, cache

    if cfg.is_encoder_decoder:
        (sk, sv), (xk, xv) = kvs["layers"]
        cache["k"], cache["v"] = sk, sv
        Fp = padded_frames(cfg)
        pad = Fp - xk.shape[2]
        cache["xk"] = jnp.pad(xk, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        cache["xv"] = jnp.pad(xv, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        return logits, cache

    k, v = kvs["layers"]
    cache["k"], cache["v"] = ring(k), ring(v)
    return logits, cache


# ---------------------------------------------------------------------------
# paged decode (serving, SERVING.md §3): gather-by-block-table
# ---------------------------------------------------------------------------
def paged_supported(cfg: ModelConfig, max_seq: int) -> bool:
    """The paged path covers the plain-GQA text families (dense/moe) with
    a non-ring cache; MLA/SSM/hybrid/encdec/vlm and sliding-window rings
    use the dense slot fallback (support matrix in SERVING.md §3)."""
    return (cfg.attention == "gqa" and cfg.family not in ("ssm", "hybrid")
            and not cfg.is_encoder_decoder and not cfg.n_patches
            and cache_len(cfg, max_seq) == max_seq)


#: lanes of a TPU vector register: the paged pools' minor dimension
LANES = 128


def pool_shape(n_blocks: int, L: int, block: int, KV: int, hd: int) -> tuple:
    """Shape of ``n_blocks`` paged KV blocks: (n_blocks, L, R, LANES) with
    R = block*KV*hd/LANES. A block's values keep their (block, KV, hd)
    order, flattened into lane-wide rows, so writing a whole block by id
    is a dense row update in the layout the pool is stored in; a
    (block, KV, hd) minor shape is tiled differently from what the
    scatter wants, and the compiler copies the whole pool around it
    (SERVING.md §3)."""
    width = block * KV * hd
    if width % LANES:
        raise ValueError(f"a paged KV block of {block} x {KV} x {hd} "
                         f"values is not a multiple of {LANES} lanes")
    return (n_blocks, L, width // LANES, LANES)


def cache_to_blocks(cache: dict, block: int):
    """Chop a B=1 prefill cache into KV blocks: (L,1,S,KV,hd) k/v ->
    ``pool_shape(S//block, L, block, KV, hd)``, ready to scatter into the
    pools by block id. S must be a block multiple (right-pad the
    prompt)."""
    k, v = cache["k"], cache["v"]
    L, _, S, KV, hd = k.shape
    assert S % block == 0, (S, block)
    shape = pool_shape(S // block, L, block, KV, hd)

    def chop(t):
        t = t[:, 0].reshape(L, S // block, block * KV * hd)
        return t.transpose(1, 0, 2).reshape(shape)
    return chop(k), chop(v)


def paged_decode_step(params, k_pool, v_pool, table, pos, tokens,
                      cfg: ModelConfig, ctx: MeshCtx):
    """One decode token per sequence against paged KV pools.

    k_pool/v_pool: ``pool_shape(P, L, block, KV, hd)`` — the pool, indexed
                   by block id.
    table:         (B, nb) int32 — per-slot block tables (id 0 = the null
                   block for unused entries / empty slots).
    pos:           (B,) int32 absolute position of the incoming token.
    tokens:        (B,) int32.

    Semantics are identical to ``decode_step`` on the dense cache the
    table describes: the pools are gathered by table and unflattened to
    (L, B, nb*block, KV, hd), ``slot_pos`` is reconstructed from ``pos``
    (slot i holds position i — no ring, enforced by ``paged_supported``),
    and after the step only the block containing the written slot is
    flattened to (L, R, LANES) and scattered back. Empty slots point at
    the null block, which absorbs their garbage writes.
    Returns (logits, k_pool, v_pool).
    """
    B, nb = table.shape
    P, L, R, _ = k_pool.shape
    KV, hd = cfg.n_kv_heads, cfg.hd
    block = R * LANES // (KV * hd)
    assert k_pool.shape == pool_shape(P, L, block, KV, hd), k_pool.shape
    Sc = nb * block

    def gather(pool):
        t = pool[table].reshape(B, nb, L, block, KV, hd)
        return t.transpose(2, 0, 1, 3, 4, 5).reshape(L, B, Sc, KV, hd)

    iota = jnp.arange(Sc, dtype=jnp.int32)
    slot_pos = jnp.where(iota[None, :] < pos[:, None], iota[None, :], -1)
    cache = {"pos": pos, "slot_pos": slot_pos,
             "k": gather(k_pool), "v": gather(v_pool)}
    logits, cache = decode_step(params, cache, tokens, cfg, ctx)

    bi = pos // block                            # block just written, per row

    def cut(row, b):                             # row: (L, Sc, KV, hd)
        t = jax.lax.dynamic_slice_in_dim(row, b * block, block, axis=1)
        return t.reshape(L, R, LANES)
    ids = table[jnp.arange(B), bi]
    k_pool = k_pool.at[ids].set(jax.vmap(cut, in_axes=(1, 0))(cache["k"], bi))
    v_pool = v_pool.at[ids].set(jax.vmap(cut, in_axes=(1, 0))(cache["v"], bi))
    return logits, k_pool, v_pool
