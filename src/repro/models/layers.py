"""Core transformer layers: norms, RoPE, attention, MLP, MoE.

Attention comes in two flavours:

* ``chunked_attention`` — FlashAttention2-style online softmax over KV
  chunks, expressed with ``jax.lax.scan`` (pure jnp; the Pallas kernel in
  ``repro.kernels.flash_attention`` implements the same contract for TPU and
  is validated against this code path).
* ``decode_attention`` — single-query attention over a (possibly
  sequence-sharded) KV cache; GSPMD turns the softmax reductions over the
  sharded seq axis into all-reduces (flash-decoding style).

The MoE block is an explicit shard_map EP(+expert-TP) hybrid:
``ep = gcd(n_experts, model_axis)`` expert-parallel groups x
``tpi = model_axis // ep``-way tensor parallel within each expert, with
all_to_all token dispatch/return. ``tpi == 1`` degenerates to pure EP
(deepseek-v2: 160 experts / 16 chips); mixtral (8 experts / 16 chips) runs
ep=8 x tpi=2 with the partial-sum-on-return trick (no grouped psum needed).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.sharding.ctx import MeshCtx

F32 = jnp.float32
NEG_INF = -1e30


def tree_index(tree, i):
    """Slice every leaf of a pytree at index ``i`` along axis 0 (binds
    ``i`` eagerly, safe inside python loops)."""
    return jax.tree.map(lambda a: a[i], tree)


def scan_or_unroll(step, carry, xs, *, scan: bool, length: int | None = None):
    """lax.scan, or an unrolled python loop (dry-run mode, so XLA's cost
    analysis sees every iteration — while-loop bodies are counted once)."""
    if scan:
        return jax.lax.scan(step, carry, xs, length=length)
    n = length if xs is None else jax.tree.leaves(xs)[0].shape[0]
    ys = []
    for i in range(n):
        x_i = None if xs is None else tree_index(xs, i)
        carry, y = step(carry, x_i)
        ys.append(y)
    ys = (jax.tree.map(lambda *zs: jnp.stack(zs), *ys)
          if ys and jax.tree.leaves(ys[0]) else None)
    return carry, ys


# ---------------------------------------------------------------------------
# norms / positions
# ---------------------------------------------------------------------------
def rmsnorm(x, w, eps: float = 1e-5):
    h = x.astype(F32)
    h = h * jax.lax.rsqrt(jnp.mean(h * h, axis=-1, keepdims=True) + eps)
    return (h * w.astype(F32)).astype(x.dtype)


def rope(x, positions, theta: float):
    """Rotary embedding. x: (..., S, H, hd); positions: (..., S)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=F32) / half)
    ang = positions[..., :, None].astype(F32) * freqs            # (..., S, half)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half].astype(F32), x[..., half:].astype(F32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_pos(positions, d_model: int, dtype):
    """Additive sinusoidal positions (whisper-style stub)."""
    half = d_model // 2
    freqs = jnp.exp(-math.log(10_000.0) * jnp.arange(half, dtype=F32)
                    / max(half - 1, 1))
    ang = positions[..., None].astype(F32) * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1).astype(dtype)


# ---------------------------------------------------------------------------
# attention (train / prefill): chunked online softmax
# ---------------------------------------------------------------------------
def chunked_attention(q, k, v, *, causal: bool, window: int = 0,
                      q_start=0, kv_len: int | None = None,
                      chunk: int = 1024, unroll: bool = False):
    """q: (B, Sq, H, hd); k, v: (B, Sk, KV, hd). GQA via head grouping.

    Online-softmax scan over KV chunks; fp32 accumulators. ``window`` > 0
    adds a sliding-window lower bound. ``kv_len`` masks ragged tails after
    padding Sk up to a chunk multiple.
    """
    B, Sq, H, hd = q.shape
    Bk, Sk, KV, _ = k.shape
    hdv = v.shape[-1]                     # may differ from hd (MLA)
    G = H // KV
    scale = 1.0 / math.sqrt(hd)

    chunk = min(chunk, Sk)
    if unroll:                       # cap the unrolled body count at 16
        chunk = max(chunk, (Sk + 15) // 16)
    pad = (-Sk) % chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        if kv_len is None:
            kv_len = Sk
    n_chunks = (Sk + pad) // chunk

    qg = q.reshape(B, Sq, KV, G, hd)
    q_pos = q_start + jnp.arange(Sq)

    kc = k.reshape(B, n_chunks, chunk, KV, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, n_chunks, chunk, KV, hdv).transpose(1, 0, 2, 3, 4)

    def body(carry, xs):
        m, l, acc = carry
        kci, vci, c_start = xs
        # scores: (B, KV, G, Sq, chunk)
        s = jnp.einsum("bqkgh,bckh->bkgqc", qg.astype(F32), kci.astype(F32),
                       preferred_element_type=F32) * scale
        kv_pos = c_start + jnp.arange(chunk)
        mask = jnp.ones((Sq, chunk), bool)
        if causal:
            mask &= kv_pos[None, :] <= q_pos[:, None]
        if window:
            mask &= kv_pos[None, :] > q_pos[:, None] - window
        if kv_len is not None:
            mask &= (kv_pos < kv_len)[None, :]
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(axis=-1)
        pv = jnp.einsum("bkgqc,bckh->bkgqh", p, vci.astype(F32),
                        preferred_element_type=F32)
        acc = acc * corr[..., None] + pv
        return (m_new, l, acc), None

    init = (jnp.full((B, KV, G, Sq), NEG_INF, F32),
            jnp.zeros((B, KV, G, Sq), F32),
            jnp.zeros((B, KV, G, Sq, hdv), F32))
    c_starts = jnp.arange(n_chunks) * chunk
    # checkpoint the chunk body: scan-AD would otherwise stack the per-chunk
    # score/probability residuals (B,KV,G,Sq,chunk f32 x n_chunks — ~60 GB
    # for deepseek train_4k) for backward; recomputing them per chunk is the
    # flash-attention trade (EXPERIMENTS §Perf, deepseek cell / iter 1).
    body_fn = body if unroll else jax.checkpoint(body)
    (m, l, acc), _ = scan_or_unroll(body_fn, init, (kc, vc, c_starts),
                                    scan=not unroll)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, hdv).astype(q.dtype)


# ---------------------------------------------------------------------------
# attention (decode): one query over a cache
# ---------------------------------------------------------------------------
def decode_attention(q, k, v, slot_pos, pos):
    """q: (B, 1, H, hd); k, v: (B, S, KV, hd); slot_pos: (B, S) int32
    absolute position held by each cache slot (-1 = empty). ``pos`` is the
    current decode position (B,). Seq-sharded caches work transparently:
    the max/sum reductions become all-reduces under GSPMD."""
    B, _, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    scale = 1.0 / math.sqrt(hd)
    qg = q.reshape(B, KV, G, hd)
    s = jnp.einsum("bkgh,bskh->bkgs", qg.astype(F32), k.astype(F32),
                   preferred_element_type=F32) * scale
    valid = (slot_pos >= 0) & (slot_pos <= pos[:, None])
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bskh->bkgh", p, v.astype(F32),
                     preferred_element_type=F32)
    return out.reshape(B, 1, H, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------
def mlp_swiglu(x, wg, wu, wd):
    g = jnp.einsum("...d,df->...f", x, wg)
    u = jnp.einsum("...d,df->...f", x, wu)
    h = jax.nn.silu(g.astype(F32)).astype(x.dtype) * u
    return jnp.einsum("...f,fd->...d", h, wd)


def mlp_gelu(x, wi, wd):
    h = jnp.einsum("...d,df->...f", x, wi)
    h = jax.nn.gelu(h.astype(F32), approximate=True).astype(x.dtype)
    return jnp.einsum("...f,fd->...d", h, wd)


# ---------------------------------------------------------------------------
# MoE: shard_map EP(+TP) hybrid with all_to_all dispatch
# ---------------------------------------------------------------------------
def moe_topology(n_experts: int, model_size: int) -> tuple[int, int, int]:
    """Returns (ep, tpi, e_loc): expert-parallel groups, intra-expert TP
    degree, experts per group."""
    ep = math.gcd(n_experts, model_size)
    tpi = model_size // ep
    e_loc = n_experts // ep
    return ep, tpi, e_loc


def moe_capacity(tokens: int, n_experts: int, top_k: int,
                 capacity_factor: float = 1.25) -> int:
    c = int(math.ceil(tokens * top_k / n_experts * capacity_factor))
    return max(8, (c + 7) // 8 * 8)


def _moe_block_local(xt, w_router, wg, wu, wd, *, n_experts, top_k, cap,
                     ep, tpi, e_loc, model_axis):
    """Per-shard body (inside shard_map). xt: (T, D) local tokens.
    wg/wu: (1, e_loc, D, F_t); wd: (1, e_loc, F_t, D)."""
    T, D = xt.shape
    M = ep * tpi

    # --- route -----------------------------------------------------------
    logits = jnp.einsum("td,de->te", xt.astype(F32), w_router.astype(F32))
    probs = jax.nn.softmax(logits, axis=-1)
    gate, ids = jax.lax.top_k(probs, top_k)                     # (T, K)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    # --- capacity-bounded dispatch buffer (E, cap, D) ----------------------
    flat_ids = ids.reshape(-1)                                  # (T*K,)
    order = jnp.argsort(flat_ids, stable=True)
    sorted_ids = flat_ids[order]
    starts = jnp.searchsorted(sorted_ids, jnp.arange(n_experts))
    pos_in_e = jnp.arange(T * top_k) - starts[sorted_ids]
    slot = jnp.where(pos_in_e < cap, sorted_ids * cap + pos_in_e,
                     n_experts * cap)                           # OOB -> drop
    xs = xt[order // top_k]                                     # (T*K, D)
    buf = jnp.zeros((n_experts * cap, D), xt.dtype).at[slot].set(
        xs, mode="drop")

    # --- all_to_all dispatch: (M, e_loc, cap, D) ----------------------------
    bufg = buf.reshape(ep, e_loc * cap, D)
    send = jnp.repeat(bufg, tpi, axis=0)                        # dup per TP half
    recv = jax.lax.all_to_all(send, model_axis, split_axis=0,
                              concat_axis=0, tiled=True)        # (M, e_loc*cap, D)

    # --- expert GEMMs (batched over local experts) --------------------------
    xr = recv.reshape(M, e_loc, cap, D).transpose(1, 0, 2, 3) \
             .reshape(e_loc, M * cap, D)
    g = jnp.einsum("etd,edf->etf", xr, wg[0])
    u = jnp.einsum("etd,edf->etf", xr, wu[0])
    h = jax.nn.silu(g.astype(F32)).astype(xr.dtype) * u
    part = jnp.einsum("etf,efd->etd", h, wd[0])                 # partial over F_t

    # --- return a2a; sum TP partials on the sender ---------------------------
    back = part.reshape(e_loc, M, cap, D).transpose(1, 0, 2, 3) \
               .reshape(M, e_loc * cap, D)
    ret = jax.lax.all_to_all(back, model_axis, split_axis=0,
                             concat_axis=0, tiled=True)
    out_buf = ret.reshape(ep, tpi, e_loc * cap, D).sum(axis=1) \
                 .reshape(n_experts * cap, D)

    # --- gather back + weighted combine -------------------------------------
    safe = jnp.minimum(slot, n_experts * cap - 1)
    y_sorted = jnp.where((slot < n_experts * cap)[:, None],
                         out_buf[safe], 0.0)
    y_exp = jnp.zeros((T * top_k, D), xt.dtype).at[order].set(y_sorted)
    y = (y_exp.reshape(T, top_k, D).astype(F32)
         * gate[..., None]).sum(axis=1).astype(xt.dtype)

    # --- load-balance aux loss ------------------------------------------------
    frac = jnp.zeros((n_experts,), F32).at[flat_ids].add(1.0) / (T * top_k)
    aux = n_experts * jnp.sum(frac * probs.mean(axis=0))
    return y, aux.reshape(1)


def moe_forward(x, p, cfg, ctx: MeshCtx, capacity_factor: float = 1.25,
                seq_sharded: bool = True):
    """x: (B, S, D), sequence-sharded over the model axis when
    ``seq_sharded`` (train/prefill). Returns (y, aux_loss)."""
    B, S, D = x.shape
    M = ctx.model_size
    ep, tpi, e_loc = moe_topology(cfg.n_experts, M)
    s_loc = S // M if seq_sharded else S
    t_loc = max(1, B // ctx.data_size) * s_loc
    cap = moe_capacity(t_loc, cfg.n_experts, cfg.top_k, capacity_factor)

    body = partial(_moe_block_local, n_experts=cfg.n_experts,
                   top_k=cfg.top_k, cap=cap, ep=ep, tpi=tpi, e_loc=e_loc,
                   model_axis=ctx.model_axis)

    ba = ctx.batch_axes

    def block(xb, w_router, wg, wu, wd):
        # FSDP: expert weights arrive sharded on their embed dim over the
        # data axes; gather them HERE so the all-gather stays inside the
        # layer scan body (hoisting it out of the loop would materialize
        # every layer's experts at once — see DESIGN.md §4).
        wg = jax.lax.all_gather(wg, ba, axis=2, tiled=True)
        wu = jax.lax.all_gather(wu, ba, axis=2, tiled=True)
        wd = jax.lax.all_gather(wd, ba, axis=3, tiled=True)
        bl, sl, d = xb.shape
        y, aux = body(xb.reshape(bl * sl, d), w_router, wg, wu, wd)
        return y.reshape(bl, sl, d), aux

    seq_spec = ctx.model_axis if seq_sharded else None
    y, aux = shard_map(
        block, mesh=ctx.mesh,
        in_specs=(P(ba, seq_spec, None), P(None, None),
                  P(ctx.model_axis, None, ba, None),
                  P(ctx.model_axis, None, ba, None),
                  P(ctx.model_axis, None, None, ba)),
        out_specs=(P(ba, seq_spec, None), P(ba)),
        check_vma=False,
    )(x, p["w_router"], p["wg"], p["wu"], p["wd"])
    return y, aux.mean()


def moe_decode(x1, p, cfg, ctx: MeshCtx):
    """Single-token MoE (decode path, B small). Two regimes:

    * ``B*K <= E`` — gather only the active experts' weights (what a real
      decode engine reads from HBM);
    * otherwise   — every expert is touched by some token: scan all experts
      in their physical (M, e_loc) layout, accumulating masked partials
      (F_t pieces sum exactly because swiglu is elementwise in F).
    """
    B, S, D = x1.shape
    E, K = cfg.n_experts, cfg.top_k
    M = ctx.model_size
    ep, tpi, e_loc = moe_topology(E, M)
    Ft = p["wg"].shape[-1]
    xt = x1.reshape(B * S, D)

    logits = jnp.einsum("td,de->te", xt.astype(F32),
                        p["w_router"].astype(F32))
    gate, ids = jax.lax.top_k(jax.nn.softmax(logits, -1), K)   # (T,K)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    if B * S * K <= E:
        # ids -> physical rows (g*tpi + h, slot)
        g = ids // e_loc
        slot = ids % e_loc
        pieces = []
        for h in range(tpi):
            m = g * tpi + h                                     # (T,K)
            wg_s = p["wg"][m, slot]                             # (T,K,D,Ft)
            wu_s = p["wu"][m, slot]
            wd_s = p["wd"][m, slot]                             # (T,K,Ft,D)
            gg = jnp.einsum("td,tkdf->tkf", xt, wg_s)
            uu = jnp.einsum("td,tkdf->tkf", xt, wu_s)
            hh = jax.nn.silu(gg.astype(F32)).astype(xt.dtype) * uu
            pieces.append(jnp.einsum("tkf,tkfd->tkd", hh, wd_s))
        y = sum(pieces)                                         # (T,K,D)
        y = (y.astype(F32) * gate[..., None]).sum(1).astype(xt.dtype)
        return y.reshape(B, S, D), jnp.zeros((), F32)

    # dense-all: scan over physical expert slices, masked accumulate
    wg = p["wg"].reshape(M * e_loc, D, Ft)
    wu = p["wu"].reshape(M * e_loc, D, Ft)
    wd = p["wd"].reshape(M * e_loc, Ft, D)

    def body(acc, i):
        m, slot = i // e_loc, i % e_loc
        e = (m // tpi) * e_loc + slot                           # logical id
        w = ((ids == e).astype(F32) * gate).sum(-1)             # (T,)
        gg = jnp.einsum("td,df->tf", xt, wg[i])
        uu = jnp.einsum("td,df->tf", xt, wu[i])
        hh = jax.nn.silu(gg.astype(F32)).astype(xt.dtype) * uu
        yy = jnp.einsum("tf,fd->td", hh, wd[i]).astype(F32)
        return acc + yy * w[:, None], None

    acc, _ = scan_or_unroll(body, jnp.zeros((B * S, D), F32),
                            jnp.arange(M * e_loc), scan=cfg.scan_layers)
    return acc.astype(x1.dtype).reshape(B, S, D), jnp.zeros((), F32)
