"""Plain float32 reference of a dense decoder-only LM with grouped-query
attention, as a ``dense_lm`` configuration file states it.

Per layer: ``h += Wo . attn(rope(Wq . n(h)), rope(Wk . n(h)), Wv . n(h))``
with causal softmax over all earlier positions and ``n`` the RMS norm
with a learned scale; then ``h += Wd . gelu_tanh(Wi . n(h))``. After the
last layer, ``logits = n(h) . U``. RoPE rotates the two halves of each
head (``x1 cos - x2 sin``, ``x1 sin + x2 cos``) at frequencies
``theta^(-i / (hd / 2))``. Everything is computed in float32 at the
``highest`` matmul precision, one sequence at a time, layer by layer, in
straightforward ``jax.numpy``; it imports nothing of the program under
test. Weights are the benchmark's own (``weights.draw``), promoted from
their served type one layer at a time.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def dims(cfg: dict) -> dict:
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    return {"d": d, "H": H, "KV": cfg["num_key_value_heads"],
            "hd": cfg.get("head_dim") or d // H,
            "eps": cfg["rms_norm_eps"], "theta": cfg["rope_theta"]}


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def fake_quant(w, kind: str | None):
    """``w`` (float32, inputs x outputs) rounded as the control's lower
    precision holds it: ``int8`` symmetric per output channel, or
    ``fp8`` (e4m3) scaled per output channel; ``None`` leaves it."""
    if kind is None:
        return w
    amax = jnp.maximum(jnp.abs(w).max(axis=-2, keepdims=True), 1e-30)
    if kind == "int8":
        scale = amax / 127.0
        return jnp.clip(jnp.round(w / scale), -127, 127) * scale
    if kind == "fp8":
        scale = amax / 448.0
        return (w / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale
    raise ValueError(f"unknown control precision {kind!r}")


def _rope(x, theta):
    S, _, hd = x.shape
    half = hd // 2
    freq = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(S, dtype=F32)[:, None] * freq
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1)


def _layer(h, lp, m, quant):
    S = h.shape[0]
    H, KV, hd = m["H"], m["KV"], m["hd"]

    def w(x):
        return fake_quant(x.astype(F32), quant)
    a = lp["attn"]
    x = _norm(h, a["ln"].astype(F32), m["eps"])
    q = _rope((x @ w(a["wq"])).reshape(S, H, hd), m["theta"])
    k = _rope((x @ w(a["wk"])).reshape(S, KV, hd), m["theta"])
    v = (x @ w(a["wv"])).reshape(S, KV, hd)
    k = jnp.repeat(k, H // KV, axis=1)
    v = jnp.repeat(v, H // KV, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v).reshape(S, H * hd)
    h = h + o @ w(a["wo"])
    f = lp["mlp"]
    x = _norm(h, f["ln"].astype(F32), m["eps"])
    u = jax.nn.gelu(x @ w(f["wi"]), approximate=True)
    return h + u @ w(f["wd"])


def _logits(params, tokens, rows, m, quant):
    """Logits (len(rows), V) of one right-padded sequence at ``rows``."""
    h = params["embed"][tokens].astype(F32)

    def step(h, lp):
        return _layer(h, lp, m, quant), None
    h, _ = jax.lax.scan(step, h, params["layers"])
    h = _norm(h[rows], params["final_norm"].astype(F32), m["eps"])
    return h @ fake_quant(params["unembed"].astype(F32), quant)


def _below_best(logits, tokens):
    return logits.max(-1) - jnp.take_along_axis(
        logits, tokens[:, None], -1)[:, 0]


@partial(jax.jit, static_argnames=("m", "quant"))
def _readings(params, tokens, rows, served, ids, m, quant):
    m = dict(m)
    ref = _logits(params, tokens, rows, m, None)
    served_gap = _below_best(ref, served)
    if quant is None:
        return served_gap, served_gap, ref[:, ids], ref[:, ids]
    low = _logits(params, tokens, rows, m, quant)
    return (served_gap, _below_best(ref, jnp.argmax(low, -1).astype(
        jnp.int32)), ref[:, ids], low[:, ids])


def readings(params, prompt, served, cfg: dict, pad_to: int, ids,
             quant: str | None = None):
    """Teacher-forced over ``prompt`` and the served tokens (the last one
    is never fed back), at every position that predicted a served token:
    the reference's best logit minus its logit of the served token (0
    when the served token is its own first choice); the same for the
    token that the control (the reference with ``quant`` weights) puts
    first; and the reference's and the control's logits of the vocabulary
    ids ``ids``. Without ``quant`` the control's readings are the
    reference's. The sequence is right-padded to ``pad_to``, which a
    causal model never reads, so one program serves every request."""
    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    if len(seq) > pad_to:
        raise ValueError(f"sequence of {len(seq)} over {pad_to}")
    n = len(served)
    toks = np.zeros(pad_to, np.int32)
    toks[:len(seq)] = seq
    rows = np.zeros(pad_to, np.int32)   # fixed shape; extra rows ignored
    rows[:n] = np.arange(len(prompt) - 1, len(prompt) - 1 + n)
    want = np.zeros(pad_to, np.int32)
    want[:n] = served
    with jax.default_matmul_precision("highest"):
        out = _readings(params, jnp.asarray(toks), jnp.asarray(rows),
                        jnp.asarray(want), jnp.asarray(ids, jnp.int32),
                        tuple(sorted(dims(cfg).items())), quant)
    return tuple(np.asarray(x)[:n] for x in out)
