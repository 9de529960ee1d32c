"""FlashAttention-2 Pallas TPU kernel with a *reciprocating* KV schedule.

The paper's §9/App. C insight: a palindromic (boustrophedonic) service order
beats FIFO re-scanning whenever a decaying cache is shared — residual
residency is maximized at the turn. The TPU analogue is the Pallas grid
pipeline: when two consecutive grid steps map a block to the same HBM
region, the DMA is elided (the block is already resident in VMEM).

With q-blocks outer and kv-blocks inner, the classic schedule re-scans KV
ascending for every q row: the last KV block of row i and the first KV
block of row i+1 differ => every row boundary refetches. The
``serpentine`` schedule reverses direction on alternate rows (exactly the
paper's palindrome): the boundary block is *revisited* and its fetch is
elided — (n_q - 1) KV+V block fetches saved per (batch, head), plus better
pipeline overlap at the turn. Online softmax is order-invariant, so the
result is identical.

Layouts: q (B, H, Sq, hd); k, v (B, KV, Sk, hd) — GQA is handled by the
index map (head h reads kv head h // (H // KV)); no materialized repeat.
Causal and sliding-window masking compose; fully-masked blocks contribute
zeros (the hillclimb pass adds block skipping).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

F32 = jnp.float32
NEG_INF = -1e30


def kv_visit_index(qi, ki, n_kv: int, schedule: str):
    """Actual kv block visited at inner step ki of q row qi (works on both
    python ints and traced scalars)."""
    if schedule == "serpentine":
        rev = qi % 2 == 1
        fwd_ki = ki
        rev_ki = n_kv - 1 - ki
        if isinstance(rev, bool):
            return rev_ki if rev else fwd_ki
        return jax.lax.select(rev, rev_ki, fwd_ki)
    return ki


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale, causal, window, n_kv, block_q, block_k, schedule,
            sq_valid, sk_valid):
    qi = pl.program_id(2)
    kis = pl.program_id(3)
    ki = kv_visit_index(qi, kis, n_kv, schedule)

    @pl.when(kis == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(F32)                      # (bq, hd)
    k = k_ref[0, 0].astype(F32)                      # (bk, hd)
    v = v_ref[0, 0].astype(F32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=F32) * scale  # (bq, bk)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    kv_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = (q_pos < sq_valid) & (kv_pos < sk_valid)
    if causal:
        mask &= kv_pos <= q_pos
    if window:
        mask &= kv_pos > q_pos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + p.sum(axis=1)
    acc_scr[...] = (acc_scr[...] * corr[:, None]
                    + jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                          preferred_element_type=F32))
    m_scr[...] = m_new

    @pl.when(kis == n_kv - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_fwd(q, k, v, *, causal=True, window=0,
                        schedule="serpentine", block_q=128, block_k=128,
                        interpret=False):
    """q: (B, H, Sq, hd); k, v: (B, KV, Sk, hd) -> (B, H, Sq, hd)."""
    B, H, Sq, hd = q.shape
    KV, Sk = k.shape[1], k.shape[2]
    G = H // KV
    scale = 1.0 / math.sqrt(hd)

    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    pq = (-Sq) % block_q
    pk = (-Sk) % block_k
    if pq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0)))
    n_q = (Sq + pq) // block_q
    n_kv = (Sk + pk) // block_k

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window, n_kv=n_kv,
        block_q=block_q, block_k=block_k, schedule=schedule,
        sq_valid=Sq, sk_valid=Sk)

    def kv_map(b, h, qi, ki):
        return (b, h // G, kv_visit_index(qi, ki, n_kv, schedule), 0)

    out = pl.pallas_call(
        kernel,
        grid=(B, H, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, hd), kv_map),
            pl.BlockSpec((1, 1, block_k, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq + pq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), F32),
            pltpu.VMEM((block_q,), F32),
            pltpu.VMEM((block_q, hd), F32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :Sq, :]


# ---------------------------------------------------------------------------
# structural DMA accounting (the serpentine win, measured from index maps)
# ---------------------------------------------------------------------------
def count_kv_fetches(n_q: int, n_kv: int, schedule: str) -> int:
    """Walk the grid order and count HBM->VMEM KV fetches, eliding
    repeats of the immediately previous block (Pallas pipeline rule)."""
    fetches, prev = 0, None
    for qi in range(n_q):
        for kis in range(n_kv):
            ki = kv_visit_index(qi, kis, n_kv, schedule)
            if ki != prev:
                fetches += 1
            prev = ki
    return fetches


def serpentine_savings(n_q: int, n_kv: int) -> dict:
    asc = count_kv_fetches(n_q, n_kv, "ascending")
    ser = count_kv_fetches(n_q, n_kv, "serpentine")
    return {"ascending": asc, "serpentine": ser,
            "saved_fraction": (asc - ser) / asc}
