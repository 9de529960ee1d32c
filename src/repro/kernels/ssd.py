"""Mamba-2 SSD (state-space duality) chunked-scan Pallas TPU kernel.

Grid: (batch, n_chunks) with the chunk dimension sequential ("arbitrary");
the inter-chunk recurrent state lives in VMEM scratch and is carried across
grid steps — the HBM working set per step is one chunk of x/dt/B/C, and the
O(S) state recurrence never round-trips through HBM (the pure-jnp reference
in ``repro.models.ssm`` materializes per-chunk states; this kernel is the
perf-critical fusion for the mamba2/zamba2 architectures).

The SSD recurrence is order-dependent, so the paper's serpentine schedule
does not apply here (documented in DESIGN.md); the reciprocating insight
lands in this kernel family via the flash-attention KV schedule instead.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

F32 = jnp.float32


def _kernel(alog_ref, x_ref, dt_ref, b_ref, c_ref, y_ref, st_scr, *,
            chunk):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        st_scr[...] = jnp.zeros_like(st_scr)

    A = -jnp.exp(alog_ref[...].astype(F32))          # (H,)
    x = x_ref[0].astype(F32)                          # (Q, H, P)
    dt = dt_ref[0].astype(F32)                        # (Q, H)
    bq = b_ref[0].astype(F32)                         # (Q, N)
    cq = c_ref[0].astype(F32)                         # (Q, N)

    la = dt * A[None, :]                              # (Q, H) log decay
    bx = x * dt[..., None]                            # (Q, H, P)
    cum = jnp.cumsum(la, axis=0)                      # (Q, H)
    total = cum[-1:, :]                               # (1, H)

    # intra-chunk (masked attention-like term)
    cb = jax.lax.dot_general(cq, bq, (((1,), (1,)), ((), ())),
                             preferred_element_type=F32)   # (Q, Q)
    seg = cum[:, None, :] - cum[None, :, :]           # (Q, Q, H)
    iota = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    iota_j = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = (iota >= iota_j)[..., None]
    m = jnp.where(causal, jnp.exp(seg), 0.0) * cb[..., None]
    y = jnp.einsum("ijh,jhp->ihp", m, bx, preferred_element_type=F32)

    # inter-chunk: carried state contribution
    state = st_scr[...]                               # (H, N, P)
    decay_in = jnp.exp(cum)                           # (Q, H)
    y += jnp.einsum("in,hnp,ih->ihp", cq, state, decay_in,
                    preferred_element_type=F32)

    # state update
    decay_out = jnp.exp(total - cum)                  # (Q, H)
    inj = jnp.einsum("jn,jhp,jh->hnp", bq, bx, decay_out,
                     preferred_element_type=F32)
    st_scr[...] = state * jnp.exp(total)[0, :, None, None] + inj

    y_ref[0] = y.astype(y_ref.dtype)


def ssd_scan_fwd(x, dt, a_log, bmat, cmat, *, chunk=128, interpret=False):
    """x: (B,S,H,P); dt: (B,S,H); a_log: (H,); bmat/cmat: (B,S,N).
    Returns y (B,S,H,P)."""
    B, S, H, Pd = x.shape
    N = bmat.shape[-1]
    chunk = min(chunk, S)
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk

    kernel = functools.partial(_kernel, chunk=chunk)
    y = pl.pallas_call(
        kernel,
        grid=(B, nc),
        in_specs=[
            pl.BlockSpec((H,), lambda b, c: (0,)),
            pl.BlockSpec((1, chunk, H, Pd), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((1, chunk, H), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, c: (b, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, H, Pd), lambda b, c: (b, c, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, S, H, Pd), x.dtype),
        scratch_shapes=[pltpu.VMEM((H, N, Pd), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(a_log, x, dt, bmat, cmat)
    return y
