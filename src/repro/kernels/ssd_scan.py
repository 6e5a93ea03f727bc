"""Mamba2 SSD (state-space duality) as a Pallas TPU kernel.

TPU adaptation of the CUDA warp-scan: one program per (batch, head, chunk);
the chunk axis is the LAST grid dimension, so the inter-chunk recurrent state
(P, N) lives in VMEM scratch and is carried across sequential grid steps.
Intra-chunk work is dense (Q,Q)/(Q,N)/(Q,P) matmuls on the MXU with f32
accumulation; Q defaults to 128 (lane-aligned).

Grouped B/C (G < H) is resolved in the BlockSpec index maps (g = h // rep),
mirroring the GQA trick in ``flash_attention``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, y_ref, s_out_ref,
                state_scr, *, Q: int, nc: int):
    h_idx = pl.program_id(1)
    c_idx = pl.program_id(2)

    @pl.when(c_idx == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0, 0].astype(jnp.float32)            # (Q, P)
    dt = dt_ref[0, 0].astype(jnp.float32)          # (Q, 1) column
    A = a_ref[h_idx]                               # SMEM scalar (per head)
    Bm = b_ref[0, 0].astype(jnp.float32)           # (Q, N)
    Cm = c_ref[0, 0].astype(jnp.float32)           # (Q, N)
    D = d_ref[h_idx]                               # SMEM scalar

    # Cumulative log-decay as a column and as a row.  Built from masked
    # (Q, Q) reductions, which lower to plain VPU/XLU work, rather than a
    # cumsum or a transpose of a (Q, 1) vector.
    ii = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    a = dt * A                                     # (Q, 1) log-decay
    a_row = jnp.sum(jnp.where(ii == jj, a, 0.0), axis=0, keepdims=True)
    dt_row = jnp.sum(jnp.where(ii == jj, dt, 0.0), axis=0, keepdims=True)
    lower = ii >= jj
    a_cs = jnp.sum(jnp.where(lower, a_row, 0.0), axis=1, keepdims=True)
    a_cs_row = jnp.sum(jnp.where(ii <= jj, a, 0.0), axis=0, keepdims=True)

    # intra-chunk: y[i] = sum_{j<=i} (C_i·B_j) exp(a_cs_i - a_cs_j) dt_j x_j
    lmat = jnp.where(lower, jnp.exp(a_cs - a_cs_row), 0.0)
    cb = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    m = cb * lmat * dt_row
    y = jax.lax.dot_general(m, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    # inter-chunk: y[i] += C_i exp(a_cs_i) S_prev^T;  S_prev: (P, N)
    s_prev = state_scr[...]
    y = y + jax.lax.dot_general(Cm * jnp.exp(a_cs), s_prev,
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
    y = y + D * x
    y_ref[0, 0] = y.astype(y_ref.dtype)

    # state update: S = S_prev * exp(a_end) + x^T (B * decay_to_end * dt)
    a_end = a_cs[Q - 1:Q, :]                       # (1, 1)
    bw = Bm * (jnp.exp(a_end - a_cs) * dt)         # (Q, N)
    sc = jax.lax.dot_general(x, bw, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (P, N)
    s_new = s_prev * jnp.exp(a_end) + sc
    state_scr[...] = s_new

    @pl.when(c_idx == nc - 1)
    def _emit_state():
        s_out_ref[0, 0] = s_new


def ssd_scan_bhlp(x, dt, A, Bm, Cm, D, *, chunk: int = 128,
                  interpret: bool = False):
    """Head-major layout.  x: (B, H, L, P); dt: (B, H, L, 1); A/D: (H,);
    Bm/Cm: (B, G, L, N).  L must be divisible by ``chunk``.  Returns
    (y (B,H,L,P) f32, final state (B,H,P,N) f32).

    Every VMEM block ends in a full ``(Q, P)``, ``(Q, N)``, ``(Q, 1)`` or
    ``(P, N)`` tile, which Mosaic accepts for any Q divisible by 8; the
    per-head scalars A and D are read from SMEM."""
    Bsz, H, L, P = x.shape
    G, N = Bm.shape[1], Bm.shape[3]
    rep = H // G
    Q = min(chunk, L)
    assert L % Q == 0, (L, Q)
    nc = L // Q
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)

    kernel = functools.partial(_ssd_kernel, Q=Q, nc=nc)
    return pl.pallas_call(
        kernel,
        grid=(Bsz, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, Q, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, Q, 1), lambda b, h, c: (b, h, c, 0)),
            smem,
            pl.BlockSpec((1, 1, Q, N),
                         lambda b, h, c, rep=rep: (b, h // rep, c, 0)),
            pl.BlockSpec((1, 1, Q, N),
                         lambda b, h, c, rep=rep: (b, h // rep, c, 0)),
            smem,
        ],
        out_specs=[
            pl.BlockSpec((1, 1, Q, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bsz, H, L, P), jnp.float32),
            jax.ShapeDtypeStruct((Bsz, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(x, dt, A, Bm, Cm, D)
