"""jit'd public wrappers around the Pallas kernels.

On CPU (this container) kernels run in ``interpret=True`` mode — the kernel
body executes in Python for correctness validation; on TPU the same
``pallas_call`` lowers to Mosaic.  Layout/padding adaptation to the model
code's conventions happens here, never inside the kernels.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .ddpm_step import ddpm_step_2d
from .flash_attention import flash_attention_bhld
from .mla_decode import mla_decode_bhw
from .ssd_scan import ssd_scan_bhlp


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk"))
def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None, bq: int = 128,
                    bk: int = 128):
    """q: (B, L, H, D); k/v: (B, S, Hkv, D) — model-layer layout.  Pads L/S
    to block multiples (causal masking keeps padded K columns inert for real
    rows) and transposes to the kernel's (B, H, L, D)."""
    B, L, H, D = q.shape
    S = k.shape[1]
    bq_ = min(bq, max(8, L))
    bk_ = min(bk, max(8, S))
    Lp = -(-L // bq_) * bq_
    Sp = -(-S // bk_) * bk_
    qp = jnp.pad(q, ((0, 0), (0, Lp - L), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, Sp - S), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, Sp - S), (0, 0), (0, 0)))
    o = flash_attention_bhld(
        qp.transpose(0, 2, 1, 3), kp.transpose(0, 2, 1, 3),
        vp.transpose(0, 2, 1, 3), causal=causal, window=window,
        bq=bq_, bk=bk_, interpret=_interpret())
    return o.transpose(0, 2, 1, 3)[:, :L]


@functools.partial(jax.jit, static_argnames=("scale", "width", "window"))
def mla_decode(q, own, kv, pos, layer, *, scale: float, width: int,
               window: Optional[int] = None):
    """Absorbed-MLA decode attention.  q: (B, H, W) query rows; own: (B, W)
    the new tokens' cache rows; kv: the stacked cache (R, B, S, W), read at
    ``layer``; pos: the new tokens' positions, a scalar or (B,).  Returns
    the context's first ``width`` columns (B, H, width) in q.dtype.  With
    ``own=None``, the split form of ``mla_decode_bhw``: (o, lse) over this
    device's rows of a cache split along its sequence."""
    B = q.shape[0]
    pos = jnp.broadcast_to(jnp.reshape(pos, (-1,)), (B,)).astype(jnp.int32)
    layer = jnp.reshape(layer, (1,)).astype(jnp.int32)
    if own is not None:
        own = own.astype(kv.dtype)[:, None]
    return mla_decode_bhw(q, own, kv, pos, layer, scale=scale, width=width,
                          window=window, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd_scan(x, dt, A, Bm, Cm, D, *, chunk: int = 128):
    """Mamba2 SSD.  x: (B, L, H, P); dt: (B, L, H); Bm/Cm: (B, L, G, N).
    Pads L with inert (dt = 0) steps.  Returns (y, final_state)."""
    B, L, H, P = x.shape
    Q = min(chunk, L)
    Lp = -(-L // Q) * Q
    if Lp != L:
        pad = Lp - L
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0), (0, 0)))
    # head-major for the kernel: (B, L, H, P) -> (B, H, L, P), dt as a
    # (B, H, L, 1) column
    f32 = lambda a: a.astype(jnp.float32)
    y, s = ssd_scan_bhlp(
        f32(x).transpose(0, 2, 1, 3), f32(dt).transpose(0, 2, 1)[..., None],
        f32(A), f32(Bm).transpose(0, 2, 1, 3), f32(Cm).transpose(0, 2, 1, 3),
        f32(D), chunk=Q, interpret=_interpret())
    return y.transpose(0, 2, 1, 3)[:, :L], s


@jax.jit
def ddpm_step(x, eps_hat, noise, alpha, alpha_bar, beta_tilde, l_rev):
    """Fused reverse-diffusion update; x/eps_hat/noise: (..., A)."""
    c1 = 1.0 / jnp.sqrt(alpha)
    c2 = (1.0 - alpha) / (jnp.sqrt(1.0 - alpha_bar) * jnp.sqrt(alpha))
    sigma = jnp.where(l_rev > 0, jnp.sqrt(beta_tilde), 0.0)
    coef = jnp.stack([c1, c2, sigma, jnp.float32(0.0)]).astype(
        jnp.float32)[None, :]
    shape = x.shape
    A = shape[-1]
    R = max(1, x.size // A)
    Ap = -(-A // 128) * 128
    def pad2(a):
        a2 = a.reshape(R, A).astype(jnp.float32)
        return jnp.pad(a2, ((0, 0), (0, Ap - A)))
    o = ddpm_step_2d(pad2(x), pad2(eps_hat), pad2(noise), coef,
                     interpret=_interpret())
    return o[:, :A].reshape(shape).astype(x.dtype)
