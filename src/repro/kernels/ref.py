"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp


def flash_attention_ref(q, k, v, *, causal: bool = True,
                        window: Optional[int] = None, scale=None):
    """q: (B, L, H, D); k/v: (B, S, Hkv, D) with H % Hkv == 0.
    Returns (B, L, H, D) in q.dtype; softmax in f32."""
    B, L, H, D = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    scale = scale or 1.0 / math.sqrt(D)
    qg = q.reshape(B, L, Hkv, G, D)
    s = jnp.einsum("blkgd,bskd->bkgls", qg, k,
                   preferred_element_type=jnp.float32) * scale
    qpos = jnp.arange(L)[:, None]
    kpos = jnp.arange(S)[None, :]
    m = jnp.ones((L, S), bool)
    if causal:
        m &= kpos <= qpos
    if window is not None:
        m &= kpos > qpos - window
    s = jnp.where(m[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgls,bskd->blkgd", p, v.astype(jnp.float32))
    return o.reshape(B, L, H, D).astype(q.dtype)


def mla_decode_ref(q, own, kv, pos, layer, *, scale: float, width: int,
                   window: Optional[int] = None):
    """Absorbed-MLA decode attention in XLA: scores of q (B, H, W) against
    every row of the stacked cache kv (R, B, S, W) at ``layer`` and the own
    rows (B, W); rows at or past ``pos`` (and, with a window, at or before
    ``pos - window``) masked; softmax; the context's first ``width``
    columns (B, H, width) in q.dtype."""
    kv = kv[layer]
    S = kv.shape[1]
    posb = jnp.reshape(pos, (-1, 1))
    scores = jnp.einsum("bhc,bsc->bhs", q, kv,
                        preferred_element_type=jnp.float32) * scale
    own_s = jnp.einsum("bhc,bc->bh", q, own,
                       preferred_element_type=jnp.float32) * scale
    kpos = jnp.arange(S)[None, :]
    valid = kpos < posb
    if window is not None:
        valid &= kpos > posb - window
    scores = jnp.where(valid[:, None, :], scores, -1e30)
    probs = jax.nn.softmax(jnp.concatenate([scores, own_s[..., None]], -1), -1)
    ctx = (jnp.einsum("bhs,bsc->bhc", probs[..., :S].astype(kv.dtype),
                      kv).astype(jnp.float32)
           + probs[..., S, None] * own.astype(jnp.float32)[:, None])
    return ctx[..., :width].astype(q.dtype)


def ssd_scan_ref(x, dt, A, Bm, Cm, D, *, chunk: int = 128):
    """Chunked-SSD oracle — delegates to the nn-layer reference (itself
    validated against a step-by-step recurrence in tests)."""
    from repro.nn.ssm import ssd_reference
    return ssd_reference(x, dt, A, Bm, Cm, D, chunk=chunk, return_state=True)


def ddpm_step_ref(x, eps_hat, noise, alpha, alpha_bar, beta_tilde, l_rev):
    """One fused reverse-diffusion update (Eqs. 19-20):
    mu = (x - (1-alpha)/sqrt(1-abar) * eps_hat)/sqrt(alpha);
    x' = mu + sqrt(beta_tilde)*noise  (noise suppressed at l_rev == 0)."""
    xf = x.astype(jnp.float32)
    mu = (xf - (1.0 - alpha) / jnp.sqrt(1.0 - alpha_bar)
          * eps_hat.astype(jnp.float32)) / jnp.sqrt(alpha)
    sigma = jnp.where(l_rev > 0, jnp.sqrt(beta_tilde), 0.0)
    return (mu + sigma * noise.astype(jnp.float32)).astype(x.dtype)
