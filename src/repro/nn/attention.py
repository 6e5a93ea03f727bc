"""Grouped-query attention with optional QKV-bias, qk-norm, sliding window,
cross-attention, and KV-cache decode.  Tensor-parallel over heads ("model").
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import core
from .core import linear, linear_init, rmsnorm, rmsnorm_init
from .rotary import apply_rope, rope_cos_sin
from .sharding import batch_spec, constrain

NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class AttnCfg:
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_head: int
    qkv_bias: bool = False
    qk_norm: bool = False
    rope: bool = True
    rope_theta: float = 10000.0
    causal: bool = True
    window: Optional[int] = None      # sliding-window size (tokens), None = full
    cross: bool = False               # cross-attention (kv from encoder states)
    d_kv_in: Optional[int] = None     # input dim for kv projections (cross)
    ring: bool = False                # decode KV cache = ring buffer of size
    # `window` instead of the full sequence (beyond-paper: 64x cache-byte
    # reduction for long_500k sliding-window decode; see §Perf)


def attn_init(key, cfg: AttnCfg, *, dtype=jnp.float32):
    kq, kk, kv, ko = jax.random.split(key, 4)
    d_kv_in = cfg.d_kv_in or cfg.d_model
    p = {
        "q": linear_init(kq, cfg.d_model, cfg.n_heads * cfg.d_head,
                         bias=cfg.qkv_bias, dtype=dtype),
        "k": linear_init(kk, d_kv_in, cfg.n_kv_heads * cfg.d_head,
                         bias=cfg.qkv_bias, dtype=dtype),
        "v": linear_init(kv, d_kv_in, cfg.n_kv_heads * cfg.d_head,
                         bias=cfg.qkv_bias, dtype=dtype),
        "o": linear_init(ko, cfg.n_heads * cfg.d_head, cfg.d_model,
                         bias=False, dtype=dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = rmsnorm_init(cfg.d_head, dtype)
        p["k_norm"] = rmsnorm_init(cfg.d_head, dtype)
    return p


def attn_spec(cfg: AttnCfg):
    def lin(bias, wspec):
        s = {"w": wspec}
        if bias:
            s["b"] = P(wspec[1])
        return s
    s = {
        "q": lin(cfg.qkv_bias, P(None, "model")),
        "k": lin(cfg.qkv_bias, P(None, "model")),
        "v": lin(cfg.qkv_bias, P(None, "model")),
        "o": lin(False, P("model", None)),
    }
    if cfg.qk_norm:
        s["q_norm"] = {"scale": P(None)}
        s["k_norm"] = {"scale": P(None)}
    return s


def _split_heads(x, n, d):
    return x.reshape(x.shape[:-1] + (n, d))


def _merge_heads(x):
    return x.reshape(x.shape[:-2] + (x.shape[-2] * x.shape[-1],))


def _gqa_scores(q, k, scale):
    """q:(B,L,H,D) k:(B,S,Hkv,D) -> (B,Hkv,G,L,S) f32."""
    B, L, H, D = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    qg = q.reshape(B, L, Hkv, G, D)
    return jnp.einsum("blkgd,bskd->bkgls", qg, k,
                      preferred_element_type=jnp.float32) * scale


def _gqa_out(probs, v):
    """probs:(B,Hkv,G,L,S) v:(B,S,Hkv,D) -> (B,L,H,D)."""
    B, Hkv, G, L, S = probs.shape
    out = jnp.einsum("bkgls,bskd->blkgd", probs, v.astype(jnp.float32))
    return out.reshape(B, L, Hkv * G, v.shape[-1])


def causal_window_mask(L: int, S: int, *, causal: bool, window: Optional[int],
                       q_offset: int = 0) -> jnp.ndarray:
    """(L,S) bool mask. q position i corresponds to absolute pos i+q_offset."""
    qpos = jnp.arange(L)[:, None] + q_offset
    kpos = jnp.arange(S)[None, :]
    m = jnp.ones((L, S), dtype=bool)
    if causal:
        m &= kpos <= qpos
    if window is not None:
        m &= kpos > qpos - window
    return m


def _block_mask(iq, ik, bq, bk, causal, window):
    qpos = iq * bq + jnp.arange(bq)[:, None]
    kpos = ik * bk + jnp.arange(bk)[None, :]
    mask = jnp.ones((bq, bk), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    return mask


def _chunked_fwd(q, k, v, causal, window, scale, bq, bk):
    """Returns (out (B,L,H,D), lse (B,Hkv,G,L) f32)."""
    B, L, H, D = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    nq, nk = L // bq, S // bk
    qc = q.reshape(B, nq, bq, Hkv, G, D)
    kc = k.reshape(B, nk, bk, Hkv, D)
    vc = v.reshape(B, nk, bk, Hkv, D)

    def q_block(_, inp):
        iq, qb = inp                                   # qb: (B,bq,Hkv,G,D)

        def k_block(carry, kinp):
            m_prev, l_prev, acc = carry
            ik, kb, vb = kinp
            s = jnp.einsum("bqkgd,bskd->bkgqs", qb, kb,
                           preferred_element_type=jnp.float32) * scale
            mask = _block_mask(iq, ik, bq, bk, causal, window)
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_cur = jnp.max(s, axis=-1)
            m_new = jnp.maximum(m_prev, m_cur)
            p_ = jnp.exp(s - m_new[..., None])
            p_ = jnp.where(m_new[..., None] > NEG_INF / 2, p_, 0.0)
            corr = jnp.where(m_prev > NEG_INF / 2,
                             jnp.exp(m_prev - m_new), 0.0)
            l_new = corr * l_prev + jnp.sum(p_, axis=-1)
            acc = corr[..., None] * acc + jnp.einsum(
                "bkgqs,bskd->bkgqd", p_, vb.astype(jnp.float32))
            return (m_new, l_new, acc), None

        m0 = jnp.full((B, Hkv, G, bq), NEG_INF)
        l0 = jnp.zeros((B, Hkv, G, bq))
        a0 = jnp.zeros((B, Hkv, G, bq, D))
        (m, l, acc), _ = jax.lax.scan(
            k_block, (m0, l0, a0),
            (jnp.arange(nk), jnp.moveaxis(kc, 1, 0),
             jnp.moveaxis(vc, 1, 0)))
        out = acc / jnp.maximum(l, 1e-30)[..., None]   # (B,Hkv,G,bq,D)
        lse = m + jnp.log(jnp.maximum(l, 1e-30))       # (B,Hkv,G,bq)
        return None, (out, lse)

    _, (blocks, lses) = jax.lax.scan(
        q_block, None, (jnp.arange(nq), jnp.moveaxis(qc, 1, 0)))
    out = jnp.moveaxis(blocks, 0, 1)                   # (B,nq,Hkv,G,bq,D)
    out = jnp.moveaxis(out, -2, 2)                     # (B,nq,bq,Hkv,G,D)
    lse = jnp.moveaxis(lses, 0, 3).reshape(B, Hkv, G, L)
    return out.reshape(B, L, H, D).astype(q.dtype), lse


def _chunked_bwd_impl(q, k, v, out, lse, do, causal, window, scale, bq, bk):
    """Flash-style recompute backward: O(bq·bk) working set, accumulating
    dk/dv in an (nk, ...) carry; probs are recomputed from q, k and lse."""
    B, L, H, D = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    nq, nk = L // bq, S // bk
    qc = jnp.moveaxis(q.reshape(B, nq, bq, Hkv, G, D), 1, 0)
    oc = jnp.moveaxis(out.reshape(B, nq, bq, Hkv, G, D), 1, 0)
    doc = jnp.moveaxis(do.reshape(B, nq, bq, Hkv, G, D), 1, 0)
    lsec = jnp.moveaxis(lse.reshape(B, Hkv, G, nq, bq), 3, 0)
    kc = k.reshape(B, nk, bk, Hkv, D).astype(jnp.float32)
    vc = v.reshape(B, nk, bk, Hkv, D).astype(jnp.float32)

    def q_block(carry, inp):
        dk_acc, dv_acc = carry
        iq, qb, ob, dob, lseb = inp
        qbf = qb.astype(jnp.float32)
        dobf = dob.astype(jnp.float32)
        # delta = rowsum(do * out): (B,bq,Hkv,G)
        delta = jnp.sum(dobf * ob.astype(jnp.float32), axis=-1)
        delta = jnp.moveaxis(delta, 1, -1)             # (B,Hkv,G,bq)

        def k_block(inner, ik):
            dq_b, dk_acc, dv_acc = inner
            kb, vb = kc[:, ik], vc[:, ik]
            s = jnp.einsum("bqkgd,bskd->bkgqs", qbf, kb,
                           preferred_element_type=jnp.float32) * scale
            mask = _block_mask(iq, ik, bq, bk, causal, window)
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            p_ = jnp.exp(s - lseb[..., None])          # (B,Hkv,G,bq,bk)
            p_ = jnp.where(mask[None, None, None], p_, 0.0)
            dob_r = jnp.moveaxis(dobf, 1, 3)           # (B,Hkv,G,bq,D)
            dv_blk = jnp.einsum("bkgqs,bkgqd->bskd", p_, dob_r)
            dp = jnp.einsum("bkgqd,bskd->bkgqs", dob_r, vb)
            ds = p_ * (dp - delta[..., None]) * scale
            dq_b = dq_b + jnp.einsum("bkgqs,bskd->bkgqd", ds, kb)
            dk_blk = jnp.einsum("bkgqs,bkgqd->bskd", ds,
                                jnp.moveaxis(qbf, 1, 3))
            return (dq_b, dk_acc.at[:, ik].add(dk_blk),
                    dv_acc.at[:, ik].add(dv_blk)), None

        dq0 = jnp.zeros((B, Hkv, G, bq, D), jnp.float32)
        (dq_b, dk_acc, dv_acc), _ = jax.lax.scan(
            k_block, (dq0, dk_acc, dv_acc), jnp.arange(nk))
        return (dk_acc, dv_acc), dq_b

    dk0 = jnp.zeros((B, nk, bk, Hkv, D), jnp.float32)
    dv0 = jnp.zeros((B, nk, bk, Hkv, D), jnp.float32)
    (dk, dv), dqs = jax.lax.scan(
        q_block, (dk0, dv0), (jnp.arange(nq), qc, oc, doc, lsec))
    dq = jnp.moveaxis(dqs, 0, 1)                       # (B,nq,Hkv,G,bq,D)
    dq = jnp.moveaxis(dq, -2, 2).reshape(B, L, H, D)
    return (dq.astype(q.dtype), dk.reshape(B, S, Hkv, D).astype(k.dtype),
            dv.reshape(B, S, Hkv, D).astype(v.dtype))


import functools as _functools


@_functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _chunked_attention_vjp(q, k, v, causal, window, scale, bq, bk):
    return _chunked_fwd(q, k, v, causal, window, scale, bq, bk)[0]


def _cvjp_fwd(q, k, v, causal, window, scale, bq, bk):
    out, lse = _chunked_fwd(q, k, v, causal, window, scale, bq, bk)
    return out, (q, k, v, out, lse)


def _cvjp_bwd(causal, window, scale, bq, bk, res, do):
    q, k, v, out, lse = res
    return _chunked_bwd_impl(q, k, v, out, lse, do, causal, window, scale,
                             bq, bk)


_chunked_attention_vjp.defvjp(_cvjp_fwd, _cvjp_bwd)


def chunked_attention(q, k, v, *, causal: bool, window: Optional[int],
                      scale: float, bq: int = 1024, bk: int = 1024):
    """Flash-style double-chunked attention in pure XLA: lax.scan over
    q-blocks (outer) and k-blocks (inner) with an online-softmax carry and a
    RECOMPUTING custom VJP (naive AD through the online-softmax scan stores
    per-step carries and regresses training memory — measured in
    EXPERIMENTS.md §Perf B2).  Working set is O(bq·bk) instead of O(L·S) in
    both directions — the beyond-paper memory optimisation for 32k-token
    prefill/train, and the jnp twin of the Pallas flash kernel.

    q: (B, L, H, D); k/v: (B, S, Hkv, D).  L % bq == 0, S % bk == 0
    (callers pad; see kernels/ops.py for the padding contract)."""
    bq = min(bq, q.shape[1])
    bk = min(bk, k.shape[1])
    return _chunked_attention_vjp(q, k, v, causal, window, scale, bq, bk)


def attn_forward(p, cfg: AttnCfg, x, *, kv_src=None, positions=None,
                 impl: str = "xla", compute_dtype=jnp.bfloat16,
                 return_kv: bool = False):
    """Full-sequence attention (train / prefill).

    x: (B, L, D).  kv_src: (B, S, Dkv) for cross-attention (defaults to x).
    positions: (L,) absolute positions for RoPE (defaults arange).
    """
    B, L, _ = x.shape
    kv_in = x if kv_src is None else kv_src
    S = kv_in.shape[1]
    q = _split_heads(linear(p["q"], x, compute_dtype=compute_dtype),
                     cfg.n_heads, cfg.d_head)
    k = _split_heads(linear(p["k"], kv_in, compute_dtype=compute_dtype),
                     cfg.n_kv_heads, cfg.d_head)
    v = _split_heads(linear(p["v"], kv_in, compute_dtype=compute_dtype),
                     cfg.n_kv_heads, cfg.d_head)
    q = constrain(q, batch_spec(None, "model", None))
    k = constrain(k, batch_spec(None, "model", None))
    v = constrain(v, batch_spec(None, "model", None))
    if cfg.qk_norm:
        q = rmsnorm(p["q_norm"], q)
        k = rmsnorm(p["k_norm"], k)
    if cfg.rope and not cfg.cross:
        if positions is None:
            positions = jnp.arange(L)
        cos, sin = rope_cos_sin(positions, cfg.d_head, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)

    scale = 1.0 / math.sqrt(cfg.d_head)
    if impl == "flash" and not cfg.cross and cfg.causal:
        from repro.kernels import ops as kops
        out = kops.flash_attention(q, k, v, causal=True, window=cfg.window)
    elif impl == "chunked" and not cfg.cross:
        out = chunked_attention(q, k, v, causal=cfg.causal,
                                window=cfg.window, scale=scale)
    else:
        scores = _gqa_scores(q, k, scale)
        if cfg.cross:
            mask = None
        else:
            mask = causal_window_mask(L, S, causal=cfg.causal, window=cfg.window)
        if mask is not None:
            scores = jnp.where(mask[None, None, None], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        out = _gqa_out(probs, v).astype(compute_dtype)
    out = constrain(out, batch_spec(None, "model", None))
    y = linear(p["o"], _merge_heads(out), compute_dtype=compute_dtype)
    if return_kv:
        return y, (k, v)
    return y


def init_kv_cache(B: int, S: int, cfg: AttnCfg, dtype=jnp.bfloat16):
    if cfg.ring and cfg.window is not None:
        S = min(S, cfg.window)
    shape = (B, S, cfg.n_kv_heads, cfg.d_head)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def kv_cache_spec(cfg: AttnCfg):
    # batch over data axes, kv heads over model.
    return {"k": batch_spec(None, "model", None), "v": batch_spec(None, "model", None)}


def decode_positions(pos):
    """A decode step's positions as (B or 1, 1): ``pos`` is a scalar shared
    by the batch or one position per row (B,)."""
    return jnp.reshape(pos, (-1, 1))


def attn_decode(p, cfg: AttnCfg, x, cache, pos, *,
                compute_dtype=jnp.bfloat16):
    """One-token decode.  x: (B, 1, D); cache: {"k","v"}: (B, S, Hkv, Dh);
    pos: the absolute position of the new token, a scalar or one per row
    (B,).  Returns (y, entry): the cache is only read, and ``entry``
    {"k","v"} (B, 1, Hkv, Dh) is the new token's row, for the caller to
    write at ``pos`` (``pos % S`` for a ring buffer).  For cross-attention
    the cache holds the (static) encoder k/v and is returned unchanged."""
    B = x.shape[0]
    q = _split_heads(linear(p["q"], x, compute_dtype=compute_dtype),
                     cfg.n_heads, cfg.d_head)
    if cfg.qk_norm:
        q = rmsnorm(p["q_norm"], q)
    scale = 1.0 / math.sqrt(cfg.d_head)

    if cfg.cross:
        k, v = cache["k"], cache["v"]
        scores = _gqa_scores(q, k, scale)
        probs = jax.nn.softmax(scores, axis=-1)
        out = _gqa_out(probs, v).astype(compute_dtype)
        y = linear(p["o"], _merge_heads(out), compute_dtype=compute_dtype)
        return y, cache

    k_new = _split_heads(linear(p["k"], x, compute_dtype=compute_dtype),
                         cfg.n_kv_heads, cfg.d_head)
    v_new = _split_heads(linear(p["v"], x, compute_dtype=compute_dtype),
                         cfg.n_kv_heads, cfg.d_head)
    if cfg.qk_norm:
        k_new = rmsnorm(p["k_norm"], k_new)
    posb = decode_positions(pos)
    if cfg.rope:
        cos, sin = rope_cos_sin(posb, cfg.d_head, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k_new = apply_rope(k_new, cos, sin)
    k, v = cache["k"], cache["v"]
    k_new, v_new = k_new.astype(k.dtype), v_new.astype(v.dtype)

    scores = _gqa_scores(q, k, scale)  # (B,Hkv,G,1,S)
    S = k.shape[1]
    kpos = jnp.arange(S)[None, :]
    if cfg.ring and cfg.window is not None:
        # slot s holds global position pos - ((pos - s) mod S); every live
        # slot is within the window by construction — mask slots not yet
        # written (global position < 0 during warm-up) and the slot this
        # token is about to take.
        gpos = posb - jnp.mod(posb - kpos, S)
        valid = (gpos >= 0) & (gpos < posb)
    else:
        valid = kpos < posb
        if cfg.window is not None:
            valid &= kpos > posb - cfg.window
    scores = jnp.where(valid[:, None, None, None, :], scores, NEG_INF)
    scores = jnp.concatenate([scores, _gqa_scores(q, k_new, scale)], axis=-1)
    probs = jax.nn.softmax(scores, axis=-1)
    out = (_gqa_out(probs[..., :S], v)
           + _gqa_out(probs[..., S:], v_new)).astype(compute_dtype)
    y = linear(p["o"], _merge_heads(out), compute_dtype=compute_dtype)
    return y, {"k": k_new, "v": v_new}
