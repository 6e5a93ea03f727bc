"""Plain float32 reference of DeepSeek-V2-Lite's forward pass for the CPU
tests, taking the program's parameter tree (any held expert share).

One sequence at a time, no cache, batching or kernels, products at
``HIGHEST`` precision, written from the published description
(arXiv:2405.04434; the model's config.json), not from the program:

- RMSNorm (eps 1e-6) before attention and before the feed-forward part;
- expanded latent attention: q = h Wq; [c; k_r] = h Wkv_a, c RMS-normed;
  [k_nope; v] = c Wkv_b; YaRN rotary on q_r and the one shared key k_r;
  causal softmax of (q_nope k_nope + q_r k_r) * mscale**2 / sqrt(192);
- YaRN frequencies: f_i = theta**(-2i/d); the pairs below the index where
  the rotations over the original context equal beta_fast keep f_i, those
  above the one where they equal beta_slow take f_i / factor, a linear ramp
  between; mscale = 0.1 * mscale_all_dim * ln(factor) + 1;
- dense layers: SwiGLU; MoE layers: softmax router over all experts, the
  top-k probabilities (renormalised only where ``norm_topk_prob``), each
  held expert's SwiGLU weighted by its probability, absent experts adding
  nothing, and the shared experts (one SwiGLU) for every token;
- final RMSNorm and the untied head.

Departure shared with the program: rotary pairs are the halves (i, i + d/2)
of the rotary dims, not the checkpoint's interleaved pairs (a fixed
permutation of the projection columns).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-6


def _f32(t):
    return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), t)


def rms(x):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + EPS)


def mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def yarn_freqs(d, theta, factor, original, beta_fast, beta_slow):
    base = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)

    def corr(rot):
        return d * math.log(original / (rot * 2 * math.pi)) / (2 * math.log(theta))

    lo = max(math.floor(corr(beta_fast)), 0)
    hi = min(math.ceil(corr(beta_slow)), d - 1)
    hi = hi + 0.001 if hi == lo else hi
    ramp = np.clip((np.arange(d // 2) - lo) / (hi - lo), 0.0, 1.0)
    return base / factor * ramp + base * (1 - ramp)


def rope(x, pos, mla):
    y = mla.yarn
    f = yarn_freqs(mla.qk_rope_dim, mla.rope_theta, y.factor,
                   y.original_max_positions, y.beta_fast, y.beta_slow)
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(f, jnp.float32)
    s = mscale(y.factor, y.mscale) / mscale(y.factor, y.mscale_all_dim)
    cos, sin = jnp.cos(ang) * s, jnp.sin(ang) * s
    if x.ndim == 3:
        cos, sin = cos[:, None], sin[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(p, h, mla):
    S, H = h.shape[0], mla.n_heads
    dn, dr, dv, C = mla.qk_nope_dim, mla.qk_rope_dim, mla.v_head_dim, mla.kv_lora_rank
    pos = jnp.arange(S)
    q = (h @ p["q_proj"]["w"]).reshape(S, H, dn + dr)
    q_nope, q_r = q[..., :dn], rope(q[..., dn:], pos, mla)
    kv_a = h @ p["kv_down"]["w"]
    lat = rms(kv_a[:, :C]) * p["kv_norm"]["scale"]
    k_r = rope(kv_a[:, C:], pos, mla)
    kv = (lat @ p["kv_up"]["w"]).reshape(S, H, dn + dv)
    scale = mscale(mla.yarn.factor, mla.yarn.mscale_all_dim) ** 2 / math.sqrt(dn + dr)
    scores = (jnp.einsum("qhd,khd->hqk", q_nope, kv[..., :dn])
              + jnp.einsum("qhd,kd->hqk", q_r, k_r)) * scale
    scores = jnp.where(pos[None, :, None] >= pos[None, None, :], scores, -jnp.inf)
    out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), kv[..., dn:])
    return out.reshape(S, H * dv) @ p["o"]["w"]


def swiglu(gate, up, down, h):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def moe(p, h, cfg):
    """(output, picked experts (S, K)) of one MoE layer over h (S, D)."""
    probs = jax.nn.softmax(h @ p["router"]["w"], axis=-1)
    w, ids = jax.lax.top_k(probs, cfg.top_k)
    if cfg.norm_topk_prob:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    first, count = cfg.held if cfg.held is not None else (0, cfg.n_experts)
    sh = p["shared"]
    y = swiglu(sh["gate"]["w"], sh["up"]["w"], sh["down"]["w"], h)
    for j in range(count):
        wt = jnp.sum(jnp.where(ids == first + j, w, 0.0), axis=-1)
        y = y + wt[:, None] * swiglu(p["gate"][j], p["up"][j], p["down"][j], h)
    return y, ids


def forward(params, lm, tokens):
    """Logits (S, V) of one sequence and the picked experts of each MoE
    layer (S, n_moe, K)."""
    params = _f32(params)
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["table"][jnp.asarray(tokens)]
        picks = []
        for gp, g in zip(params["groups"], lm.groups):
            blk = g.cycle[0]
            for r in range(g.repeats):
                p = jax.tree.map(lambda a: a[r], gp["stacked"]["0"])
                x = x + attention(p["mixer"], rms(x) * p["norm1"]["scale"], blk.mla)
                h = rms(x) * p["norm2"]["scale"]
                if blk.ffn == "moe":
                    y, ids = moe(p["ffn"], h, blk.moe)
                    picks.append(ids)
                else:
                    f = p["ffn"]
                    y = swiglu(f["gate"]["w"], f["up"]["w"], f["down"]["w"], h)
                x = x + y
        logits = (rms(x) * params["final_norm"]["scale"]) @ params["lm_head"]["w"]
    return np.asarray(logits), np.stack([np.asarray(i) for i in picks], axis=1)
