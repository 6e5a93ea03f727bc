"""Multi-head Latent Attention (DeepSeek-V2/V3): compressed-KV attention.

Prefill uses the expanded (standard) formulation; decode uses the *absorbed*
formulation (W_UK folded into the query, W_UV folded into the output) so the
per-token KV-cache is just ``c_kv`` (kv_lora_rank) + ``k_rope`` — the paper's
edge-memory constraint is directly served by this: cache bytes drop from
2·H·Dh to (kv_lora + d_rope) per token (~9x for V3).  Decode attention runs
in the Pallas kernel ``kernels/mla_decode.py``, which reads each slot's live
cache rows once, in place in the stacked cache; the XLA absorbed form is
only its test oracle (``kernels/ref.py``).  Under a mesh the kernel runs on
each device's shard of the cache, whose sequence ``mla_cache_spec`` splits
over "model", and the shards' parts are combined by their log-sum-exps.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import ops as kops

from .core import linear, linear_init, rmsnorm, rmsnorm_init
from .rotary import YaRN, apply_rope, rope_cos_sin
from .attention import NEG_INF, causal_window_mask, decode_positions
from .sharding import batch_spec, constrain, current_mesh, fit_spec


@dataclasses.dataclass(frozen=True)
class MLACfg:
    d_model: int
    n_heads: int
    q_lora_rank: int = 0          # 0 -> direct q projection
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 10000.0
    causal: bool = True
    window: Optional[int] = None
    yarn: Optional[YaRN] = None   # YaRN rope scaling (DeepSeek-V2/V3)

    @property
    def softmax_scale(self) -> float:
        scale = 1.0 / math.sqrt(self.qk_nope_dim + self.qk_rope_dim)
        return scale if self.yarn is None else scale * self.yarn.softmax_factor


def mla_init(key, cfg: MLACfg, *, dtype=jnp.float32):
    ks = jax.random.split(key, 6)
    H = cfg.n_heads
    qd = cfg.qk_nope_dim + cfg.qk_rope_dim
    p = {}
    if cfg.q_lora_rank:
        p["q_down"] = linear_init(ks[0], cfg.d_model, cfg.q_lora_rank, dtype=dtype)
        p["q_norm"] = rmsnorm_init(cfg.q_lora_rank, dtype)
        p["q_up"] = linear_init(ks[1], cfg.q_lora_rank, H * qd, dtype=dtype)
    else:
        p["q_proj"] = linear_init(ks[1], cfg.d_model, H * qd, dtype=dtype)
    p["kv_down"] = linear_init(ks[2], cfg.d_model,
                               cfg.kv_lora_rank + cfg.qk_rope_dim, dtype=dtype)
    p["kv_norm"] = rmsnorm_init(cfg.kv_lora_rank, dtype)
    p["kv_up"] = linear_init(ks[3], cfg.kv_lora_rank,
                             H * (cfg.qk_nope_dim + cfg.v_head_dim), dtype=dtype)
    p["o"] = linear_init(ks[4], H * cfg.v_head_dim, cfg.d_model, dtype=dtype)
    return p


def mla_spec(cfg: MLACfg):
    s = {
        "kv_down": {"w": P(None, None)},
        "kv_norm": {"scale": P(None)},
        "kv_up": {"w": P(None, "model")},
        "o": {"w": P("model", None)},
    }
    if cfg.q_lora_rank:
        s["q_down"] = {"w": P(None, None)}
        s["q_norm"] = {"scale": P(None)}
        s["q_up"] = {"w": P(None, "model")}
    else:
        s["q_proj"] = {"w": P(None, "model")}
    return s


def _project_q(p, cfg: MLACfg, x, compute_dtype):
    H = cfg.n_heads
    qd = cfg.qk_nope_dim + cfg.qk_rope_dim
    if cfg.q_lora_rank:
        qc = rmsnorm(p["q_norm"], linear(p["q_down"], x, compute_dtype=compute_dtype))
        q = linear(p["q_up"], qc, compute_dtype=compute_dtype)
    else:
        q = linear(p["q_proj"], x, compute_dtype=compute_dtype)
    q = q.reshape(x.shape[:-1] + (H, qd))
    return q[..., : cfg.qk_nope_dim], q[..., cfg.qk_nope_dim:]


def _compress_kv(p, cfg: MLACfg, x, positions, compute_dtype):
    """Returns (c_kv normalized (B,S,C), k_rope roped (B,S,1,dr))."""
    ckr = linear(p["kv_down"], x, compute_dtype=compute_dtype)
    c_kv = rmsnorm(p["kv_norm"], ckr[..., : cfg.kv_lora_rank])
    k_rope = ckr[..., cfg.kv_lora_rank:][..., None, :]  # single shared rope head
    cos, sin = rope_cos_sin(positions, cfg.qk_rope_dim, cfg.rope_theta, cfg.yarn)
    k_rope = apply_rope(k_rope, cos, sin)
    return c_kv, k_rope


def mla_forward(p, cfg: MLACfg, x, *, positions=None,
                compute_dtype=jnp.bfloat16, return_kv: bool = False):
    """Full-sequence MLA (train / prefill), expanded formulation."""
    B, L, _ = x.shape
    H = cfg.n_heads
    if positions is None:
        positions = jnp.arange(L)
    q_nope, q_rope = _project_q(p, cfg, x, compute_dtype)
    cos, sin = rope_cos_sin(positions, cfg.qk_rope_dim, cfg.rope_theta, cfg.yarn)
    q_rope = apply_rope(q_rope, cos, sin)
    c_kv, k_rope = _compress_kv(p, cfg, x, positions, compute_dtype)
    kv = linear(p["kv_up"], c_kv, compute_dtype=compute_dtype)
    kv = kv.reshape(B, L, H, cfg.qk_nope_dim + cfg.v_head_dim)
    k_nope, v = kv[..., : cfg.qk_nope_dim], kv[..., cfg.qk_nope_dim:]
    k_nope = constrain(k_nope, batch_spec(None, "model", None))
    v = constrain(v, batch_spec(None, "model", None))

    scale = cfg.softmax_scale
    scores = (jnp.einsum("blhd,bshd->bhls", q_nope, k_nope,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("blhd,bsxd->bhls", q_rope,
                           jnp.broadcast_to(k_rope, (B, L, 1, cfg.qk_rope_dim)),
                           preferred_element_type=jnp.float32)) * scale
    mask = causal_window_mask(L, L, causal=cfg.causal, window=cfg.window)
    scores = jnp.where(mask[None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhls,bshd->blhd", probs, v.astype(jnp.float32))
    out = out.astype(compute_dtype).reshape(B, L, H * cfg.v_head_dim)
    y = linear(p["o"], out, compute_dtype=compute_dtype)
    if return_kv:
        return y, (c_kv, k_rope[:, :, 0, :])
    return y


# A cache row is padded to a whole number of 128-lane tiles: the TPU lays
# the row out in such tiles anyway, and writing one token's row into a
# cache whose rows are not tile-aligned makes XLA copy the whole cache.
ROW_TILE = 128


def cache_row_width(cfg: MLACfg) -> int:
    w = cfg.kv_lora_rank + cfg.qk_rope_dim
    return -(-w // ROW_TILE) * ROW_TILE


def init_mla_cache(B: int, S: int, cfg: MLACfg, dtype=jnp.bfloat16):
    """One row a position: the normalised latent c_kv (kv_lora_rank), the
    rotary key (qk_rope_dim), then zeros to ``cache_row_width``, so that a
    token's entry is one contiguous write."""
    return {"kv": jnp.zeros((B, S, cache_row_width(cfg)), dtype)}


def mla_cache_entry(cfg: MLACfg, c_kv, k_rope):
    """The cache rows of latents ``c_kv`` (..., C) and rotary keys
    ``k_rope`` (..., dr)."""
    pad = cache_row_width(cfg) - cfg.kv_lora_rank - cfg.qk_rope_dim
    zeros = jnp.zeros(c_kv.shape[:-1] + (pad,), c_kv.dtype)
    return jnp.concatenate([c_kv, k_rope.astype(c_kv.dtype), zeros], axis=-1)


def mla_cache_spec(cfg: MLACfg):
    # no head dim -> shard sequence over "model" so huge contexts fit.
    return {"kv": batch_spec("model", None)}


def _attend(cfg: MLACfg, q, own, kv, pos, layer):
    """Decode attention of the absorbed query rows q (B, H, W) over the
    stacked cache kv (R, B, S, W) at ``layer`` and the own rows (B, W).
    Under a mesh, one kernel per device on its shard of the cache, laid out
    by ``mla_cache_spec``; where that splits the sequence, each device's
    part (context and log-sum-exp over its rows) and the own row are
    combined across the split: a Pallas call has no partitioning rule, so
    left to GSPMD it would gather the whole cache onto every device."""
    kw = dict(scale=cfg.softmax_scale, width=cfg.kv_lora_rank,
              window=cfg.window)
    mesh = current_mesh()
    if mesh is None:
        return kops.mla_decode(q, own, kv, pos, layer, **kw)
    B = q.shape[0]
    spec = fit_spec(P(None, *mla_cache_spec(cfg)["kv"]), kv.shape, mesh)
    b_ax, s_ax = spec[1], spec[2]

    def local(q, own, kv, pos, layer):
        if s_ax is None:
            return kops.mla_decode(q, own, kv, pos, layer, **kw)
        first = jax.lax.axis_index(s_ax) * kv.shape[2]
        o, lse = kops.mla_decode(q, None, kv, pos - first, layer, **kw)
        own_lse = jnp.einsum("bhc,bc->bh", q, own,
                             preferred_element_type=jnp.float32
                             )[..., None] * kw["scale"]
        top = jnp.maximum(jax.lax.pmax(lse, s_ax), own_lse)
        w, w_own = jnp.exp(lse - top), jnp.exp(own_lse - top)
        num = (jax.lax.psum(w * o, s_ax)
               + w_own * own[:, None, :kw["width"]].astype(jnp.float32))
        return (num / (jax.lax.psum(w, s_ax) + w_own)).astype(q.dtype)

    pos = jnp.broadcast_to(jnp.reshape(pos, (-1,)), (B,)).astype(jnp.int32)
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(b_ax, None, None), P(b_ax, None), spec, P(b_ax), P()),
        out_specs=P(b_ax, None, None), check_vma=False,
    )(q, own, kv, pos, jnp.asarray(layer, jnp.int32))


def mla_decode(p, cfg: MLACfg, x, cache, pos, *, layer,
               compute_dtype=jnp.bfloat16):
    """One-token absorbed-MLA decode.  x: (B,1,D); cache kv: stacked over
    layers (R,B,S,C+dr), read at ``layer``; pos: the new token's position,
    a scalar or one per row (B,).  Returns (y, entry): the cache is only
    read, and ``entry`` {"kv": (B,1,C+dr)} is the new token's row, for the
    caller to write at ``pos``.  Attention runs in the decode kernel
    (``kernels/mla_decode.py``), which reads each slot's rows before ``pos``
    once, in place."""
    B = x.shape[0]
    H = cfg.n_heads
    C = cfg.kv_lora_rank
    q_nope, q_rope = _project_q(p, cfg, x, compute_dtype)  # (B,1,H,*)
    posb = decode_positions(pos)
    cos, sin = rope_cos_sin(posb, cfg.qk_rope_dim, cfg.rope_theta, cfg.yarn)
    q_rope = apply_rope(q_rope, cos, sin)
    c_new, kr_new = _compress_kv(p, cfg, x, posb, compute_dtype)

    kv = cache["kv"]
    entry = mla_cache_entry(cfg, c_new, kr_new[:, :, 0, :]).astype(kv.dtype)

    W = p["kv_up"]["w"].astype(compute_dtype)  # (C, H*(nope+v))
    W = W.reshape(C, H, cfg.qk_nope_dim + cfg.v_head_dim)
    W_uk, W_uv = W[..., : cfg.qk_nope_dim], W[..., cfg.qk_nope_dim:]
    # absorb: q_lat (B,1,H,C); beside the rotary query it meets a cache row
    # [c_kv; k_rope] whole: q_lat . c_kv + q_rope . k_rope
    q_lat = jnp.einsum("blhd,chd->blhc", q_nope, W_uk)
    q = mla_cache_entry(cfg, q_lat, q_rope)
    ctx = _attend(cfg, q[:, 0], entry[:, 0], kv, pos, layer)     # (B,H,C)
    out = jnp.einsum("bhc,chv->bhv", ctx.astype(compute_dtype), W_uv)
    y = linear(p["o"], out.reshape(B, 1, H * cfg.v_head_dim),
               compute_dtype=compute_dtype)
    return y, {"kv": entry}
