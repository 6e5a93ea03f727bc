"""Composable transformer/SSM blocks.

A block = optional sequence *mixer* (GQA attention / MLA / Mamba2-SSD) +
optional cross-attention + optional FFN (dense SwiGLU/GELU or MoE), each
pre-normed with a residual.  Blocks are assembled into *groups* (scanned
cycles) by ``repro.models.lm``.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.nn import core
from repro.nn.attention import (AttnCfg, attn_decode, attn_forward, attn_init,
                                attn_spec, init_kv_cache, kv_cache_spec)
from repro.nn.mla import (MLACfg, init_mla_cache, mla_cache_entry,
                          mla_cache_spec, mla_decode, mla_forward, mla_init,
                          mla_spec)
from repro.nn.mlp import MLPCfg, mlp_apply, mlp_init, mlp_spec
from repro.nn.moe import (MoECfg, moe_apply, moe_decode, moe_init,
                          moe_prefill, moe_spec)
from repro.nn.ssm import (SSMCfg, init_ssm_state, ssm_decode, ssm_forward,
                          ssm_init, ssm_spec, ssm_state_spec)
from repro.nn.sharding import batch_spec, constrain


@dataclasses.dataclass(frozen=True)
class BlockCfg:
    d_model: int
    mixer: str = "attn"            # "attn" | "mla" | "ssm" | "none"
    ffn: str = "mlp"               # "mlp" | "moe" | "none"
    norm: str = "rms"              # "rms" | "ln" | "ln_np" (OLMo non-parametric)
    attn: Optional[AttnCfg] = None
    mla: Optional[MLACfg] = None
    ssm: Optional[SSMCfg] = None
    mlp: Optional[MLPCfg] = None
    moe: Optional[MoECfg] = None
    cross: Optional[AttnCfg] = None  # cross-attention (enc-dec decoder)
    shared: bool = False             # reuse params across group repeats (Zamba2)


# -- norms -------------------------------------------------------------------

def _norm_init(kind: str, d: int, dtype):
    if kind == "rms":
        return core.rmsnorm_init(d, dtype)
    if kind == "ln":
        return core.layernorm_init(d, dtype=dtype)
    if kind == "ln_np":
        return core.layernorm_init(d, elementwise=False, dtype=dtype)
    raise ValueError(kind)


def _norm_spec(kind: str):
    if kind == "rms":
        return core.rmsnorm_spec()
    if kind == "ln":
        return core.layernorm_spec()
    if kind == "ln_np":
        return core.layernorm_spec(elementwise=False)
    raise ValueError(kind)


def _norm_apply(kind: str, p, x):
    if kind == "rms":
        return core.rmsnorm(p, x)
    return core.layernorm(p, x)


# -- block init / spec -------------------------------------------------------

def block_init(key, cfg: BlockCfg, *, dtype=jnp.float32):
    km, kc, kf = jax.random.split(key, 3)
    p = {}
    if cfg.mixer != "none":
        p["norm1"] = _norm_init(cfg.norm, cfg.d_model, dtype)
    if cfg.mixer == "attn":
        p["mixer"] = attn_init(km, cfg.attn, dtype=dtype)
    elif cfg.mixer == "mla":
        p["mixer"] = mla_init(km, cfg.mla, dtype=dtype)
    elif cfg.mixer == "ssm":
        p["mixer"] = ssm_init(km, cfg.ssm, dtype=dtype)
    if cfg.cross is not None:
        p["norm_cross"] = _norm_init(cfg.norm, cfg.d_model, dtype)
        p["cross"] = attn_init(kc, cfg.cross, dtype=dtype)
    if cfg.ffn != "none":
        p["norm2"] = _norm_init(cfg.norm, cfg.d_model, dtype)
    if cfg.ffn == "mlp":
        p["ffn"] = mlp_init(kf, cfg.mlp, dtype=dtype)
    elif cfg.ffn == "moe":
        p["ffn"] = moe_init(kf, cfg.moe, dtype=dtype)
    return p


def block_spec(cfg: BlockCfg):
    s = {}
    if cfg.mixer != "none":
        s["norm1"] = _norm_spec(cfg.norm)
    if cfg.mixer == "attn":
        s["mixer"] = attn_spec(cfg.attn)
    elif cfg.mixer == "mla":
        s["mixer"] = mla_spec(cfg.mla)
    elif cfg.mixer == "ssm":
        s["mixer"] = ssm_spec(cfg.ssm)
    if cfg.cross is not None:
        s["norm_cross"] = _norm_spec(cfg.norm)
        s["cross"] = attn_spec(cfg.cross)
    if cfg.ffn != "none":
        s["norm2"] = _norm_spec(cfg.norm)
    if cfg.ffn == "mlp":
        s["ffn"] = mlp_spec(cfg.mlp)
    elif cfg.ffn == "moe":
        s["ffn"] = moe_spec(cfg.moe)
    return s


# -- forward (train / full sequence) ----------------------------------------

def block_forward(p, cfg: BlockCfg, x, *, positions=None, enc=None,
                  impl: str = "xla", compute_dtype=jnp.bfloat16):
    """x: (B,L,D) -> (x, aux_loss)."""
    aux = jnp.float32(0.0)
    if cfg.mixer == "attn":
        x = x + attn_forward(p["mixer"], cfg.attn,
                             _norm_apply(cfg.norm, p["norm1"], x),
                             positions=positions, impl=impl,
                             compute_dtype=compute_dtype)
    elif cfg.mixer == "mla":
        x = x + mla_forward(p["mixer"], cfg.mla,
                            _norm_apply(cfg.norm, p["norm1"], x),
                            positions=positions, compute_dtype=compute_dtype)
    elif cfg.mixer == "ssm":
        x = x + ssm_forward(p["mixer"], cfg.ssm,
                            _norm_apply(cfg.norm, p["norm1"], x),
                            impl=impl, compute_dtype=compute_dtype)
    if cfg.cross is not None:
        x = x + attn_forward(p["cross"], cfg.cross,
                             _norm_apply(cfg.norm, p["norm_cross"], x),
                             kv_src=enc, compute_dtype=compute_dtype)
    if cfg.ffn == "mlp":
        x = x + mlp_apply(p["ffn"], cfg.mlp,
                          _norm_apply(cfg.norm, p["norm2"], x),
                          compute_dtype=compute_dtype)
    elif cfg.ffn == "moe":
        y, a = moe_apply(p["ffn"], cfg.moe,
                         _norm_apply(cfg.norm, p["norm2"], x),
                         compute_dtype=compute_dtype)
        x = x + y
        aux = aux + a
    x = constrain(x, batch_spec(None, None))
    return x, aux


# -- cache -------------------------------------------------------------------

def block_init_cache(cfg: BlockCfg, B: int, S: int, *, enc_len: int = 0,
                     dtype=jnp.bfloat16):
    c = {}
    if cfg.mixer == "attn":
        c["mixer"] = init_kv_cache(B, S, cfg.attn, dtype)
    elif cfg.mixer == "mla":
        c["mixer"] = init_mla_cache(B, S, cfg.mla, dtype)
    elif cfg.mixer == "ssm":
        c["mixer"] = init_ssm_state(B, cfg.ssm, dtype)
    if cfg.cross is not None:
        c["cross"] = init_kv_cache(B, enc_len, cfg.cross, dtype)
    return c


def block_cache_spec(cfg: BlockCfg, *, seq_shard: Optional[str] = None):
    """seq_shard: mesh axis to shard the cache *sequence* dim over (used when
    kv-heads cannot fill the model axis, e.g. long-context decode)."""
    c = {}
    if cfg.mixer == "attn":
        if seq_shard is not None:
            c["mixer"] = {"k": batch_spec(seq_shard, None, None),
                          "v": batch_spec(seq_shard, None, None)}
        else:
            c["mixer"] = kv_cache_spec(cfg.attn)
    elif cfg.mixer == "mla":
        c["mixer"] = mla_cache_spec(cfg.mla)
    elif cfg.mixer == "ssm":
        c["mixer"] = ssm_state_spec(cfg.ssm)
    if cfg.cross is not None:
        c["cross"] = kv_cache_spec(cfg.cross)
    return c


def block_prefill(p, cfg: BlockCfg, x, cache, *, positions=None, enc=None,
                  impl: str = "xla", compute_dtype=jnp.bfloat16):
    """Full-sequence forward that also fills the cache at positions [0, L).
    Returns (x, cache, route): ``route`` is the MoE's ``{"ids",
    "dropped"}`` (``moe_prefill``), empty for a block without one."""
    route = {}
    new = dict(cache)
    if cfg.mixer == "attn":
        y, (k, v) = attn_forward(p["mixer"], cfg.attn,
                                 _norm_apply(cfg.norm, p["norm1"], x),
                                 positions=positions, impl=impl,
                                 compute_dtype=compute_dtype, return_kv=True)
        x = x + y
        new["mixer"] = {
            "k": jax.lax.dynamic_update_slice_in_dim(
                cache["mixer"]["k"], k.astype(cache["mixer"]["k"].dtype), 0, axis=1),
            "v": jax.lax.dynamic_update_slice_in_dim(
                cache["mixer"]["v"], v.astype(cache["mixer"]["v"].dtype), 0, axis=1),
        }
    elif cfg.mixer == "mla":
        y, (c_kv, k_rope) = mla_forward(p["mixer"], cfg.mla,
                                        _norm_apply(cfg.norm, p["norm1"], x),
                                        positions=positions,
                                        compute_dtype=compute_dtype,
                                        return_kv=True)
        x = x + y
        kv = cache["mixer"]["kv"]
        new["mixer"] = {"kv": jax.lax.dynamic_update_slice_in_dim(
            kv, mla_cache_entry(cfg.mla, c_kv, k_rope).astype(kv.dtype), 0, axis=1)}
    elif cfg.mixer == "ssm":
        y, st = ssm_forward(p["mixer"], cfg.ssm,
                            _norm_apply(cfg.norm, p["norm1"], x),
                            impl=impl, compute_dtype=compute_dtype,
                            return_state=True)
        x = x + y
        new["mixer"] = {"conv": st["conv"].astype(cache["mixer"]["conv"].dtype),
                        "ssm": st["ssm"]}
    if cfg.cross is not None:
        y, (k, v) = attn_forward(p["cross"], cfg.cross,
                                 _norm_apply(cfg.norm, p["norm_cross"], x),
                                 kv_src=enc, compute_dtype=compute_dtype,
                                 return_kv=True)
        x = x + y
        new["cross"] = {"k": k.astype(cache["cross"]["k"].dtype),
                        "v": v.astype(cache["cross"]["v"].dtype)}
    if cfg.ffn == "mlp":
        x = x + mlp_apply(p["ffn"], cfg.mlp,
                          _norm_apply(cfg.norm, p["norm2"], x),
                          compute_dtype=compute_dtype)
    elif cfg.ffn == "moe":
        y, route = moe_prefill(p["ffn"], cfg.moe,
                               _norm_apply(cfg.norm, p["norm2"], x),
                               compute_dtype=compute_dtype)
        x = x + y
    x = constrain(x, batch_spec(None, None))
    return x, new, route


def block_decode(p, cfg: BlockCfg, x, cache, pos, *,
                 compute_dtype=jnp.bfloat16):
    """One-token step.  x: (B,1,D); pos: int32, a scalar or one per row.
    Returns (x, new, route): ``new["mixer"]`` is the new token's cache row
    for an attention mixer (the cache is only read; the caller writes the
    row), the whole new state for a recurrent one; ``route`` as
    ``block_prefill``'s."""
    route = {}
    new = dict(cache)
    if cfg.mixer == "attn":
        y, new["mixer"] = attn_decode(p["mixer"], cfg.attn,
                                      _norm_apply(cfg.norm, p["norm1"], x),
                                      cache["mixer"], pos,
                                      compute_dtype=compute_dtype)
        x = x + y
    elif cfg.mixer == "mla":
        y, new["mixer"] = mla_decode(p["mixer"], cfg.mla,
                                     _norm_apply(cfg.norm, p["norm1"], x),
                                     cache["mixer"], pos,
                                     compute_dtype=compute_dtype)
        x = x + y
    elif cfg.mixer == "ssm":
        y, new["mixer"] = ssm_decode(p["mixer"], cfg.ssm,
                                     _norm_apply(cfg.norm, p["norm1"], x),
                                     cache["mixer"],
                                     compute_dtype=compute_dtype)
        x = x + y
    if cfg.cross is not None:
        y, _ = attn_decode(p["cross"], cfg.cross,
                           _norm_apply(cfg.norm, p["norm_cross"], x),
                           cache["cross"], pos, compute_dtype=compute_dtype)
        x = x + y
    if cfg.ffn == "mlp":
        x = x + mlp_apply(p["ffn"], cfg.mlp,
                          _norm_apply(cfg.norm, p["norm2"], x),
                          compute_dtype=compute_dtype)
    elif cfg.ffn == "moe":
        y, route = moe_decode(p["ffn"], cfg.moe,
                              _norm_apply(cfg.norm, p["norm2"], x),
                              compute_dtype=compute_dtype)
        x = x + y
    return x, new, route
