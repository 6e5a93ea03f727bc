"""Mixture-of-Experts with shared experts + top-k routed experts
(DeepSeek-V2/V3 style), sort-based capacity dispatch.

Why sort-based: the classic one-hot dispatch tensor (T, E, C) is infeasible at
E=256 / T~1M.  We instead sort the (token, expert) assignments by expert id,
rank tokens within an expert, drop overflow beyond the capacity, and scatter
into a dense (E, C, d) buffer that is expert-parallel over the "model" mesh
axis — GSPMD lowers the scatter/gather to all-to-all style collectives.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .core import linear_init, silu
from .mlp import MLPCfg, mlp_apply, mlp_init, mlp_spec
from .sharding import constrain


@dataclasses.dataclass(frozen=True)
class MoECfg:
    d_model: int
    d_ff: int                      # per routed expert
    n_experts: int                 # routed experts
    top_k: int
    n_shared: int = 0              # shared experts (each of size d_ff)
    capacity_factor: float = 1.25
    aux_coef: float = 0.001
    router_dtype: object = jnp.float32
    dispatch: str = "gspmd"        # "gspmd" (global scatter; simple, but
    # GSPMD lowers it to full-buffer all-reduces) | "shardmap" (local
    # dispatch per data shard + model-axis psum combine — the TPU-native
    # expert-parallel path, §Perf iteration A2)


def moe_init(key, cfg: MoECfg, *, dtype=jnp.float32):
    kr, ku, kg, kd, ks = jax.random.split(key, 5)
    E, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff
    scale = 1.0 / math.sqrt(d)
    p = {
        "router": {"w": (jax.random.normal(kr, (d, E)) * scale).astype(jnp.float32)},
        "up": (jax.random.normal(ku, (E, d, f)) * scale).astype(dtype),
        "gate": (jax.random.normal(kg, (E, d, f)) * scale).astype(dtype),
        "down": (jax.random.normal(kd, (E, f, d)) * (1.0 / math.sqrt(f))).astype(dtype),
    }
    if cfg.n_shared:
        p["shared"] = mlp_init(
            ks, MLPCfg(cfg.d_model, cfg.d_ff * cfg.n_shared), dtype=dtype)
    return p


def moe_spec(cfg: MoECfg):
    s = {
        "router": {"w": P(None, None)},
        "up": P("model", None, None),
        "gate": P("model", None, None),
        "down": P("model", None, None),
    }
    if cfg.n_shared:
        s["shared"] = mlp_spec(MLPCfg(cfg.d_model, cfg.d_ff * cfg.n_shared))
    return s


def _capacity(T: int, cfg: MoECfg) -> int:
    cap = int(math.ceil(T * cfg.top_k * cfg.capacity_factor / cfg.n_experts))
    return max(cap, cfg.top_k)


def _local_dispatch_combine(router_w, up, gate, down, xl, cfg: MoECfg,
                            compute_dtype, model_axis: str,
                            all_axes: tuple):
    """shard_map body: tokens are THIS data-shard's slice; up/gate/down are
    THIS model-shard's expert slice (E_loc, ...).  No cross-device traffic
    except the final psum over the model axis."""
    E = cfg.n_experts
    E_loc = up.shape[0]
    K = cfg.top_k
    B_loc, L, D = xl.shape
    T = B_loc * L
    cap = max(int(math.ceil(T * K * cfg.capacity_factor / E)), K)
    xt = xl.reshape(T, D)

    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                        router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    w, ids = jax.lax.top_k(probs, K)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-9)

    flat_ids = ids.reshape(T * K)
    flat_w = w.reshape(T * K)
    flat_tok = jnp.repeat(jnp.arange(T), K)
    order = jnp.argsort(flat_ids, stable=True)
    e_sorted = flat_ids[order]
    t_sorted = flat_tok[order]
    w_sorted = flat_w[order]
    starts = jnp.searchsorted(e_sorted, jnp.arange(E), side="left")
    rank = jnp.arange(T * K) - starts[e_sorted]
    keep = rank < cap
    slot = jnp.where(keep, e_sorted * cap + rank, E * cap)

    # dispatch LOCALLY into the full (E·cap) buffer, then slice my experts
    tok_vals = jnp.where(keep[:, None], xt[t_sorted].astype(compute_dtype), 0)
    buf = jnp.zeros((E * cap + 1, D), compute_dtype).at[slot].add(tok_vals)
    midx = jax.lax.axis_index(model_axis)
    mine = jax.lax.dynamic_slice_in_dim(buf[: E * cap].reshape(E, cap, D),
                                        midx * E_loc, E_loc, axis=0)

    up_h = jnp.einsum("ecd,edf->ecf", mine, up.astype(compute_dtype))
    gate_h = jnp.einsum("ecd,edf->ecf", mine, gate.astype(compute_dtype))
    out = jnp.einsum("ecf,efd->ecd", silu(gate_h) * up_h,
                     down.astype(compute_dtype))

    # combine MY experts' contributions, then sum over the model axis
    out_flat = jnp.concatenate(
        [out.reshape(E_loc * cap, D), jnp.zeros((1, D), compute_dtype)], 0)
    myslot = slot - midx * E_loc * cap
    valid = keep & (myslot >= 0) & (myslot < E_loc * cap)
    contrib = out_flat[jnp.where(valid, myslot, E_loc * cap)] \
        * jnp.where(valid, w_sorted, 0.0)[:, None].astype(compute_dtype)
    y = jnp.zeros((T, D), compute_dtype).at[t_sorted].add(contrib)
    y = jax.lax.psum(y, model_axis)

    frac = jnp.zeros(E, jnp.float32).at[flat_ids].add(1.0) / (T * K)
    mean_prob = jnp.mean(probs, axis=0)
    aux = cfg.aux_coef * E * jnp.sum(frac * mean_prob)
    aux = jax.lax.pmean(aux, all_axes)          # invariant across shards
    return y.reshape(B_loc, L, D), aux


def moe_apply_shardmap(p, cfg: MoECfg, x, *, compute_dtype=jnp.bfloat16):
    """Expert-parallel MoE via shard_map (requires an active mesh whose
    'model' size divides n_experts).  Collective cost per layer: one bf16
    psum of the (T_local, D) activations over the model axis — vs the
    GSPMD path's full (E·cap, D) buffer all-reduces."""
    import functools
    from jax.sharding import PartitionSpec as P
    from .sharding import batch_axes, current_mesh
    mesh = current_mesh()
    assert mesh is not None and "model" in mesh.axis_names
    ba = batch_axes()
    lead = ba if len(ba) != 1 else ba[0]
    all_axes = tuple(mesh.axis_names)
    body = functools.partial(
        _local_dispatch_combine, cfg=cfg, compute_dtype=compute_dtype,
        model_axis="model", all_axes=all_axes)
    y, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, None), P("model", None, None),
                  P("model", None, None), P("model", None, None),
                  P(lead if ba else None, None, None)),
        out_specs=(P(lead if ba else None, None, None), P()),
        check_vma=False,
    )(p["router"]["w"], p["up"], p["gate"], p["down"], x)
    if "shared" in p:
        y = y + mlp_apply(p["shared"],
                          MLPCfg(cfg.d_model, cfg.d_ff * cfg.n_shared), x,
                          compute_dtype=compute_dtype)
    return y, aux


def moe_apply(p, cfg: MoECfg, x, *, compute_dtype=jnp.bfloat16):
    """x: (B, L, D) -> (y, aux_loss)."""
    if cfg.dispatch == "shardmap":
        from .sharding import current_mesh
        if current_mesh() is not None:
            return moe_apply_shardmap(p, cfg, x,
                                      compute_dtype=compute_dtype)
        # no mesh (smoke tests / single host): fall through to gspmd
    B, L, D = x.shape
    T = B * L
    E, K = cfg.n_experts, cfg.top_k
    cap = _capacity(T, cfg)
    xt = x.reshape(T, D)

    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                        p["router"]["w"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    w, ids = jax.lax.top_k(probs, K)                     # (T,K)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-9)  # renormalize top-k

    # --- flatten assignments and sort by expert id --------------------------
    flat_ids = ids.reshape(T * K)
    flat_w = w.reshape(T * K)
    flat_tok = jnp.repeat(jnp.arange(T), K)
    order = jnp.argsort(flat_ids, stable=True)
    e_sorted = flat_ids[order]
    t_sorted = flat_tok[order]
    w_sorted = flat_w[order]

    # rank of each assignment within its expert
    starts = jnp.searchsorted(e_sorted, jnp.arange(E), side="left")
    rank = jnp.arange(T * K) - starts[e_sorted]
    keep = rank < cap
    slot = jnp.where(keep, e_sorted * cap + rank, E * cap)  # sentinel row

    # --- dispatch ------------------------------------------------------------
    tok_vals = jnp.where(keep[:, None], xt[t_sorted].astype(compute_dtype), 0)
    buf = jnp.zeros((E * cap + 1, D), compute_dtype).at[slot].add(tok_vals)
    h = buf[: E * cap].reshape(E, cap, D)
    h = constrain(h, P("model", None, None))

    # --- expert FFN (SwiGLU) --------------------------------------------------
    up = jnp.einsum("ecd,edf->ecf", h, p["up"].astype(compute_dtype))
    gate = jnp.einsum("ecd,edf->ecf", h, p["gate"].astype(compute_dtype))
    out = jnp.einsum("ecf,efd->ecd", silu(gate) * up,
                     p["down"].astype(compute_dtype))
    out = constrain(out, P("model", None, None))

    # --- combine --------------------------------------------------------------
    out_flat = jnp.concatenate(
        [out.reshape(E * cap, D), jnp.zeros((1, D), compute_dtype)], axis=0)
    contrib = out_flat[slot] * w_sorted[:, None].astype(compute_dtype)
    y = jnp.zeros((T, D), compute_dtype).at[t_sorted].add(contrib)
    y = y.reshape(B, L, D)

    # --- shared experts -------------------------------------------------------
    if "shared" in p:
        y = y + mlp_apply(p["shared"],
                          MLPCfg(cfg.d_model, cfg.d_ff * cfg.n_shared), x,
                          compute_dtype=compute_dtype)

    # --- load-balance aux loss (Switch-style) ---------------------------------
    frac = jnp.zeros(E, jnp.float32).at[flat_ids].add(1.0) / (T * K)
    mean_prob = jnp.mean(probs, axis=0)
    aux = cfg.aux_coef * E * jnp.sum(frac * mean_prob)
    return y, aux
