"""Mixture-of-Experts with shared experts + top-k routed experts
(DeepSeek-V2/V3 style).

Training (``moe_apply``) uses sort-based capacity dispatch.  Why
sort-based: the classic one-hot dispatch tensor (T, E, C) is infeasible at
E=256 / T~1M.  We instead sort the (token, expert) assignments by expert id,
rank tokens within an expert, drop overflow beyond the capacity, and scatter
into a dense (E, C, d) buffer that is expert-parallel over the "model" mesh
axis — GSPMD lowers the scatter/gather to all-to-all style collectives.

Serving (``moe_prefill``, ``moe_decode``) drops nothing, and computes the
share of the layer that this chip's experts give: ``MoECfg.held`` names the
experts whose weights are here (expert parallelism without its exchange).
The router keeps its full width; picks of absent experts add nothing, and
the shared experts run for every token.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

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
    n_experts: int                 # routed experts (the router's width)
    top_k: int
    n_shared: int = 0              # shared experts (each of size d_ff)
    norm_topk_prob: bool = True    # renormalise the top-k weights to sum 1
    # (first, count): the routed experts whose weights this chip holds;
    # None holds all of them
    held: Optional[Tuple[int, int]] = None
    capacity_factor: float = 1.25
    aux_coef: float = 0.001
    router_dtype: object = jnp.float32
    dispatch: str = "gspmd"        # "gspmd" (global scatter; simple, but
    # GSPMD lowers it to full-buffer all-reduces) | "shardmap" (local
    # dispatch per data shard + model-axis psum combine — the TPU-native
    # expert-parallel path, §Perf iteration A2)


def held_range(cfg: MoECfg) -> Tuple[int, int]:
    """(first, count) of the routed experts held here."""
    return cfg.held if cfg.held is not None else (0, cfg.n_experts)


def moe_init(key, cfg: MoECfg, *, dtype=jnp.float32):
    kr, ku, kg, kd, ks = jax.random.split(key, 5)
    E, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff
    n = held_range(cfg)[1]
    scale = 1.0 / math.sqrt(d)
    p = {
        "router": {"w": (jax.random.normal(kr, (d, E)) * scale).astype(jnp.float32)},
        "up": (jax.random.normal(ku, (n, d, f)) * scale).astype(dtype),
        "gate": (jax.random.normal(kg, (n, d, f)) * scale).astype(dtype),
        "down": (jax.random.normal(kd, (n, f, d)) * (1.0 / math.sqrt(f))).astype(dtype),
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


def route(router_w, cfg: MoECfg, xt):
    """Softmax router over all ``n_experts`` in float32 (on a TPU too, where
    a float32 product otherwise rounds its operands to bfloat16), greedy
    top-k: returns the
    pick weights (T, K) (renormalised where ``norm_topk_prob``), the picked
    expert ids (T, K) and the probabilities (T, E)."""
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                        router_w.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    w, ids = jax.lax.top_k(probs, cfg.top_k)
    if cfg.norm_topk_prob:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-9)
    return w, ids, probs


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

    w, ids, probs = route(router_w, cfg, xt)

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
    """x: (B, L, D) -> (y, aux_loss).  Training dispatch, over every
    routed expert."""
    if cfg.held is not None:
        raise ValueError("capacity dispatch runs every routed expert; "
                         "a held share is served by moe_prefill/moe_decode")
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

    w, ids, probs = route(p["router"]["w"], cfg, xt)     # (T,K)

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


# ---------------------------------------------------------------------------
# serving: dropless, over the held experts
# ---------------------------------------------------------------------------

def _held_picks(cfg: MoECfg, ids, w):
    """Each pick's local expert index, ``count`` for an absent expert, and
    its weight, 0 for an absent expert."""
    first, count = held_range(cfg)
    local = ids - first
    mine = (local >= 0) & (local < count)
    return jnp.where(mine, local, count), jnp.where(mine, w, 0.0)


def _shared(p, cfg: MoECfg, x, compute_dtype):
    return mlp_apply(p["shared"], MLPCfg(cfg.d_model, cfg.d_ff * cfg.n_shared),
                     x, compute_dtype=compute_dtype)


def moe_prefill(p, cfg: MoECfg, x, *, compute_dtype=jnp.bfloat16):
    """x: (B, L, D) -> (y, {"ids": (B, L, K) picked experts, "dropped": held
    picks left uncomputed}).  The (token, pick) pairs are sorted by held
    expert and each held expert runs on exactly its rows as one ragged
    matmul (``jax.lax.ragged_dot``); no capacity, so nothing is dropped.
    Picks of absent experts sort last, past every group, and add nothing."""
    B, L, D = x.shape
    T, K = B * L, cfg.top_k
    count = held_range(cfg)[1]
    xt = x.reshape(T, D)
    w, ids, _ = route(p["router"]["w"], cfg, xt)
    local, w = _held_picks(cfg, ids, w)
    flat = local.reshape(T * K)
    order = jnp.argsort(flat, stable=True)
    rows = order // K
    sizes = jnp.bincount(flat, length=count + 1)[:count].astype(jnp.int32)
    xs = xt[rows].astype(compute_dtype)
    cd = lambda a: a.astype(compute_dtype)
    h = (silu(jax.lax.ragged_dot(xs, cd(p["gate"]), sizes))
         * jax.lax.ragged_dot(xs, cd(p["up"]), sizes))
    out = jax.lax.ragged_dot(h, cd(p["down"]), sizes)
    computed = jnp.arange(T * K) < jnp.sum(sizes)
    out = jnp.where(computed[:, None], out, 0)
    contrib = out * w.reshape(T * K)[order][:, None].astype(compute_dtype)
    y = jnp.zeros((T, D), compute_dtype).at[rows].add(contrib)
    y = y.reshape(B, L, D)
    if "shared" in p:
        y = y + _shared(p, cfg, x, compute_dtype)
    dropped = jnp.sum(flat < count) - jnp.sum(computed)
    return y, {"ids": ids.reshape(B, L, K), "dropped": dropped.astype(jnp.int32)}


def moe_decode(p, cfg: MoECfg, x, *, compute_dtype=jnp.bfloat16):
    """x: (B, 1, D), one token per sequence -> (y, {"ids", "dropped"}) as
    ``moe_prefill``.  A decode step reads every held expert's weights
    whatever its routing, so each held expert runs on every token as one
    dense product, and the picks weight the results (unpicked and absent
    experts weigh 0): dropless, and the layer's slice of the stacked
    weights feeds the products directly (``ragged_dot`` copies each
    layer's expert weights out of the stack first, on a v5e)."""
    B, L, D = x.shape
    T = B * L
    count = held_range(cfg)[1]
    xt = x.reshape(T, D)
    w, ids, _ = route(p["router"]["w"], cfg, xt)
    local, w = _held_picks(cfg, ids, w)
    gates = jnp.zeros((T, count + 1), jnp.float32).at[
        jnp.arange(T)[:, None], local].add(w)[:, :count]
    xc = xt.astype(compute_dtype)
    cd = lambda a: a.astype(compute_dtype)
    h = (silu(jnp.einsum("td,edf->tef", xc, cd(p["gate"])))
         * jnp.einsum("td,edf->tef", xc, cd(p["up"])))
    out = jnp.einsum("tef,efd->ted", h, cd(p["down"]))
    y = jnp.einsum("ted,te->td", out, gates.astype(compute_dtype)).reshape(B, L, D)
    if "shared" in p:
        y = y + _shared(p, cfg, x, compute_dtype)
    return y, {"ids": ids.reshape(B, L, cfg.top_k),
               "dropped": jnp.zeros((), jnp.int32)}


def held_load(cfg: MoECfg, ids, valid):
    """Picks per held expert: ``ids`` (..., K) picked experts, ``valid``
    (...) the tokens to count -> (count,) int32."""
    local, _ = _held_picks(cfg, ids, jnp.zeros(ids.shape, jnp.float32))
    count = held_range(cfg)[1]
    local = jnp.where(valid[..., None], local, count)
    return jnp.bincount(local.reshape(-1), length=count + 1)[:count].astype(jnp.int32)
