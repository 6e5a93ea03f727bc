"""CompositeLM: a decoder-only LM assembled from *groups* of scanned block
cycles.  Covers dense / MoE / SSM / hybrid / VLM architectures.

A group is ``repeats`` × ``cycle`` (a tuple of heterogeneous BlockCfg).  The
repeats are executed with ``lax.scan`` over stacked parameters, keeping the
HLO (and compile time) independent of depth; blocks marked ``shared=True``
store one copy of parameters reused by every repeat (Zamba2's shared
attention), while their caches remain per-repeat.

VLM support: ``prefix_embed_dim > 0`` adds a projector that maps precomputed
vision-patch embeddings (the stubbed ViT frontend, per the carve-out) into
``n_prefix`` leading sequence slots.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.nn import core
from repro.nn.sharding import batch_spec, constrain
from .blocks import (BlockCfg, block_cache_spec, block_decode, block_forward,
                     block_init, block_init_cache, block_prefill, block_spec)


@dataclasses.dataclass(frozen=True)
class GroupCfg:
    cycle: Tuple[BlockCfg, ...]
    repeats: int


@dataclasses.dataclass(frozen=True)
class LMCfg:
    name: str
    vocab: int
    d_model: int
    groups: Tuple[GroupCfg, ...]
    final_norm: str = "rms"
    tie_embeddings: bool = True
    pos_embed: str = "none"        # "none" (rope inside attn) | "learned"
    max_positions: int = 0          # for learned positions
    n_prefix: int = 0               # VLM: number of vision-patch slots
    prefix_embed_dim: int = 0       # VLM: raw patch-embedding dim (0 = no VLM)
    mtp: bool = False               # DeepSeek-V3 multi-token prediction module
    remat: bool = False             # checkpoint each scanned cycle
    unroll: bool = False            # python-unroll group repeats instead of
    # lax.scan — used by the dry-run so XLA cost_analysis counts every layer
    # (while-loop bodies are NOT multiplied by trip count), at the price of
    # depth-proportional HLO/compile time.

    @property
    def n_layers(self) -> int:
        return sum(g.repeats * len(g.cycle) for g in self.groups)


# ---------------------------------------------------------------------------
# init / specs
# ---------------------------------------------------------------------------

def _stack_spec(spec):
    """Prepend a None (repeat) dim to every PartitionSpec leaf."""
    return jax.tree.map(lambda s: P(None, *s), spec,
                        is_leaf=lambda s: isinstance(s, P))


def _group_init(key, g: GroupCfg, *, dtype):
    shared, stacked = {}, {}
    keys = jax.random.split(key, len(g.cycle))
    for i, bcfg in enumerate(g.cycle):
        if bcfg.shared:
            shared[str(i)] = block_init(keys[i], bcfg, dtype=dtype)
        else:
            bkeys = jax.random.split(keys[i], g.repeats)
            stacked[str(i)] = jax.vmap(
                lambda k, c=bcfg: block_init(k, c, dtype=dtype))(bkeys)
    return {"shared": shared, "stacked": stacked}


def _group_spec(g: GroupCfg):
    shared, stacked = {}, {}
    for i, bcfg in enumerate(g.cycle):
        if bcfg.shared:
            shared[str(i)] = block_spec(bcfg)
        else:
            stacked[str(i)] = _stack_spec(block_spec(bcfg))
    return {"shared": shared, "stacked": stacked}


def lm_init(key, cfg: LMCfg, *, dtype=jnp.float32):
    keys = jax.random.split(key, len(cfg.groups) + 4)
    p: dict = {
        "embed": core.embedding_init(keys[0], cfg.vocab, cfg.d_model, dtype=dtype),
        "groups": [
            _group_init(keys[2 + i], g, dtype=dtype)
            for i, g in enumerate(cfg.groups)
        ],
        "final_norm": (core.rmsnorm_init(cfg.d_model, dtype)
                       if cfg.final_norm == "rms"
                       else core.layernorm_init(
                           cfg.d_model, elementwise=cfg.final_norm == "ln",
                           dtype=dtype)),
    }
    if cfg.pos_embed == "learned":
        p["pos"] = core.normal_init(keys[1], (cfg.max_positions, cfg.d_model),
                                    0.02, dtype)
    if not cfg.tie_embeddings:
        p["lm_head"] = core.linear_init(keys[-1], cfg.d_model, cfg.vocab,
                                        dtype=dtype)
    if cfg.prefix_embed_dim:
        p["proj"] = core.linear_init(keys[-2], cfg.prefix_embed_dim,
                                     cfg.d_model, bias=True, dtype=dtype)
    if cfg.mtp:
        # DeepSeek-V3 MTP depth-1 module: norm both streams, project 2d->d,
        # one extra block, shared unembed.
        km1, km2 = jax.random.split(keys[-3])
        mtp_block = cfg.groups[-1].cycle[-1]
        p["mtp"] = {
            "norm_h": core.rmsnorm_init(cfg.d_model, dtype),
            "norm_e": core.rmsnorm_init(cfg.d_model, dtype),
            "proj": core.linear_init(km1, 2 * cfg.d_model, cfg.d_model,
                                     dtype=dtype),
            "block": block_init(km2, mtp_block, dtype=dtype),
        }
    return p


def lm_spec(cfg: LMCfg):
    s: dict = {
        "embed": core.embedding_spec(),
        "groups": [_group_spec(g) for g in cfg.groups],
        "final_norm": (core.rmsnorm_spec() if cfg.final_norm == "rms"
                       else core.layernorm_spec(
                           elementwise=cfg.final_norm == "ln")),
    }
    if cfg.pos_embed == "learned":
        s["pos"] = P(None, None)
    if not cfg.tie_embeddings:
        s["lm_head"] = {"w": P(None, "model")}
    if cfg.prefix_embed_dim:
        s["proj"] = {"w": P(None, None), "b": P(None)}
    if cfg.mtp:
        mtp_block = cfg.groups[-1].cycle[-1]
        s["mtp"] = {
            "norm_h": core.rmsnorm_spec(),
            "norm_e": core.rmsnorm_spec(),
            "proj": {"w": P(None, None)},
            "block": block_spec(mtp_block),
        }
    return s


# ---------------------------------------------------------------------------
# embedding / head
# ---------------------------------------------------------------------------

def _embed_inputs(p, cfg: LMCfg, tokens, prefix_embeds, *, compute_dtype,
                  pos_offset: int = 0):
    x = core.embed(p["embed"], tokens, compute_dtype=compute_dtype)
    if cfg.prefix_embed_dim and prefix_embeds is not None:
        vis = core.linear(p["proj"], prefix_embeds, compute_dtype=compute_dtype)
        x = jnp.concatenate([vis, x], axis=1)
    if cfg.pos_embed == "learned":
        L = x.shape[1]
        pos = jax.lax.dynamic_slice_in_dim(p["pos"], pos_offset, L, axis=0)
        x = x + pos.astype(compute_dtype)
    return x


def _logits(p, cfg: LMCfg, x, *, compute_dtype):
    if cfg.tie_embeddings:
        logits = core.unembed(p["embed"], x, compute_dtype=compute_dtype)
    else:
        w = p["lm_head"]["w"].astype(compute_dtype)
        logits = jnp.einsum("...d,dv->...v", x.astype(compute_dtype), w,
                            preferred_element_type=jnp.float32)
    return constrain(logits, batch_spec(None, "model"))


def _final_norm(p, cfg: LMCfg, x):
    if cfg.final_norm == "rms":
        return core.rmsnorm(p["final_norm"], x)
    return core.layernorm(p["final_norm"], x)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _index_tree(tree, i):
    return jax.tree.map(lambda a: a[i], tree)


def _stack_trees(trees):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def _group_forward(gp, g: GroupCfg, x, *, positions, impl, compute_dtype,
                   remat, unroll=False):
    def body(carry, xs):
        x, aux = carry
        for i, bcfg in enumerate(g.cycle):
            bp = gp["shared"][str(i)] if bcfg.shared else xs[str(i)]
            x, a = block_forward(bp, bcfg, x, positions=positions, impl=impl,
                                 compute_dtype=compute_dtype)
            aux = aux + a
        return (x, aux), None

    if remat:
        body = jax.checkpoint(body)
    if unroll:
        carry = (x, jnp.float32(0.0))
        for r in range(g.repeats):
            xs_r = (_index_tree(gp["stacked"], r) if gp["stacked"] else None)
            carry, _ = body(carry, xs_r)
        return carry
    xs = gp["stacked"] if gp["stacked"] else None
    (x, aux), _ = jax.lax.scan(body, (x, jnp.float32(0.0)), xs,
                               length=g.repeats)
    return x, aux


def lm_forward(p, cfg: LMCfg, tokens, *, prefix_embeds=None, positions=None,
               impl: str = "xla", compute_dtype=jnp.bfloat16):
    """tokens: (B, L_text) int32 [+ prefix_embeds (B, n_prefix, raw_dim)].

    Returns (logits (B, L, vocab) f32, aux_loss scalar)."""
    x = _embed_inputs(p, cfg, tokens, prefix_embeds,
                      compute_dtype=compute_dtype)
    L = x.shape[1]
    if positions is None:
        positions = jnp.arange(L)
    x = constrain(x, batch_spec(None, None))
    aux = jnp.float32(0.0)
    for gp, g in zip(p["groups"], cfg.groups):
        x, a = _group_forward(gp, g, x, positions=positions, impl=impl,
                              compute_dtype=compute_dtype, remat=cfg.remat,
                              unroll=cfg.unroll)
        aux = aux + a
    x = _final_norm(p, cfg, x)
    return _logits(p, cfg, x, compute_dtype=compute_dtype), aux


def softmax_xent(logits, labels, *, ignore: int = -100):
    """logits (B,L,V) f32; labels (B,L) int32 with `ignore` masked out."""
    mask = (labels != ignore)
    labels_safe = jnp.where(mask, labels, 0)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels_safe[..., None],
                               axis=-1)[..., 0]
    nll = (logz - gold) * mask
    return jnp.sum(nll) / jnp.maximum(jnp.sum(mask), 1)


def lm_loss(p, cfg: LMCfg, batch, *, impl: str = "xla",
            compute_dtype=jnp.bfloat16):
    """batch: {"tokens", "labels"[, "prefix_embeds"]}.  Returns (loss, metrics).

    With ``cfg.mtp`` the DeepSeek-V3 depth-1 MTP loss is added (weight 0.3)."""
    logits, aux = lm_forward(p, cfg, batch["tokens"],
                             prefix_embeds=batch.get("prefix_embeds"),
                             impl=impl, compute_dtype=compute_dtype)
    loss = softmax_xent(logits, batch["labels"])
    metrics = {"xent": loss, "aux": aux}
    if cfg.mtp:
        # depth-1 MTP: combine hidden h_{1:L-1} with embedding of t_{2:L}
        # (approximated from the token stream), one extra block, shared head.
        x = _embed_inputs(p, cfg, batch["tokens"], batch.get("prefix_embeds"),
                          compute_dtype=compute_dtype)
        h = core.rmsnorm(p["mtp"]["norm_h"], x[:, :-1])
        e = core.rmsnorm(p["mtp"]["norm_e"], x[:, 1:])
        hm = core.linear(p["mtp"]["proj"], jnp.concatenate([h, e], axis=-1),
                         compute_dtype=compute_dtype)
        mtp_block = cfg.groups[-1].cycle[-1]
        hm, a2 = block_forward(p["mtp"]["block"], mtp_block, hm,
                               positions=jnp.arange(hm.shape[1]),
                               compute_dtype=compute_dtype)
        mtp_logits = _logits(p, cfg, _final_norm(p, cfg, hm),
                             compute_dtype=compute_dtype)
        mtp_loss = softmax_xent(mtp_logits, batch["labels"][:, 1:])
        loss = loss + 0.3 * mtp_loss
        aux = aux + a2
        metrics["mtp_xent"] = mtp_loss
    loss = loss + aux
    metrics["loss"] = loss
    return loss, metrics


# ---------------------------------------------------------------------------
# cache / prefill / decode
# ---------------------------------------------------------------------------

def _stacked_cache(g: GroupCfg, make):
    """Per-repeat cache for every stateful block in the cycle."""
    out = {}
    for i, bcfg in enumerate(g.cycle):
        c = make(bcfg)
        if c:
            out[str(i)] = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (g.repeats,) + a.shape)
                if hasattr(a, "shape") else a, c)
    return out


def lm_init_cache(cfg: LMCfg, B: int, S: int, *, dtype=jnp.bfloat16):
    return [
        _stacked_cache(g, lambda b: block_init_cache(b, B, S, dtype=dtype))
        for g in cfg.groups
    ]


def lm_cache_spec(cfg: LMCfg, *, seq_shard: Optional[str] = None):
    out = []
    for g in cfg.groups:
        gs = {}
        for i, bcfg in enumerate(g.cycle):
            c = block_cache_spec(bcfg, seq_shard=seq_shard)
            if c:
                gs[str(i)] = _stack_spec(c)
        out.append(gs)
    return out


def _stack_routes(routes):
    """One scanned repeat's MoE routes, in layer order: ``{"ids": (m, B,
    L, K), "dropped": ()}`` for its m MoE blocks, ``{}`` for none."""
    if not routes:
        return {}
    return {"ids": jnp.stack([r["ids"] for r in routes]),
            "dropped": sum(r["dropped"] for r in routes)}


def _merge_routes(groups):
    """The routes of every group's scan -> ``{"ids": (n_moe_layers, B, L,
    K), "dropped": ()}``, or None for a model without MoE layers."""
    groups = [r for r in groups if r]
    if not groups:
        return None
    ids = [r["ids"].reshape((-1,) + r["ids"].shape[2:]) for r in groups]
    return {"ids": jnp.concatenate(ids),
            "dropped": sum(jnp.sum(r["dropped"]) for r in groups)}


def _group_prefill(gp, g: GroupCfg, x, cache, *, positions, impl,
                   compute_dtype, unroll=False):
    def body(x, xs):
        params_xs, cache_xs = xs
        new_cache, routes = {}, []
        for i, bcfg in enumerate(g.cycle):
            bp = gp["shared"][str(i)] if bcfg.shared else params_xs[str(i)]
            bc = cache_xs.get(str(i), {})
            x, nc, r = block_prefill(bp, bcfg, x, bc, positions=positions,
                                     impl=impl, compute_dtype=compute_dtype)
            if nc:
                new_cache[str(i)] = nc
            if r:
                routes.append(r)
        return x, (new_cache, _stack_routes(routes))

    if unroll:
        ys = []
        for r in range(g.repeats):
            x, y = body(x, (_index_tree(gp["stacked"], r),
                            _index_tree(cache, r)))
            ys.append(y)
        new_cache, routes = _stack_trees(ys)
        return x, new_cache, routes
    x, (new_cache, routes) = jax.lax.scan(body, x, (gp["stacked"], cache),
                                          length=g.repeats)
    return x, new_cache, routes


def lm_prefill(p, cfg: LMCfg, tokens, cache, *, prefix_embeds=None,
               length=None, impl: str = "xla", compute_dtype=jnp.bfloat16,
               return_route: bool = False):
    """Prefill positions [0, L); returns (logits at position ``length - 1``
    (B, 1, V), filled cache[, route]).

    ``length`` (default L) is the true prompt length when ``tokens`` is
    padded on the right to a compile shape: attention is causal, so no
    position below ``length`` sees a pad, and the pads' cache entries lie
    past the position decoding starts from, where a decode step writes
    before it reads.  ``return_route`` adds the MoE routes ``{"ids":
    (n_moe_layers, B, L, K), "dropped": ()}`` (None without MoE layers)."""
    x = _embed_inputs(p, cfg, tokens, prefix_embeds,
                      compute_dtype=compute_dtype)
    L = x.shape[1]
    positions = jnp.arange(L)
    x = constrain(x, batch_spec(None, None))
    new_cache, routes = [], []
    for gp, g, gc in zip(p["groups"], cfg.groups, cache):
        x, nc, r = _group_prefill(gp, g, x, gc, positions=positions,
                                  impl=impl, compute_dtype=compute_dtype,
                                  unroll=cfg.unroll)
        new_cache.append(nc)
        routes.append(r)
    last = L if length is None else length
    x = _final_norm(p, cfg, jax.lax.dynamic_slice_in_dim(x, last - 1, 1, axis=1))
    logits = _logits(p, cfg, x, compute_dtype=compute_dtype)
    if return_route:
        return logits, new_cache, _merge_routes(routes)
    return logits, new_cache


def _write_index(bcfg: BlockCfg, pos, S: int):
    """Where a decode step at ``pos`` writes in a sequence mixer's cache of
    length S: ``pos``, wrapped in a ring-buffer window."""
    a = bcfg.attn
    if bcfg.mixer == "attn" and a.ring and a.window is not None:
        return pos % S
    return pos


def _put_rows(c, e, r, at):
    """``c`` (repeats, B, S, ...) with the rows ``e`` (B, 1, ...) written in
    layer r at position ``at``: a scalar for the batch, or one per row."""
    e = e.astype(c.dtype)
    if jnp.ndim(at) == 0:
        return jax.lax.dynamic_update_slice(c, e[None], (r, 0, at) + (0,) * (c.ndim - 3))
    return c.at[r, jnp.arange(c.shape[1]), at].set(e[:, 0])


def _group_decode(gp, g: GroupCfg, x, cache, pos, *, compute_dtype,
                  unroll=False):
    """The group's cache rides in the scan's carry.  Each layer of a
    sequence mixer only reads it and writes its new token's row into it;
    a recurrent mixer's state is replaced.  No copy of the cache is made:
    an MLA mixer's decode kernel reads its layer in the stacked cache."""
    def body(carry, xs):
        x, cache = carry
        params_xs, r = xs
        routes = []
        for i, bcfg in enumerate(g.cycle):
            k = str(i)
            bp = gp["shared"][k] if bcfg.shared else params_xs[k]
            bc = {n: c if n == "mixer" and bcfg.mixer == "mla"
                  else jax.tree.map(lambda a: a[r], c)
                  for n, c in cache.get(k, {}).items()}
            x, nc, route = block_decode(bp, bcfg, x, bc, pos, layer=r,
                                        compute_dtype=compute_dtype)
            if nc and bcfg.mixer in ("attn", "mla"):
                rows = jax.tree.map(
                    lambda c, e: _put_rows(c, e, r, _write_index(bcfg, pos, c.shape[2])),
                    cache[k]["mixer"], nc["mixer"])
                cache = {**cache, k: {**cache[k], "mixer": rows}}
            elif nc:
                cache = {**cache, k: jax.tree.map(
                    lambda c, n: jax.lax.dynamic_update_index_in_dim(
                        c, n.astype(c.dtype), r, axis=0), cache[k], nc)}
            if route:
                routes.append(route)
        return (x, cache), _stack_routes(routes)

    xs = (gp["stacked"], jnp.arange(g.repeats))
    if unroll:
        ys = []
        for r in range(g.repeats):
            (x, cache), y = body((x, cache), _index_tree(xs, r))
            ys.append(y)
        return x, cache, _stack_trees(ys)
    (x, cache), routes = jax.lax.scan(body, (x, cache), xs, length=g.repeats)
    return x, cache, routes


def lm_decode(p, cfg: LMCfg, token, cache, pos, *,
              compute_dtype=jnp.bfloat16, return_route: bool = False):
    """One-token decode.  token: (B, 1) int32; pos: int32 absolute position
    of `token`, a scalar for the batch or one per row (B,).  Returns
    (logits (B,1,V), new_cache[, route]), the route as ``lm_prefill``'s."""
    x = core.embed(p["embed"], token, compute_dtype=compute_dtype)
    if cfg.pos_embed == "learned":
        x = x + jnp.take(p["pos"], jnp.reshape(pos, (-1, 1)),
                         axis=0).astype(compute_dtype)
    x = constrain(x, batch_spec(None, None))
    new_cache, routes = [], []
    for gp, g, gc in zip(p["groups"], cfg.groups, cache):
        x, nc, r = _group_decode(gp, g, x, gc, pos, compute_dtype=compute_dtype,
                                 unroll=cfg.unroll)
        new_cache.append(nc)
        routes.append(r)
    x = _final_norm(p, cfg, x)
    logits = _logits(p, cfg, x, compute_dtype=compute_dtype)
    if return_route:
        return logits, new_cache, _merge_routes(routes)
    return logits, new_cache
