"""Plain reference of DeepSeek-V2-Lite for the gateway cell: a float32
``jax.numpy`` forward pass of the published layer, one sequence at a time,
with no cache, batching or kernels, every product at ``HIGHEST`` precision.
It imports nothing of the program.

The layer (arXiv:2405.04434; the model's config.json):

- pre-norm residual blocks, RMSNorm with eps 1e-6;
- latent attention in its expanded form: q = h Wq (no q compression), per
  head 128 "nope" and 64 rotary dims; [c; k_r] = h Wkv_a with c (512)
  RMS-normed and k_r (64) one rotary key shared by all heads;
  [k_nope; v] = c Wkv_b; softmax over (q_nope k_nope + q_r k_r) times
  ``192**-0.5 * mscale**2`` with ``mscale = 0.1 * 0.707 * ln(40) + 1``,
  causal; output through Wo;
- YaRN rotary frequencies: ``f_i = theta**(-2i/64)``; pairs below the
  index where the rotations over 4096 positions equal beta_fast = 32 keep
  ``f_i``, pairs above the one where they equal beta_slow = 1 take
  ``f_i / 40``, a linear ramp between; cos and sin scaled by
  mscale(0.707) / mscale(0.707) = 1;
- layer 0: SwiGLU feed-forward of width 10944;
- layers 1-26: softmax router over all 64 experts, the 6 largest
  probabilities picked greedily and used as they are (no renormalisation,
  routed_scaling_factor 1); each picked expert held here (``held``) adds
  its SwiGLU output (width 1408) times its probability, picks of absent
  experts add nothing; 2 shared experts, one SwiGLU of width 2816, for
  every token;
- final RMSNorm and an untied output head.

Departures from the published model, each shared with the program: the
rotary pairs are the halves of the 64 rotary dims (i, i + 32) and not the
checkpoint's interleaved (2i, 2i + 1), which is a fixed permutation of the
columns of Wq and Wkv_a and the same model under random weights; weights
are random, drawn from the seed as below and rounded to bfloat16 as the
checkpoint stores them.

Weights follow the program's documented key derivation
(``repro.models.lm.lm_init`` with ``dtype=bfloat16``): one key per weight,
split from the seed's key in the order of the program's initialisers, so
that a layer's weights can be made alone and dropped once it is applied.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-6


# -- shapes -----------------------------------------------------------------

def shapes(config: dict) -> dict:
    """The sizes the reference needs, read from a configuration file."""
    rs = config["rope_scaling"]
    dep = config["deployment"]
    first, count = dep["held_experts"]
    return {"D": config["hidden_size"], "V": config["vocab_size"],
            "H": config["num_attention_heads"], "C": config["kv_lora_rank"],
            "dn": config["qk_nope_head_dim"], "dr": config["qk_rope_head_dim"],
            "dv": config["v_head_dim"], "F_dense": config["intermediate_size"],
            "F": config["moe_intermediate_size"],
            "E": dep["routed_experts_published"],
            "K": config["num_experts_per_tok"],
            "shared": config["n_shared_experts"],
            "layers": config["num_hidden_layers"],
            "dense_layers": config["first_k_dense_replace"],
            "theta": float(config["rope_theta"]),
            "yarn": {"factor": float(rs["factor"]),
                     "original": int(rs["original_max_position_embeddings"]),
                     "beta_fast": float(rs["beta_fast"]),
                     "beta_slow": float(rs["beta_slow"]),
                     "mscale": float(rs["mscale"]),
                     "mscale_all_dim": float(rs["mscale_all_dim"])},
            "first": int(first), "held": int(count)}


# -- weights from the seed ---------------------------------------------------

def _trunc(key, shape, dt):
    w = jax.random.truncated_normal(key, -2.0, 2.0, shape) * (1.0 / math.sqrt(shape[0]))
    return w.astype(dt).astype(jnp.float32)


def _normal(key, shape, std, dt):
    return (jax.random.normal(key, shape) * std).astype(dt).astype(jnp.float32)


def _swiglu_weights(key, D, F, dt):
    k_up, k_down, k_gate = jax.random.split(key, 3)
    return {"up": _trunc(k_up, (D, F), dt), "down": _trunc(k_down, (F, D), dt),
            "gate": _trunc(k_gate, (D, F), dt)}


def layer_weights(key, c: dict, moe: bool, dt=jnp.bfloat16) -> dict:
    """The weights of one layer from its key (``block_init``): attention
    (``mla_init``) and either the dense feed-forward (``mlp_init``) or the
    router, the held experts and the shared experts (``moe_init``).  Norm
    scales are 1."""
    D, H = c["D"], c["H"]
    k_attn, _, k_ffn = jax.random.split(key, 3)
    ks = jax.random.split(k_attn, 6)
    w = {"wq": _trunc(ks[1], (D, H * (c["dn"] + c["dr"])), dt),
         "wkv_a": _trunc(ks[2], (D, c["C"] + c["dr"]), dt),
         "wkv_b": _trunc(ks[3], (c["C"], H * (c["dn"] + c["dv"])), dt),
         "wo": _trunc(ks[4], (H * c["dv"], D), dt)}
    if not moe:
        w["ffn"] = _swiglu_weights(k_ffn, D, c["F_dense"], dt)
        return w
    kr, ku, kg, kd, k_sh = jax.random.split(k_ffn, 5)
    n, F = c["held"], c["F"]
    w["router"] = jax.random.normal(kr, (D, c["E"])) * (1.0 / math.sqrt(D))
    w["up"] = _normal(ku, (n, D, F), 1.0 / math.sqrt(D), dt)
    w["gate"] = _normal(kg, (n, D, F), 1.0 / math.sqrt(D), dt)
    w["down"] = _normal(kd, (n, F, D), 1.0 / math.sqrt(F), dt)
    w["shared"] = _swiglu_weights(k_sh, D, F * c["shared"], dt)
    return w


def layer_keys(seed_key, c: dict) -> list:
    """One key per layer, in layer order (``lm_init`` -> ``_group_init``:
    the dense group from the model key's third split, the MoE group from
    its fourth, each group's repeats split from its one cycle key)."""
    keys = jax.random.split(seed_key, 6)
    dense = jax.random.split(jax.random.split(keys[2], 1)[0], c["dense_layers"])
    moe = jax.random.split(jax.random.split(keys[3], 1)[0],
                           c["layers"] - c["dense_layers"])
    return list(dense) + list(moe)


def embed_weights(seed_key, c: dict, dt=jnp.bfloat16):
    keys = jax.random.split(seed_key, 6)
    return _normal(keys[0], (c["V"], c["D"]), 1.0 / math.sqrt(c["D"]), dt)


def head_weights(seed_key, c: dict, dt=jnp.bfloat16):
    keys = jax.random.split(seed_key, 6)
    return _trunc(keys[5], (c["D"], c["V"]), dt)


# -- the layer -----------------------------------------------------------------

def _rms(x):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + EPS)


def _mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def yarn_freqs(c: dict):
    d, theta, y = c["dr"], c["theta"], c["yarn"]
    base = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)

    def corr(rot):
        return d * math.log(y["original"] / (rot * 2 * math.pi)) / (2 * math.log(theta))

    lo = max(math.floor(corr(y["beta_fast"])), 0)
    hi = min(math.ceil(corr(y["beta_slow"])), d - 1)
    hi = hi + 0.001 if hi == lo else hi
    ramp = np.clip((np.arange(d // 2) - lo) / (hi - lo), 0.0, 1.0)
    return (base / y["factor"] * ramp + base * (1 - ramp)).astype(np.float32)


def _rope(x, pos, c):
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(yarn_freqs(c))
    y = c["yarn"]
    s = _mscale(y["factor"], y["mscale"]) / _mscale(y["factor"], y["mscale_all_dim"])
    cos, sin = jnp.cos(ang) * s, jnp.sin(ang) * s
    if x.ndim == 3:
        cos, sin = cos[:, None], sin[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(w, h, c: dict):
    """Expanded latent attention over one causal sequence h (S, D)."""
    S, H = h.shape[0], c["H"]
    pos = jnp.arange(S)
    q = (h @ w["wq"]).reshape(S, H, c["dn"] + c["dr"])
    q_nope, q_r = q[..., :c["dn"]], _rope(q[..., c["dn"]:], pos, c)
    kv_a = h @ w["wkv_a"]
    lat, k_r = _rms(kv_a[:, :c["C"]]), _rope(kv_a[:, c["C"]:], pos, c)
    kv = (lat @ w["wkv_b"]).reshape(S, H, c["dn"] + c["dv"])
    k_nope, v = kv[..., :c["dn"]], kv[..., c["dn"]:]
    y = c["yarn"]
    scale = _mscale(y["factor"], y["mscale_all_dim"]) ** 2 / math.sqrt(c["dn"] + c["dr"])
    scores = (jnp.einsum("qhd,khd->hqk", q_nope, k_nope)
              + jnp.einsum("qhd,kd->hqk", q_r, k_r)) * scale
    scores = jnp.where(pos[None, :, None] >= pos[None, None, :], scores, -jnp.inf)
    out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    return out.reshape(S, H * c["dv"]) @ w["wo"]


def swiglu(w, h):
    return (jax.nn.silu(h @ w["gate"]) * (h @ w["up"])) @ w["down"]


def moe(w, h, c: dict):
    """Routed experts held here plus the shared experts; returns the output
    and each token's 6 picked experts (S, K), largest probability first."""
    probs = jax.nn.softmax(h @ w["router"], axis=-1)
    p, ids = jax.lax.top_k(probs, c["K"])
    y = swiglu(w["shared"], h)
    for j in range(c["held"]):
        wt = jnp.sum(jnp.where(ids == c["first"] + j, p, 0.0), axis=-1)
        ew = {"gate": w["gate"][j], "up": w["up"][j], "down": w["down"][j]}
        y = y + wt[:, None] * swiglu(ew, h)
    return y, ids


def layer(w, x, c: dict, moe_layer: bool):
    x = x + attention(w, _rms(x), c)
    if not moe_layer:
        return x + swiglu(w["ffn"], _rms(x)), None
    y, ids = moe(w, _rms(x), c)
    return x + y, ids


# -- the whole forward pass, layer by layer --------------------------------------

def forward(seed_key, c: dict, tokens, rows, *, dt=jnp.float32):
    """Teacher-forced logits of each sequence, one at a time.  ``tokens``
    (n, P) int: sequences padded at the end to one length P, so that one
    compiled layer serves them all (causal attention and dropless routing
    keep every position free of what follows it).  ``rows[i]`` is
    ``(positions (R,), vocab ids (R, M))``: the logits returned for
    sequence i.  Also returns each sequence's picked experts (P, n_moe, K).

    Weights are made one layer at a time from ``seed_key`` and dropped
    after use.  ``dt`` below float32 is the control: the weights and the
    residual stream between layers rounded to ``dt``."""
    def cast(t):
        if dt == jnp.float32:
            return t
        return jax.tree.map(lambda a: a.astype(dt).astype(jnp.float32), t)

    make = {m: jax.jit(lambda k, m=m: cast(layer_weights(k, c, m)))
            for m in (False, True)}
    step = {m: jax.jit(lambda w, x, m=m: _step(w, x, c, m, cast))
            for m in (False, True)}
    with jax.default_matmul_precision("highest"):
        emb = jax.jit(lambda k: cast(embed_weights(k, c)))(seed_key)
        xs = [cast(emb[jnp.asarray(t)]) for t in tokens]
        del emb
        ids = [[] for _ in xs]
        for li, k in enumerate(layer_keys(seed_key, c)):
            moe_layer = li >= c["dense_layers"]
            w = make[moe_layer](k)
            for i in range(len(xs)):
                xs[i], r = step[moe_layer](w, xs[i])
                if moe_layer:
                    ids[i].append(r)
            del w
        head = jax.jit(lambda k: cast(head_weights(k, c)))(seed_key)
        out = []
        for i, (p, v) in enumerate(rows):
            logits = _rms(xs[i][jnp.asarray(p)]) @ head
            out.append(np.asarray(jnp.take_along_axis(logits, jnp.asarray(v), axis=1)))
    return out, [np.stack([np.asarray(r) for r in rs], axis=1) for rs in ids]


def _step(w, x, c, moe_layer, cast):
    x, ids = layer(w, x, c, moe_layer)
    return cast(x), ids
