"""Model FLOPs and the bytes a decode step must read, for the gateway's
latent-attention mixture-of-experts model, worked out from a
configuration's shapes (``reference_lm.shapes``).

FLOPs count matrix products only, 2 per multiply-add, and only the work
the model needs: a prefill's true prompt length (not its padded compile
shape) with causal attention over the pairs it has, its logits at the last
position; a decode token's absorbed latent attention over the positions it
attends; routed experts only for the picks of experts held here (the
program's counters).  Bytes are those a decode step cannot avoid reading:
every weight but the embedding table (of which it reads the rows of its
tokens), the held experts that at least one pick of the step chose, and
the latent cache of the positions the step's tokens attend.
"""
from __future__ import annotations

BF16 = 2
F32 = 4


def attn_proj_macs(c: dict) -> int:
    """Multiply-adds of one token through a layer's attention projections,
    expanded form (q, kv_a, kv_b, o)."""
    D, H = c["D"], c["H"]
    return (D * H * (c["dn"] + c["dr"]) + D * (c["C"] + c["dr"])
            + c["C"] * H * (c["dn"] + c["dv"]) + H * c["dv"] * D)


def ffn_macs(c: dict) -> int:
    """Multiply-adds of one token through every layer's feed-forward part
    that does not depend on routing: the dense layers, and per MoE layer the
    router and the shared experts."""
    D = c["D"]
    moe_layers = c["layers"] - c["dense_layers"]
    return (c["dense_layers"] * 3 * D * c["F_dense"]
            + moe_layers * (D * c["E"] + 3 * D * c["F"] * c["shared"]))


def routed(c: dict, picks: int) -> int:
    """FLOPs of ``picks`` (token, held expert) pairs."""
    return 2 * picks * 3 * c["D"] * c["F"]


def prefill(c: dict, L: int) -> int:
    """FLOPs of one prefill of true length L, routed experts aside."""
    H, pairs = c["H"], L * (L + 1) // 2
    per_layer = (L * attn_proj_macs(c)
                 + H * (c["dn"] + c["dr"]) * pairs + H * c["dv"] * pairs)
    return 2 * (c["layers"] * per_layer + L * ffn_macs(c) + c["D"] * c["V"])


def decode(c: dict, tokens: int, attended: int) -> int:
    """FLOPs of ``tokens`` decoded tokens that together attend ``attended``
    cache positions, routed experts aside.  Absorbed latent attention: the
    query through W_uk into the latent, scores against the latent and
    rotary keys, the context out through W_uv."""
    D, H, C = c["D"], c["H"], c["C"]
    proj = (D * H * (c["dn"] + c["dr"]) + D * (C + c["dr"])
            + 2 * H * c["dn"] * C + H * c["dv"] * D)
    per_pos = H * (C + c["dr"]) + H * C
    return 2 * (tokens * (c["layers"] * proj + ffn_macs(c) + D * c["V"])
                + c["layers"] * per_pos * attended)


def weight_bytes(c: dict) -> dict:
    """Bytes of the model's weights as stored (bfloat16, routers float32),
    by part."""
    D = c["D"]
    moe_layers = c["layers"] - c["dense_layers"]
    norms = c["layers"] * (2 * D + c["C"]) + D
    return {"embedding": BF16 * c["V"] * D,
            "head": BF16 * D * c["V"],
            "attention": BF16 * (c["layers"] * attn_proj_macs(c) + norms),
            "dense": BF16 * c["dense_layers"] * 3 * D * c["F_dense"],
            "router": F32 * moe_layers * D * c["E"],
            "shared": BF16 * moe_layers * 3 * D * c["F"] * c["shared"],
            "expert": BF16 * 3 * D * c["F"],
            "experts_held": BF16 * moe_layers * c["held"] * 3 * D * c["F"]}


def decode_step_bytes(c: dict, tokens: float, experts_touched: float,
                      attended: float) -> float:
    """Bytes a decode step of ``tokens`` tokens must read, where
    ``experts_touched`` (layer, held expert) pairs ran for at least one pick
    and the tokens attend ``attended`` cache positions in all."""
    w = weight_bytes(c)
    fixed = w["head"] + w["attention"] + w["dense"] + w["router"] + w["shared"]
    cache = c["layers"] * (c["C"] + c["dr"]) * BF16
    return (fixed + tokens * BF16 * c["D"] + experts_touched * w["expert"]
            + attended * cache)
