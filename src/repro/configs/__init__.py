"""Architecture registry: the 10 assigned architectures × 4 input shapes.

Each ``configs/<id>.py`` exports ``ARCH: Arch`` with the exact assigned
config (``make_full``) and a reduced same-family smoke variant
(``make_smoke``).  ``input_specs(arch, shape)`` returns weak-type-correct
``ShapeDtypeStruct`` stand-ins for every model input of the corresponding
step (train / prefill / decode) — no device allocation, as used by the
multi-pod dry-run.
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ShapeCfg:
    name: str
    seq_len: int
    global_batch: int
    step: str  # "train" | "prefill" | "decode"


SHAPES: Dict[str, ShapeCfg] = {
    "train_4k": ShapeCfg("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeCfg("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeCfg("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeCfg("long_500k", 524288, 1, "decode"),
}

# window applied to attention archs for the sub-quadratic long_500k variant
LONG_CONTEXT_WINDOW = 8192


@dataclasses.dataclass(frozen=True)
class Arch:
    name: str
    family: str                      # dense|moe|hybrid|ssm|audio|vlm
    cite: str
    make_full: Callable[..., Any]    # kwargs: window, remat
    make_smoke: Callable[[], Any]
    kind: str = "lm"                 # "lm" | "whisper"
    n_prefix: int = 0                # VLM vision slots
    prefix_embed_dim: int = 0        # VLM raw patch dim
    needs_window_for_long: bool = True   # False for ssm/hybrid (native)
    supports_long: bool = True       # whisper: False (see DESIGN.md)


ARCH_IDS = [
    "qwen2_0_5b", "olmo_1b", "codeqwen1_5_7b", "deepseek_v3_671b",
    "zamba2_7b", "deepseek_v2_236b", "mamba2_130m", "whisper_small",
    "internvl2_2b", "qwen3_4b",
]

_ALIASES = {i.replace("_", "-"): i for i in ARCH_IDS}
_ALIASES.update({
    "qwen2-0.5b": "qwen2_0_5b", "olmo-1b": "olmo_1b",
    "codeqwen1.5-7b": "codeqwen1_5_7b", "deepseek-v3-671b": "deepseek_v3_671b",
    "zamba2-7b": "zamba2_7b", "deepseek-v2-236b": "deepseek_v2_236b",
    "mamba2-130m": "mamba2_130m", "whisper-small": "whisper_small",
    "internvl2-2b": "internvl2_2b", "qwen3-4b": "qwen3_4b",
    # served by the gateway's Engine, outside the 10 x 4 dry-run matrix
    "deepseek-v2-lite": "deepseek_v2_lite",
})


def canonical_id(name: str) -> str:
    """'qwen2-0.5b' -> 'qwen2_0_5b' (the module id used in filenames)."""
    return _ALIASES.get(name, name)


def get_arch(name: str) -> Arch:
    mod_name = _ALIASES.get(name, name)
    mod = importlib.import_module(f"repro.configs.{mod_name}")
    return mod.ARCH


def list_archs():
    return [get_arch(i) for i in ARCH_IDS]


def supports(arch: Arch, shape: str) -> Tuple[bool, str]:
    if shape == "long_500k" and not arch.supports_long:
        return False, ("decoder uses learned absolute positions capped at "
                       "448 in the source model; a 524k decoder context has "
                       "no meaningful analogue (DESIGN.md §Shape skips)")
    return True, ""


def make_cfg(arch: Arch, shape: str, *, remat: Optional[bool] = None,
             unroll: bool = False):
    """Model config for (arch, shape): applies the sliding-window variant for
    attention-family archs on long_500k, remat for training shapes.
    ``unroll=True`` python-unrolls layer stacks (dry-run cost accounting)."""
    kw = {}
    if shape == "long_500k" and arch.needs_window_for_long:
        kw["window"] = LONG_CONTEXT_WINDOW
    if remat is None:
        remat = SHAPES[shape].step == "train"
    kw["remat"] = remat
    cfg = arch.make_full(**kw)
    if unroll:
        cfg = dataclasses.replace(cfg, unroll=True)
    return cfg


# ---------------------------------------------------------------------------
# input specs (ShapeDtypeStruct stand-ins — no allocation)
# ---------------------------------------------------------------------------

def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def input_specs(arch: Arch, shape: str, *, cache_dtype=jnp.bfloat16):
    """Returns (step, inputs: dict[str, pytree-of-ShapeDtypeStruct]).

    train:   {tokens, labels[, prefix_embeds | frame_embeds]}
    prefill: {tokens[, prefix_embeds | frame_embeds], cache}
    decode:  {token, cache, pos}
    """
    sc = SHAPES[shape]
    cfg = make_cfg(arch, shape)
    B, L = sc.global_batch, sc.seq_len
    step = sc.step

    if arch.kind == "whisper":
        from repro.models.whisper import whisper_init_cache
        fe = _sds((B, cfg.n_frames, cfg.d_model), jnp.bfloat16)
        if step == "train":
            return step, {"frame_embeds": fe,
                          "tokens": _sds((B, L), jnp.int32),
                          "labels": _sds((B, L), jnp.int32)}
        cache = jax.eval_shape(
            lambda: whisper_init_cache(cfg, B, L, dtype=cache_dtype))
        if step == "prefill":
            return step, {"frame_embeds": fe,
                          "tokens": _sds((B, L), jnp.int32), "cache": cache}
        return step, {"token": _sds((B, 1), jnp.int32), "cache": cache,
                      "pos": _sds((), jnp.int32)}

    from repro.models.lm import lm_init_cache
    n_pre = arch.n_prefix
    if step == "train":
        d = {"tokens": _sds((B, L - n_pre), jnp.int32),
             "labels": _sds((B, L), jnp.int32)}
        if n_pre:
            d["prefix_embeds"] = _sds((B, n_pre, arch.prefix_embed_dim),
                                      jnp.bfloat16)
        return step, d
    if step == "prefill":
        cache = jax.eval_shape(
            lambda: lm_init_cache(cfg, B, L, dtype=cache_dtype))
        d = {"tokens": _sds((B, L - n_pre), jnp.int32), "cache": cache}
        if n_pre:
            d["prefix_embeds"] = _sds((B, n_pre, arch.prefix_embed_dim),
                                      jnp.bfloat16)
        return step, d
    cache = jax.eval_shape(lambda: lm_init_cache(cfg, B, L, dtype=cache_dtype))
    return step, {"token": _sds((B, 1), jnp.int32), "cache": cache,
                  "pos": _sds((), jnp.int32)}
