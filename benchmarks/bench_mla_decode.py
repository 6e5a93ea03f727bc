"""Absorbed-MLA decode attention: the Pallas kernel's variants against the
XLA form, one decode step over every layer of a stacked latent cache.

The default shapes are the gateway cell's (DeepSeek-V2-Lite, 64 slots,
2048 positions, rows of 640, 16 heads, 27 layers), with each slot's
position drawn as the `serve-b64` traffic stands at a window's start: a
lognormal prompt plus a uniform point of a lognormal output.  A variant
sets the kernel's block rows, query rows a grid step holds, and buffers
(`kernels/mla_decode.py`'s `BLOCK_ROWS`, `GROUP_ROWS`, `BUFFERS`).
Prints one JSON line per variant: the median ms of a step over
``--reps`` timed calls, and the largest difference from the XLA form.

  python -m benchmarks.bench_mla_decode                  # on the chip
  JAX_PLATFORMS=cpu python -m benchmarks.bench_mla_decode \
      --slots 4 --seq 64 --layers 2 --reps 2 --variants 16/512/2
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import mla_decode as K
from repro.kernels.ref import mla_decode_ref

DEFAULT_VARIANTS = ("128/512/2", "256/512/2", "256/256/2", "256/1024/2",
                    "512/512/2", "128/512/4", "256/512/3", "512/512/3")


def positions(rng, slots: int, seq: int) -> np.ndarray:
    """Prompt plus a uniform point of the output, lognormal lengths of
    median seq * 3/16 (384 of 2048), sigma 0.5, as `serve-b64` draws."""
    med = seq * 3 / 16
    draw = lambda: np.clip(rng.lognormal(np.log(med), 0.5, slots),
                           seq / 16, seq / 2).astype(int)
    return np.minimum(draw() + (rng.random(slots) * draw()).astype(int),
                      seq - 1).astype(np.int32)


def _timed(fn, args, reps: int) -> float:
    jax.block_until_ready(fn(*args))
    ms = []
    for _ in range(reps):
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ms.append((time.perf_counter() - t) * 1e3)
    return float(np.median(ms))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--slots", type=int, default=64)
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--layers", type=int, default=27)
    ap.add_argument("--heads", type=int, default=16)
    ap.add_argument("--width", type=int, default=640)
    ap.add_argument("--reps", type=int, default=30)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--variants", default=",".join(DEFAULT_VARIANTS),
                    help="block rows/query rows/buffers, comma-separated")
    args = ap.parse_args()
    B, H, W, S, R = args.slots, args.heads, args.width, args.seq, args.layers
    C, scale = 512 * W // 640, 0.1
    rng = np.random.default_rng(args.seed)
    ks = jax.random.split(jax.random.PRNGKey(args.seed), 3)
    q = jax.random.normal(ks[0], (B, H, W), jnp.bfloat16)
    own = jax.random.normal(ks[1], (B, W), jnp.bfloat16)
    kv = jax.random.normal(ks[2], (R, B, S, W), jnp.bfloat16)
    pos = jnp.asarray(positions(rng, B, S))
    interpret = jax.default_backend() == "cpu"

    def step(attend):
        """A step's attention over every layer; the contexts summed, so
        that no layer's call is left out."""
        def run(q, own, kv, pos):
            def body(acc, r):
                return acc + attend(q, own, kv, pos, r).astype(jnp.float32), None
            return jax.lax.scan(body, jnp.zeros((B, H, C), jnp.float32),
                                jnp.arange(R))[0]
        return jax.jit(run)

    ref = step(lambda q, own, kv, pos, r: mla_decode_ref(
        q, own, kv, pos, r, scale=scale, width=C))
    want = ref(q, own, kv, pos)
    base = {"slots": B, "seq": S, "layers": R, "pos_mean": float(pos.mean()),
            "pos_max": int(pos.max()), "backend": jax.default_backend()}
    print(json.dumps({**base, "variant": "xla",
                      "step_ms": _timed(ref, (q, own, kv, pos), args.reps)}),
          flush=True)
    for v in args.variants.split(","):
        bk, group, bufs = (int(x) for x in v.split("/"))
        K.BLOCK_ROWS, K.GROUP_ROWS, K.BUFFERS = bk, group, bufs
        fn = step(lambda q, own, kv, pos, r: K.mla_decode_bhw(
            q, own[:, None], kv, pos, jnp.reshape(r, (1,)).astype(jnp.int32),
            scale=scale, width=C, interpret=interpret))
        got = fn(q, own, kv, pos)
        print(json.dumps({
            **base, "variant": v,
            "step_ms": _timed(fn, (q, own, kv, pos), args.reps),
            "max_abs_diff": float(jnp.max(jnp.abs(got - want)))}), flush=True)


if __name__ == "__main__":
    main()
