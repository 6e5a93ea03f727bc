"""Faults planted in the gateway's program underneath a run, to show that
the comparison deciding the gateway cell's ``correct`` catches them
(``chipbench/tests`` and ``chipbench/calibrate_gateway.py``).  Never used
by a benchmark run.

- ``padded``: the engine before its repair: the first token from the last
  pad of the prompt's prefill bucket, decoding from the bucket's length,
  so later tokens attend the pads' keys.
- ``overflow``: the serving expert dispatch with a capacity of 1.25
  times the even share per routed expert (the training dispatch's), in
  prefill and decode: picks past an expert's capacity are dropped.
- ``no_mscale``: YaRN's softmax factor mscale**2 left out of attention.
"""
from __future__ import annotations

import contextlib
import math

import jax
import jax.numpy as jnp

from chipbench import program  # noqa: F401  (puts the program on the path)

import repro.models.blocks as blocks  # noqa: E402
import repro.nn.moe as moe  # noqa: E402
import repro.nn.rotary as rotary  # noqa: E402
import repro.serving.engine as engine  # noqa: E402

FAULTS = ("padded", "overflow", "no_mscale")
CAPACITY = 1.25


def _last_pad(orig):
    def prefill(*a, length=None, **kw):
        return orig(*a, **kw)
    return prefill


def _from_bucket(orig):
    def admit(self, uid, prompt, max_new_tokens):
        slot = orig(self, uid, prompt, max_new_tokens)
        self.pos[slot] = min(engine._bucket(len(prompt)), self.sc.max_seq)
        return slot
    return admit


def _capacity_dispatch(orig):
    def dispatch(p, cfg, x, *, compute_dtype=jnp.bfloat16):
        B, L, D = x.shape
        T, K = B * L, cfg.top_k
        count = moe.held_range(cfg)[1]
        cap = max(math.ceil(T * K * CAPACITY / cfg.n_experts), K)
        xt = x.reshape(T, D)
        w, ids, _ = moe.route(p["router"]["w"], cfg, xt)
        local, w = moe._held_picks(cfg, ids, w)
        flat = local.reshape(T * K)
        order = jnp.argsort(flat, stable=True)
        e_sorted = flat[order]
        starts = jnp.searchsorted(e_sorted, jnp.arange(count + 1), side="left")
        rank = jnp.arange(T * K) - starts[e_sorted]
        keep = jnp.zeros(T * K, bool).at[order].set(rank < cap).reshape(T, K)
        gates = jnp.zeros((T, count + 1), jnp.float32).at[
            jnp.arange(T)[:, None], local].add(jnp.where(keep, w, 0.0))[:, :count]
        xc = xt.astype(compute_dtype)
        cd = lambda a: a.astype(compute_dtype)
        h = (moe.silu(jnp.einsum("td,edf->tef", xc, cd(p["gate"])))
             * jnp.einsum("td,edf->tef", xc, cd(p["up"])))
        out = jnp.einsum("tef,efd->ted", h, cd(p["down"]))
        y = jnp.einsum("ted,te->td", out, cd(gates)).reshape(B, L, D)
        if "shared" in p:
            y = y + moe._shared(p, cfg, x, compute_dtype)
        dropped = jnp.sum((local < count) & ~keep).astype(jnp.int32)
        return y, {"ids": ids.reshape(B, L, K), "dropped": dropped}
    return dispatch


def _targets(name):
    if name == "padded":
        return [(engine, "lm_prefill", _last_pad),
                (engine.Engine, "admit", _from_bucket)]
    if name == "overflow":
        return [(blocks, "moe_prefill", _capacity_dispatch),
                (blocks, "moe_decode", _capacity_dispatch)]
    if name == "no_mscale":
        return [(rotary.YaRN, "softmax_factor", lambda _: property(lambda self: 1.0))]
    raise ValueError(f"unknown fault {name!r}")


@contextlib.contextmanager
def planted(name: str | None):
    """Run the ``with`` block with fault ``name`` in the program (``None``:
    no fault).  Jitted programs are dropped on entry and exit, so no program
    compiled with the fault outlives the block."""
    if name is None:
        yield
        return
    targets = _targets(name)
    saved = [(obj, attr, obj.__dict__[attr]) for obj, attr, _ in targets]
    for obj, attr, wrap in targets:
        setattr(obj, attr, wrap(getattr(obj, attr)))
    jax.clear_caches()
    try:
        yield
    finally:
        for obj, attr, orig in saved:
            setattr(obj, attr, orig)
        jax.clear_caches()
