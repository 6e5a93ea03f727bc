"""Unit tests for the nn substrate: norms, rope, attention (incl. decode
consistency), MLA absorbed-decode equivalence, MoE dispatch, SSD."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.nn import core
from repro.nn.attention import (AttnCfg, attn_decode, attn_forward,
                                attn_init, init_kv_cache)
from repro.nn.mla import (MLACfg, init_mla_cache, mla_decode, mla_forward,
                          mla_init)
from repro.nn.moe import MoECfg, moe_apply, moe_init
from repro.nn.rotary import apply_rope, rope_cos_sin
from repro.nn.ssm import SSMCfg, init_ssm_state, ssm_decode, ssm_forward, ssm_init

KEY = jax.random.PRNGKey(0)
F32 = dict(compute_dtype=jnp.float32)


def test_rmsnorm_unit_scale():
    p = core.rmsnorm_init(16)
    x = jax.random.normal(KEY, (4, 16)) * 10
    y = core.rmsnorm(p, x)
    rms = jnp.sqrt(jnp.mean(y * y, axis=-1))
    np.testing.assert_allclose(np.asarray(rms), 1.0, rtol=1e-3)


def test_layernorm_nonparametric_is_standardising():
    p = core.layernorm_init(16, elementwise=False)
    assert p == {}
    x = jax.random.normal(KEY, (4, 16)) * 3 + 5
    y = core.layernorm(p, x)
    np.testing.assert_allclose(np.asarray(jnp.mean(y, -1)), 0.0, atol=1e-5)
    np.testing.assert_allclose(np.asarray(jnp.std(y, -1)), 1.0, atol=1e-2)


def test_rope_preserves_norm_and_relative_phase():
    pos = jnp.arange(8)
    cos, sin = rope_cos_sin(pos, 16)
    x = jax.random.normal(KEY, (1, 8, 2, 16))
    y = apply_rope(x, cos, sin)
    np.testing.assert_allclose(np.asarray(jnp.linalg.norm(y, axis=-1)),
                               np.asarray(jnp.linalg.norm(x, axis=-1)),
                               rtol=1e-5)
    # dot(q_i, k_j) after rope depends only on i - j
    q = jnp.ones((1, 8, 1, 16))
    k = jnp.ones((1, 8, 1, 16))
    qr, kr = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
    d02 = jnp.sum(qr[0, 2, 0] * kr[0, 0, 0])
    d13 = jnp.sum(qr[0, 3, 0] * kr[0, 1, 0])
    np.testing.assert_allclose(float(d02), float(d13), rtol=1e-5)


def _write_row(cache, row, t):
    """Decode reads the cache; the caller writes the token's row at t."""
    return jax.tree.map(
        lambda c, r: jax.lax.dynamic_update_slice_in_dim(c, r, t, axis=1),
        cache, row)


@pytest.mark.parametrize("n_kv,window,qk_norm,bias", [
    (4, None, False, False), (2, None, False, True), (1, 8, True, False)])
def test_attention_decode_matches_forward(n_kv, window, qk_norm, bias):
    cfg = AttnCfg(64, 4, n_kv, 16, qkv_bias=bias, qk_norm=qk_norm,
                  window=window)
    p = attn_init(KEY, cfg)
    x = jax.random.normal(KEY, (2, 12, 64))
    full = attn_forward(p, cfg, x, **F32)
    cache = init_kv_cache(2, 16, cfg, jnp.float32)
    y = None
    for t in range(12):
        y, row = attn_decode(p, cfg, x[:, t:t + 1], cache, jnp.int32(t),
                             **F32)
        cache = _write_row(cache, row, t)
    np.testing.assert_allclose(np.asarray(y[:, 0]),
                               np.asarray(full[:, -1]), rtol=2e-4, atol=2e-4)


def test_mla_absorbed_decode_matches_expanded_forward():
    cfg = MLACfg(64, 4, q_lora_rank=32, kv_lora_rank=32, qk_nope_dim=16,
                 qk_rope_dim=8, v_head_dim=16)
    p = mla_init(KEY, cfg)
    x = jax.random.normal(KEY, (2, 10, 64))
    full = mla_forward(p, cfg, x, **F32)
    cache = init_mla_cache(2, 12, cfg, jnp.float32)
    y = None
    for t in range(10):
        # the decode reads a cache stacked over layers, here of one layer
        y, row = mla_decode(p, cfg, x[:, t:t + 1],
                            jax.tree.map(lambda c: c[None], cache),
                            jnp.int32(t), layer=0, **F32)
        cache = _write_row(cache, row, t)
    np.testing.assert_allclose(np.asarray(y[:, 0]),
                               np.asarray(full[:, -1]), rtol=2e-4, atol=2e-4)


_MLA_MESH_SCRIPT = textwrap.dedent(r"""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.roofline import collective_bytes
    from repro.nn import sharding as shlib
    from repro.nn.mla import (MLACfg, init_mla_cache, mla_cache_spec,
                              mla_decode, mla_init)
    from repro.nn.sharding import fit_spec

    mesh = jax.make_mesh((2, 2), ("data", "model"),
                         (jax.sharding.AxisType.Auto,) * 2)
    key = jax.random.PRNGKey(3)
    for window in (None, 20):
        cfg = MLACfg(64, 4, q_lora_rank=32, kv_lora_rank=32, qk_nope_dim=16,
                     qk_rope_dim=8, v_head_dim=16, window=window)
        p = mla_init(key, cfg)
        B, S, R = 4, 64, 2
        x = jax.random.normal(key, (B, 1, 64))
        kv = jax.random.normal(key, (R,) + init_mla_cache(B, S, cfg)["kv"].shape)
        # before, inside and past the second shard's rows (32..63)
        pos = jnp.array([5, 31, 40, 63], jnp.int32)
        f = lambda p, x, kv, pos: mla_decode(p, cfg, x, {"kv": kv}, pos,
                                             layer=1, compute_dtype=jnp.float32)
        want, _ = jax.jit(f)(p, x, kv, pos)
        with shlib.use_mesh(mesh), mesh:
            spec = fit_spec(P(None, *mla_cache_spec(cfg)["kv"]), kv.shape, mesh)
            assert spec == P(None, "data", "model", None), spec
            kv_sh = jax.device_put(kv, NamedSharding(mesh, spec))
            step = jax.jit(f)
            got, _ = step(p, x, kv_sh, pos)
            text = step.lower(p, x, kv_sh, pos).compile().as_text()
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)
        # a device holds 1/4 of the cache: no collective moves its rows
        shard = kv.size * kv.dtype.itemsize // 4
        gathered = collective_bytes(text)["all-gather"]
        assert gathered < shard // 8, (gathered, shard)
    print("MLA-MESH-OK")
""")


def test_mla_decode_on_a_mesh_splits_the_cache_sequence():
    """On a 2x2 (data, model) mesh of forced host devices, with the cache
    laid out by ``mla_cache_spec`` (slots over "data", sequence over
    "model"), the decode kernel runs on each device's shard and the
    shards' parts combine to the single-device result, windowed or not,
    with positions before, inside and past a shard; no collective gathers
    the cache.  Runs in a subprocess: the device count must be set before
    jax initializes."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4").strip()
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")]).rstrip(os.pathsep)
    out = subprocess.run([sys.executable, "-c", _MLA_MESH_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    assert "MLA-MESH-OK" in out.stdout


def test_mla_cache_is_compressed():
    """MLA decode cache bytes/token must be (kv_lora + rope_dim), rounded
    up to whole 128-lane tiles as the TPU stores a row, far below 2·H·Dh —
    the edge-memory win described in DESIGN.md."""
    cfg = MLACfg(64, 16, kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8,
                 v_head_dim=16)
    cache = init_mla_cache(1, 1, cfg)
    per_tok = sum(x.size for x in jax.tree.leaves(cache))
    assert per_tok == 128 * -(-(cfg.kv_lora_rank + cfg.qk_rope_dim) // 128)
    assert per_tok < 2 * cfg.n_heads * cfg.v_head_dim


def test_moe_full_capacity_matches_dense_computation():
    cfg = MoECfg(32, 64, n_experts=4, top_k=2, capacity_factor=64.0)
    p = moe_init(KEY, cfg)
    x = jax.random.normal(KEY, (2, 8, 32))
    y, aux = moe_apply(p, cfg, x, compute_dtype=jnp.float32)
    # dense reference: weighted sum over top-k experts, no capacity
    xt = np.asarray(x).reshape(16, 32)
    logits = xt @ np.asarray(p["router"]["w"])
    probs = jax.nn.softmax(jnp.asarray(logits), -1)
    w, ids = jax.lax.top_k(probs, 2)
    w = np.asarray(w / w.sum(-1, keepdims=True))
    up, gate, down = (np.asarray(p[k], np.float32)
                      for k in ("up", "gate", "down"))
    yr = np.zeros_like(xt)
    for t in range(16):
        for j in range(2):
            e = int(ids[t, j])
            h = xt[t] @ up[e]
            g = xt[t] @ gate[e]
            yr[t] += w[t, j] * ((g / (1 + np.exp(-g))) * h) @ down[e]
    np.testing.assert_allclose(np.asarray(y).reshape(16, 32), yr,
                               rtol=2e-3, atol=2e-3)
    assert float(aux) > 0.0


def test_moe_capacity_drops_are_bounded():
    """With capacity_factor=1.0 at most cap tokens per expert contribute."""
    cfg = MoECfg(16, 32, n_experts=2, top_k=1, capacity_factor=1.0)
    p = moe_init(KEY, cfg)
    x = jax.random.normal(KEY, (1, 16, 16))
    y, _ = moe_apply(p, cfg, x, compute_dtype=jnp.float32)
    assert not bool(jnp.isnan(y).any())


def test_ssm_decode_matches_forward():
    cfg = SSMCfg(32, 64, head_dim=16, n_groups=1, d_state=8, chunk=8)
    p = ssm_init(KEY, cfg)
    x = jax.random.normal(KEY, (2, 12, 32))
    full = ssm_forward(p, cfg, x, **F32)
    st = init_ssm_state(2, cfg, jnp.float32)
    y = None
    for t in range(12):
        y, st = ssm_decode(p, cfg, x[:, t:t + 1], st, **F32)
    np.testing.assert_allclose(np.asarray(y[:, 0]),
                               np.asarray(full[:, -1]), rtol=2e-3, atol=2e-3)


def test_ssm_prefill_state_continues_decode():
    cfg = SSMCfg(32, 64, head_dim=16, n_groups=1, d_state=8, chunk=4)
    p = ssm_init(KEY, cfg)
    x = jax.random.normal(KEY, (1, 9, 32))
    full = ssm_forward(p, cfg, x, **F32)
    _, st = ssm_forward(p, cfg, x[:, :8], return_state=True, **F32)
    st = {"conv": st["conv"], "ssm": st["ssm"]}
    y, _ = ssm_decode(p, cfg, x[:, 8:9], st, **F32)
    np.testing.assert_allclose(np.asarray(y[:, 0]),
                               np.asarray(full[:, -1]), rtol=2e-3, atol=2e-3)


def test_moe_shardmap_matches_gspmd_on_host_mesh():
    """The shard_map expert-parallel path must agree with the global-scatter
    path (exercised on a 1x1 host mesh; the multi-device equivalence is
    covered by the dry-run and a calibration script)."""
    import dataclasses
    from repro.launch.mesh import make_host_mesh
    from repro.nn import sharding as shlib
    cfg = MoECfg(32, 64, n_experts=4, top_k=2, n_shared=1,
                 capacity_factor=8.0)
    cfg_sm = dataclasses.replace(cfg, dispatch="shardmap")
    p = moe_init(KEY, cfg)
    x = jax.random.normal(KEY, (2, 8, 32))
    y_ref, _ = moe_apply(p, cfg, x, compute_dtype=jnp.float32)
    mesh = make_host_mesh()
    with shlib.use_mesh(mesh), mesh:
        y_sm, _ = jax.jit(lambda p, x: moe_apply(p, cfg_sm, x,
                                                 compute_dtype=jnp.float32))(p, x)
    np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_sm),
                               rtol=2e-4, atol=2e-4)
