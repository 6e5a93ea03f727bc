"""Compile for one described TPU v5e chip, with no chip attached: the
Pallas kernels at the widths of the models that use them, and the
paper-scale fused B=8 training program within one chip's 16 GB.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

V5E_HBM_BYTES = 16 * 1024 ** 3


@pytest.fixture(scope="module")
def four_chips():
    """The four described chips of a v5e 2x2 host."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # a described-chip compile can be written to the persistent cache but
    # never read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(four_chips):
    return SingleDeviceSharding(four_chips[0])


def _shape(one_chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_qwen2_0_5b(one_chip):
    from repro.kernels.flash_attention import flash_attention_bhld
    B, H, Hkv, L, D = 1, 14, 2, 512, 64       # qwen2-0.5b: 14 q / 2 kv heads
    q = _shape(one_chip, (B, H, L, D), jnp.bfloat16)
    kv = _shape(one_chip, (B, Hkv, L, D), jnp.bfloat16)
    fn = jax.jit(lambda q, k, v: flash_attention_bhld(q, k, v, causal=True))
    _assert_kernel(fn.lower(q, kv, kv).compile())


def test_ddpm_step_d3pg_action_width(one_chip):
    from repro.core import EnvCfg
    from repro.kernels.ddpm_step import ddpm_step_2d
    A = EnvCfg().action_dim                  # 2U = 20, lane-padded to 128
    x = _shape(one_chip, (64, -(-A // 128) * 128))
    coef = _shape(one_chip, (1, 4))
    fn = jax.jit(lambda x, e, n, c: ddpm_step_2d(x, e, n, c))
    _assert_kernel(fn.lower(x, x, x, coef).compile())


def test_ssd_scan_mamba2_130m(one_chip):
    from repro.configs import get_arch
    from repro.kernels.ssd_scan import ssd_scan_bhlp
    ssm = get_arch("mamba2-130m").make_full().groups[0].cycle[0].ssm
    H, P, G, N = ssm.n_heads, ssm.head_dim, ssm.n_groups, ssm.d_state
    B, L = 1, 2 * ssm.chunk
    fn = jax.jit(lambda *a: ssd_scan_bhlp(*a, chunk=ssm.chunk))
    compiled = fn.lower(
        _shape(one_chip, (B, H, L, P)), _shape(one_chip, (B, H, L, 1)),
        _shape(one_chip, (H,)), _shape(one_chip, (B, G, L, N)),
        _shape(one_chip, (B, G, L, N)), _shape(one_chip, (H,))).compile()
    _assert_kernel(compiled)


def test_mla_decode_deepseek_v2_lite(one_chip):
    from repro.kernels.mla_decode import mla_decode_bhw
    # 64 slots, 2048 positions, rows of 576 values padded to 640 lanes,
    # 26 stacked MoE layers, 16 heads
    B, H, W, S, R = 64, 16, 640, 2048, 26
    fn = jax.jit(lambda *a: mla_decode_bhw(*a, scale=0.1, width=512))
    _assert_kernel(fn.lower(
        _shape(one_chip, (B, H, W), jnp.bfloat16),
        _shape(one_chip, (B, 1, W), jnp.bfloat16),
        _shape(one_chip, (R, B, S, W), jnp.bfloat16),
        _shape(one_chip, (B,), jnp.int32),
        _shape(one_chip, (1,), jnp.int32)).compile())


def test_deepseek_v2_lite_decode_step_reads_the_cache_in_place(one_chip,
                                                               monkeypatch):
    """The gateway's decode step at the cell's size: the kernel reads each
    layer in the donated stacked cache, so no layer (168 MB) or cache
    (4.5 GB) is copied: the step's temporaries stay at MB scale."""
    from repro.configs import get_arch
    from repro.kernels import ops
    from repro.models.lm import lm_decode, lm_init, lm_init_cache
    monkeypatch.setattr(ops, "_interpret", lambda: False)  # the CPU's branch
    cfg = get_arch("deepseek-v2-lite").make_full(held=(0, 16))
    B, S = 64, 2048
    place = lambda t: jax.tree.map(
        lambda a: _shape(one_chip, a.shape, a.dtype), t)
    params = place(jax.eval_shape(
        lambda k: lm_init(k, cfg, dtype=jnp.bfloat16),
        jax.ShapeDtypeStruct((2,), jnp.uint32)))
    cache = place(jax.eval_shape(lambda: lm_init_cache(cfg, B, S)))

    def step(params, tok, pos, cache):
        logits, cache = lm_decode(params, cfg, tok[:, None], cache, pos)
        return jnp.argmax(logits[:, 0], -1), cache

    ids = _shape(one_chip, (B,), jnp.int32)
    compiled = jax.jit(step, donate_argnums=(3,)).lower(
        params, ids, ids, cache).compile()
    _assert_kernel(compiled)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 4 * 1024 ** 3, mem
    assert mem.temp_size_in_bytes < 16 * 1024 ** 2, mem


def test_mla_decode_deepseek_v3_splits_the_cache_over_chips(four_chips,
                                                          monkeypatch):
    """DeepSeek-V3's decode attention on a 2x2 (data, model) mesh of
    described chips, the cache laid out by ``mla_cache_spec``: slots over
    "data", sequence over "model".  Each chip runs the kernel's split form
    on its rows, and no collective gathers the cache (a chip's shard is
    3 x 8 x 2048 rows of 640 bf16, 63 MB)."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.configs import get_arch
    from repro.kernels import ops
    from repro.launch.roofline import collective_bytes
    from repro.nn import sharding as shlib
    from repro.nn.mla import (cache_row_width, mla_cache_spec, mla_decode,
                              mla_init)
    monkeypatch.setattr(ops, "_interpret", lambda: False)  # the CPU's branch
    cfg = get_arch("deepseek-v3-671b").make_full().groups[0].cycle[0].mla
    B, S, R = 16, 4096, 3
    mesh = Mesh(np.array(four_chips).reshape(2, 2), ("data", "model"),
                axis_types=(jax.sharding.AxisType.Auto,) * 2)
    on = lambda spec, a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=NamedSharding(mesh, spec))
    params = jax.tree.map(lambda a: on(P(), a), jax.eval_shape(
        lambda k: mla_init(k, cfg, dtype=jnp.bfloat16),
        jax.ShapeDtypeStruct((2,), jnp.uint32)))
    with shlib.use_mesh(mesh), mesh:
        kv = on(P(None, *mla_cache_spec(cfg)["kv"]), jax.ShapeDtypeStruct(
            (R, B, S, cache_row_width(cfg)), jnp.bfloat16))
        x = on(P("data"), jax.ShapeDtypeStruct((B, 1, cfg.d_model),
                                               jnp.bfloat16))
        pos = on(P("data"), jax.ShapeDtypeStruct((B,), jnp.int32))
        compiled = jax.jit(
            lambda p, x, kv, pos: mla_decode(p, cfg, x, {"kv": kv}, pos,
                                             layer=1)[0]
        ).lower(params, x, kv, pos).compile()
    _assert_kernel(compiled)
    shard = R * (B // 2) * (S // 2) * cache_row_width(cfg) * 2
    moved = collective_bytes(compiled.as_text())
    assert moved["all-gather"] < shard // 64, (moved, shard)


def test_fused_training_program_fits_one_chip(one_chip):
    from repro.core import EnvCfg, T2DRLCfg, t2drl_init_batch
    from repro.core.t2drl import _run_training_jit
    cfg = T2DRLCfg(env=EnvCfg(), warmup=50)  # paper widths, U=M=T=K=10, L=5
    B, E = 8, 3
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    ts = jax.eval_shape(lambda k: t2drl_init_batch(k, cfg, B), key)
    ts = jax.tree.map(lambda s: _shape(one_chip, s.shape, s.dtype), ts)
    compiled = _run_training_jit.lower(
        ts, _shape(one_chip, (2,), jnp.uint32),
        _shape(one_chip, (E,), jnp.int32), cfg=cfg, train=True).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert 0 < total < V5E_HBM_BYTES, total
