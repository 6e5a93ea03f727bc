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
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # a described-chip compile can be written to the persistent cache but
    # never read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


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
