"""chip_smoke.py's phases at tiny sizes on the CPU: train (all three
episode cores) -> checkpoint -> twin -> decide -> gateway, the sharded
comparison on 4 forced host devices, and the refusal to run without a
TPU."""
import os
import subprocess
import sys
from types import SimpleNamespace

import jax
import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402
from repro.configs import get_arch  # noqa: E402
from repro.core import EnvCfg  # noqa: E402
from repro.fleet import FleetCfg  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402

# T=9 frames give 8 DDQN transitions per episode, so the cacher's update
# (batch 32) opens within a few episodes
ENV = EnvCfg(U=4, M=4, T=9, K=2)


def _cfg(**kw):
    return chip_smoke.paper_cfg(env=ENV, L=2, warmup=2, **kw)


def _episodes():
    return chip_smoke.min_episodes(_cfg())


@pytest.fixture(scope="module")
def fused_ts():
    return chip_smoke.phase_train(_cfg(), num_envs=2, episodes=_episodes(),
                                  label="fused independent")


@pytest.mark.parametrize("policy,num_envs", [("independent", 1),
                                             ("shared", 2)])
def test_phase_train_other_cores(policy, num_envs, capsys):
    chip_smoke.phase_train(_cfg(policy=policy), num_envs=num_envs,
                           episodes=_episodes(), label=policy)
    assert capsys.readouterr().out.startswith("[train] ")


def test_warmup_must_open_within_one_episode():
    with pytest.raises(ValueError, match="warmup"):
        chip_smoke.paper_cfg(env=ENV, warmup=ENV.T * ENV.K)


def test_episodes_must_open_the_ddqn_update():
    assert chip_smoke.min_episodes(chip_smoke.paper_cfg()) == 4
    assert _episodes() == 5
    with pytest.raises(ValueError, match="DDQN"):
        chip_smoke.phase_train(_cfg(), num_envs=1, episodes=_episodes() - 1,
                               label="too short")


def test_compile_cache_placement(monkeypatch, tmp_path):
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert use_compile_cache(ROOT) == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == was
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert use_compile_cache(ROOT) == os.path.join(
            os.path.abspath(ROOT), ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == os.path.join(
            os.path.abspath(ROOT), ".jax_cache")
        with pytest.raises(ValueError, match="src/repro"):
            use_compile_cache(str(tmp_path))
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_phases_checkpoint_twin_decide(fused_ts, tmp_path, capsys):
    cfg = _cfg()
    restored = chip_smoke.phase_checkpoint(fused_ts, cfg, str(tmp_path))
    assert (tmp_path / "train_state.msgpack").exists()
    res = chip_smoke.phase_twin(restored, cfg,
                                FleetCfg(ticks_per_slot=5,
                                         arrivals_per_user_s=0.5), seed=3)
    assert res["requests"] > 0
    chip_smoke.phase_decide(restored, cfg, calls=3, seed=0)
    lines = capsys.readouterr().out.splitlines()
    assert [ln.split("]")[0] for ln in lines] == [
        "[checkpoint", "[twin", "[decide"]
    assert all("compile_s=" in ln and "steady_s=" in ln for ln in lines)


def test_phase_gateway_smoke_lm():
    done = chip_smoke.phase_gateway(
        get_arch("qwen2-0.5b").make_smoke(), requests=3, prompt_len=8,
        new_tokens=3, max_batch=2, max_seq=64, seed=0)
    assert sorted(done) == [0, 1, 2]
    # a prompt shorter than its prefill bucket decodes from its own length
    done = chip_smoke.phase_gateway(
        get_arch("qwen2-0.5b").make_smoke(), requests=2, prompt_len=5,
        new_tokens=2, max_batch=1, max_seq=64, seed=0)
    assert sorted(done) == [0, 1]


def test_main_refuses_without_tpu(monkeypatch, capsys):
    # whatever this host has, main() sees only a CPU device
    cpu = SimpleNamespace(platform="cpu", device_kind="cpu")
    monkeypatch.setattr(chip_smoke.jax, "devices", lambda: [cpu])
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "no TPU" in out.err


_FOUR = """
import sys
sys.path.insert(0, {root!r})
import jax
import chip_smoke
from repro.core import EnvCfg
assert jax.device_count() == 4, jax.devices()
cfg = chip_smoke.paper_cfg(env=EnvCfg(U=4, M=4, T=9, K=2), L=2, warmup=2)
chip_smoke.phase_sharded(cfg, num_envs=8,
                         episodes=chip_smoke.min_episodes(cfg), n_devices=4)
print("FOUR-OK")
"""


def test_phase_sharded_on_four_host_devices():
    """The --four-chips comparison on 4 forced CPU devices.  Runs in a
    subprocess: the device count must be set before jax initializes."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4").strip()
    out = subprocess.run(
        [sys.executable, "-c", _FOUR.format(root=os.path.abspath(ROOT))],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    assert "FOUR-OK" in out.stdout
    assert ("[sharded] rollout B=8 episodes=5 on 4 devices (2 cells each) "
            "vs one, matmul precision default" in out.stdout)
    assert ("[sharded] train B=8 episodes=5 on 4 devices (2 cells each) "
            "vs one, matmul precision highest" in out.stdout)
