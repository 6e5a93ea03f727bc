"""Smoke run of the system's main path on a TPU, in one process.

  python chip_smoke.py                # one chip: every phase below
  python chip_smoke.py --four-chips   # four chips: sharded training only

One chip runs, in order: train (the single-learner, fused and
shared-learner episode cores at the paper's widths) -> checkpoint save and
load -> fleet twin on ``paper-default`` -> greedy per-slot decisions ->
the gateway ``Engine`` serving qwen2-0.5b at its published widths.  Every
phase prints one line: what ran, its compile seconds (trace + lower +
compile, or a persistent-cache read) and its steady seconds (a repeat of
the same work after ``block_until_ready``).  These are smoke timings, not
benchmark metrics.  ``--four-chips`` runs only ``run_training_sharded``
over a 4-device ``("cells",)`` mesh against ``run_training`` on one chip.

Weights and data come from ``--seed``; nothing is downloaded.  Files are
written only under ``--out``.  The script exits non-zero, printing no
result, when JAX finds no TPU.  Its last line is the JSON object
``{"ok": true, "device": {"platform", "kind", "count"}}``.

The phase functions take their sizes as arguments, so the tests run them
on the CPU at tiny sizes (``tests/test_chip_smoke.py``).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(_ROOT, "src"))

import jax                                                   # noqa: E402
import jax.numpy as jnp                                      # noqa: E402
import numpy as np                                           # noqa: E402

from repro.checkpoint import load_train_state, save_train_state  # noqa: E402
from repro.configs import get_arch                           # noqa: E402
from repro.core import (EnvCfg, T2DRLCfg, env_reset, export_policy,  # noqa: E402
                        greedy_frame_cache, greedy_slot_action,
                        run_training, run_training_sharded,
                        t2drl_init_batch, train_t2drl)
from repro.fleet import FleetCfg, simulate_fleet             # noqa: E402
from repro.launch.compile_cache import use_compile_cache     # noqa: E402
from repro.launch.mesh import make_cells_mesh                # noqa: E402
from repro.models.lm import lm_forward, lm_init              # noqa: E402
from repro.scenarios import build_scenario                   # noqa: E402
from repro.serving import Engine, ServeCfg                   # noqa: E402
from repro.serving.engine import _bucket                     # noqa: E402

# gitignored: the B=8 checkpoint holds every cell's replay buffers
DEFAULT_OUT = os.path.join(_ROOT, ".chip_smoke")
# the gateway's tokens must be among the reference's top 5, not its argmax:
# bf16 round-off may reorder near-tied logits of a random-weight model
_TOP_K = 5
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class CompileClock:
    """Sums JAX's own trace, lowering and backend-compile durations
    (a persistent-cache hit is timed inside the backend-compile event)
    while the ``with`` block runs."""

    def __enter__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def _on_event(self, event, duration, **_):
        if event in _COMPILE_EVENTS:
            self.seconds += duration

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on_event)
        return False


def _timed(fn):
    """Run ``fn`` once cold and once more; return (result of the cold
    call, compile seconds of the cold call, wall seconds of the repeat)."""
    with CompileClock() as clock:
        out = jax.block_until_ready(fn())
    t0 = time.perf_counter()
    jax.block_until_ready(fn())
    return out, clock.seconds, time.perf_counter() - t0


def _report(phase, what, compile_s, steady_s):
    steady = "not measured" if steady_s is None else f"{steady_s:.3f}"
    print(f"[{phase}] {what}: compile_s={compile_s:.3f} steady_s={steady}",
          flush=True)


def _leaves_changed(after, before):
    """True when any leaf of ``after`` differs from ``before`` (``before``
    may carry a leading B=1 axis that ``after`` has squeezed)."""
    return any(not np.array_equal(np.asarray(a),
                                  np.asarray(b).reshape(np.shape(a)))
               for a, b in zip(jax.tree.leaves(after),
                               jax.tree.leaves(before)))


def paper_cfg(*, env: EnvCfg = EnvCfg(), L: int = 5, warmup: int = 50,
              policy: str = "independent", seed: int = 0) -> T2DRLCfg:
    """The paper's T2DRL (D3PG allocator, DDQN cacher) at ``env``.
    ``warmup`` is set below one episode's ``T*K`` slot transitions so the
    learner update chain runs within the first episode."""
    if warmup >= env.T * env.K:
        raise ValueError(f"warmup={warmup} must be below one episode's "
                         f"{env.T * env.K} slot transitions")
    return T2DRLCfg(env=env, L=L, warmup=warmup, policy=policy, seed=seed)


def min_episodes(cfg: T2DRLCfg) -> int:
    """Fewest episodes after which every cell's DDQN frame buffer (``T-1``
    transitions per episode) holds more than one batch, so the cacher's
    update runs: 4 at the paper's T=10 and batch 32."""
    return cfg.ddqn_cfg().batch // (cfg.env.T - 1) + 1


def _check_episodes(cfg: T2DRLCfg, episodes: int):
    if episodes < min_episodes(cfg):
        raise ValueError(f"episodes={episodes} never opens the DDQN update: "
                         f"it needs {min_episodes(cfg)}")


# -- phases -------------------------------------------------------------------

def phase_train(cfg: T2DRLCfg, *, num_envs: int, episodes: int, label: str):
    """``train_t2drl`` for ``episodes`` episodes over ``num_envs`` cells.
    Asserts a finite history, and that both learners' parameters (the
    D3PG actor and the DDQN online network) moved away from the initial
    state ``train_t2drl`` starts from."""
    _check_episodes(cfg, episodes)
    k_init, _ = jax.random.split(jax.random.PRNGKey(cfg.seed))
    ts0 = t2drl_init_batch(k_init, cfg, num_envs)
    (ts, hist), compile_s, steady_s = _timed(
        lambda: train_t2drl(cfg, episodes=episodes, num_envs=num_envs))
    for k, v in hist.items():
        assert np.all(np.isfinite(np.asarray(v))), f"non-finite history {k}"
    assert _leaves_changed(ts["d3pg"]["actor"], ts0["d3pg"]["actor"]), \
        f"{label}: the D3PG actor parameters did not change"
    assert _leaves_changed(ts["ddqn"]["q"], ts0["ddqn"]["q"]), \
        f"{label}: the DDQN online parameters did not change"
    _report("train", f"{label} B={num_envs} episodes={episodes}",
            compile_s, steady_s)
    return ts


def phase_checkpoint(ts, cfg: T2DRLCfg, out_dir: str):
    """``save_train_state`` then ``load_train_state``; asserts the restored
    leaves are bit-identical, with the same dtypes and tree structure."""
    path = os.path.join(out_dir, "train_state.msgpack")
    t0 = time.perf_counter()
    save_train_state(path, ts, meta={"allocator": cfg.allocator,
                                     "cacher": cfg.cacher, "seed": cfg.seed})
    restored, meta = load_train_state(path)
    wall = time.perf_counter() - t0
    assert meta["seed"] == cfg.seed
    assert jax.tree.structure(restored) == jax.tree.structure(ts)
    for a, b in zip(jax.tree.leaves(restored), jax.tree.leaves(ts)):
        b = np.asarray(b)
        assert a.dtype == b.dtype and np.array_equal(a, b)
    _report("checkpoint", f"save+load {path}", 0.0, wall)
    return restored


def phase_twin(ts, cfg: T2DRLCfg, fcfg: FleetCfg, *, seed: int):
    """``simulate_fleet`` of the restored state on ``paper-default`` (one
    episode of ``cfg.env.T`` frames over every cell of ``ts``); asserts
    request conservation."""
    num_cells = ts["models"].a1.shape[0]
    sc = build_scenario("paper-default", cfg.env, num_envs=num_cells)
    assert sc.env == cfg.env
    res, compile_s, steady_s = _timed(
        lambda: simulate_fleet(ts, cfg, fcfg, seed=seed, mods=sc.mods,
                               user_counts=sc.user_counts))
    assert res["requests"] > 0
    assert res["requests"] == res["admitted"] + res["dropped"]
    assert res["hist"].sum() == res["admitted"]
    _report("twin", f"paper-default C={num_cells} T={cfg.env.T} "
            f"requests={res['requests']}", compile_s, steady_s)
    return res


def phase_decide(ts, cfg: T2DRLCfg, *, calls: int, seed: int):
    """``calls`` jitted ``greedy_slot_action`` decisions for cell 0 of
    ``ts``.  Asserts each lies on the bandwidth and compute simplexes, and
    that the first equals the same call run op by op."""
    policy = export_policy(ts, cfg, cell=0)
    models = jax.tree.map(lambda x: jnp.asarray(x)[0], ts["models"])
    key = jax.random.PRNGKey(seed)
    env = env_reset(key, cfg.env)
    env = env._replace(rho=greedy_frame_cache(policy, cfg, models,
                                              env.gamma_idx, key))
    decide = jax.jit(greedy_slot_action, static_argnames="cfg")
    keys = jax.random.split(key, calls)
    with CompileClock() as clock:
        first = jax.block_until_ready(
            decide(policy, cfg, env, models, keys[0]))
    t0 = time.perf_counter()
    for k in keys:
        b, xi = jax.block_until_ready(decide(policy, cfg, env, models, k))
        b, xi = np.asarray(b), np.asarray(xi)
        assert np.all(np.isfinite(b)) and np.all(np.isfinite(xi))
        assert np.all(b >= 0) and abs(b.sum() - 1.0) < 1e-4
        assert np.all(xi >= 0) and xi.sum() <= 1.0 + 1e-4
    steady_s = (time.perf_counter() - t0) / calls
    with jax.disable_jit():
        eager = greedy_slot_action(policy, cfg, env, models, keys[0])
    for a, e in zip(first, eager):
        np.testing.assert_allclose(np.asarray(a), np.asarray(e),
                                   rtol=1e-3, atol=1e-4)
    _report("decide", f"greedy_slot_action x{calls} (steady per call)",
            clock.seconds, steady_s)


def phase_gateway(lm_cfg, *, requests: int, prompt_len: int, new_tokens: int,
                  max_batch: int, max_seq: int, seed: int):
    """``Engine`` serving ``lm_cfg`` with parameters from ``lm_init``.
    Asserts every request returns its prefill token plus ``new_tokens``
    decoded ones, that a repeat gives the same tokens, and that each token
    is among the ``_TOP_K`` of a teacher-forced ``lm_forward`` over the
    prompt and the tokens before it.  ``prompt_len`` is a prefill bucket
    (a power of two, at least 8), so the engine pads no prompt."""
    if _bucket(prompt_len) != prompt_len:
        raise ValueError(f"prompt_len={prompt_len} is not a prefill bucket")
    params = jax.jit(lambda k: lm_init(k, lm_cfg))(jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    prompts = rng.integers(0, lm_cfg.vocab, (requests, prompt_len),
                           dtype=np.int32)
    reqs = [(i, prompts[i], new_tokens) for i in range(requests)]
    engine = Engine(lm_cfg, params, ServeCfg(max_batch=max_batch,
                                             max_seq=max_seq))
    with CompileClock() as clock:
        done, _ = engine.run(reqs)
    t0 = time.perf_counter()
    again, _ = engine.run(reqs)
    steady_s = time.perf_counter() - t0
    assert sorted(done) == list(range(requests))
    for uid, toks in done.items():
        assert len(toks) == new_tokens + 1, (uid, len(toks))
        assert again[uid] == toks, uid
    gen = np.asarray([done[i] for i in range(requests)], np.int32)
    ctx = np.concatenate([prompts, gen[:, :-1]], axis=1)
    logits, _ = jax.jit(lambda p, t: lm_forward(p, lm_cfg, t))(
        params, jnp.asarray(ctx))
    top = np.asarray(jax.lax.top_k(logits[:, prompt_len - 1:], _TOP_K)[1])
    assert all(gen[i, t] in top[i, t] for i in range(requests)
               for t in range(new_tokens + 1)), "engine left the reference"
    _report("gateway", f"{lm_cfg.name} Engine {requests} requests x "
            f"{new_tokens} new tokens", clock.seconds, steady_s)
    return done


def phase_sharded(cfg: T2DRLCfg, *, num_envs: int, episodes: int,
                  n_devices: int, seed: int = 5):
    """``run_training_sharded`` over an ``n_devices`` ``("cells",)`` mesh
    against ``run_training`` on one device, under the fused-vs-vmap
    tolerance contract.  The rollout runs at the default matmul precision
    and must match to round-off with exact hit ratios.  Training runs at
    ``highest`` precision on both sides: at the TPU's default (bf16
    operand) precision the two update programs round in different places,
    and Adam carries that past the contract within two episodes.  Asserts
    every device holds ``num_envs / n_devices`` cells of the sharded
    result, and that the sharded run trained both learners."""
    _check_episodes(cfg, episodes)
    mesh = make_cells_mesh(n_devices)
    key, ep = jax.random.PRNGKey(seed), jnp.arange(episodes)
    per_dev = num_envs // n_devices

    def init():
        return t2drl_init_batch(jax.random.PRNGKey(0), cfg, num_envs)

    def leaves(tree):
        return [np.asarray(x) for x in jax.tree.leaves(tree)]

    for train, rtol, precision in ((False, 1e-6, None),
                                   (True, 1e-4, "highest")):
        with CompileClock() as clock, jax.default_matmul_precision(precision):
            t0 = time.perf_counter()
            ts_s, st_s = jax.block_until_ready(run_training_sharded(
                init(), cfg, key, ep, train=train, mesh=mesh))
            sharded_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            ts_r, st_r = jax.block_until_ready(run_training(
                init(), cfg, key, ep, train=train))
            single_s = time.perf_counter() - t0
        for leaf in jax.tree.leaves(ts_s):
            shards = leaf.addressable_shards
            assert {s.device for s in shards} == set(mesh.devices.flat)
            assert all(s.data.shape[0] == per_dev for s in shards), \
                (leaf.shape, [s.data.shape for s in shards])
        if train:
            ts0 = init()
            assert _leaves_changed(ts_s["d3pg"]["actor"], ts0["d3pg"]["actor"])
            assert _leaves_changed(ts_s["ddqn"]["q"], ts0["ddqn"]["q"])
        pairs = list(zip(leaves((ts_s, st_s)), leaves((ts_r, st_r))))
        # share of the tolerance used by the worst element (passes <= 1)
        used = max(float(np.max(np.abs(a.astype(np.float64) - b)
                                / (1e-4 + rtol * np.abs(b)), initial=0.0))
                   for a, b in pairs)
        print(f"[sharded] worst element uses {used:.3g} of the tolerance",
              flush=True)
        for a, b in pairs:
            np.testing.assert_allclose(a, b, atol=1e-4, rtol=rtol)
        if not train:
            np.testing.assert_array_equal(np.asarray(st_s["hit_ratio"]),
                                          np.asarray(st_r["hit_ratio"]))
        _report("sharded", f"{'train' if train else 'rollout'} B={num_envs} "
                f"episodes={episodes} on {n_devices} devices ({per_dev} "
                f"cells each) vs one, matmul precision "
                f"{precision or 'default'}; first calls with compile: "
                f"sharded {sharded_s:.3f} s, single {single_s:.3f} s",
                clock.seconds, None)


# -- entry point ---------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded-vs-one-chip training "
                         "comparison over four chips")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=DEFAULT_OUT,
                    help="directory for the checkpoint file")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices)}
    print(f"[device] platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']} jax={jax.__version__}", flush=True)
    if dev["platform"] != "tpu":
        print("chip_smoke: no TPU found; this script never falls back to "
              "another backend", file=sys.stderr)
        return 1
    need = 4 if args.four_chips else 1
    if dev["count"] < need:
        print(f"chip_smoke: needs {need} chips, found {dev['count']}",
              file=sys.stderr)
        return 1
    print(f"[device] compile cache: {use_compile_cache(_ROOT)}", flush=True)

    cfg = paper_cfg(seed=args.seed)
    E = min_episodes(cfg)
    if args.four_chips:
        phase_sharded(cfg, num_envs=8, episodes=E, n_devices=4)
    else:
        seed = args.seed
        phase_train(cfg, num_envs=1, episodes=E, label="single-learner")
        ts = phase_train(cfg, num_envs=8, episodes=E,
                         label="fused independent")
        phase_train(paper_cfg(seed=seed, policy="shared"), num_envs=8,
                    episodes=E, label="shared learner")
        restored = phase_checkpoint(ts, cfg, args.out)
        phase_twin(restored, cfg, FleetCfg(), seed=seed)
        phase_decide(restored, cfg, calls=20, seed=seed)
        phase_gateway(get_arch("qwen2-0.5b").make_full(), requests=4,
                      prompt_len=8, new_tokens=4, max_batch=4, max_seq=256,
                      seed=seed)
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
