"""Table 3 — algorithm running time per time slot (ms) vs number of users —
plus the vectorized training-core throughput (episodes·envs/sec).

The per-slot section measures the jitted *inference* path of each allocator
on this host (CPU here, RTX A5000 in the paper — absolute numbers differ,
the ordering SCHRS >> T2DRL > DDPG is the reproduced claim).  The
throughput section measures end-to-end multi-cell training of the batched
vector-env core (DESIGN.md §6/§12) for B in {1, 8}: in shared-learner mode
the per-slot optimizer step costs the same at any B, so B=8 must beat B=1's
aggregate throughput by well over 2x even on CPU; the fully independent
multi-seed mode is measured in BOTH execution paths — the fused batched
program (DESIGN.md §13, the default) and the legacy per-learner ``vmap``
reference — so the ISSUE-6 before/after (vmap was *slower* at B=8 than
running B=1 eight times) stays pinned in runtime.json.

``--breakdown`` adds a per-stage attribution for the independent path:
compile time, rollout + replay-write time (a ``train=False`` episode runs
the identical program minus learner updates), and the update chain
(train minus rollout) — the stage the fused rewrite attacks.

Methodology: each configuration is timed over ``reps`` repetitions of one
fully-jitted ``run_training`` call (compile excluded and reported
separately) and the MINIMUM time is used — on small shared boxes the
minimum is the least-contended estimate, and the run-to-run spread is
recorded alongside.  ``run_training`` donates its train state, so every
repetition gets a fresh one (built outside the timed region).

Both sections merge into ``experiments/bench/runtime.json``.  The
throughput section also records the pre-refactor shared-learner B=8
baseline (measured at the PR-4 parent commit on the 2-core reference box
with the same min-of-N protocol) and the speedup against it.

``--smoke`` is the CI mode (2 episodes each): the shared-learner B=8
throughput floor, plus the ISSUE-6 independent-mode gates — fused B=8
must at least match the legacy vmap path (no more vmap slowdown) and hold
B=1's aggregate throughput (>=1.0x with 2+ cores; 0.85x on a single-core
box, where the update chain is compute-bound and batching has nothing to
amortize).  When more than one XLA device is visible (CI forces two via
``XLA_FLAGS=--xla_force_host_platform_device_count=2``) it also runs a
tiny ``run_training_sharded`` call so the shard_map path keeps compiling.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp

import dataclasses

from repro.core import (EnvCfg, GACfg, T2DRLCfg, actor_act, env_reset,
                        ga_allocate, make_actor_schedule, make_models,
                        observe, run_training, run_training_sharded,
                        t2drl_init, t2drl_init_batch)
from repro.obs import ObsCfg
from .common import OUT_DIR, save_json

# Pre-refactor (PR 3, commit ae1b38e) shared-learner B=8 throughput on the
# 2-core reference box: min of 6 repetitions of 4 episodes at the paper
# workload (U=M=T=K=10, warmup=100, tuned lr, L=5) — the baseline the
# agent-protocol episode core is gated against (ISSUE 5 acceptance: >=1.3x).
PRE_REFACTOR_SHARED_B8 = 10.65    # episodes*envs/sec

# CI floor for --smoke: well below the reference-box result so slower CI
# runners pass, far above a structural regression (e.g. losing the scan
# slimming or the sequential-runtime compile path).
SMOKE_FLOOR = 3.0                 # episodes*envs/sec, shared B=8

# The independent-mode smoke gates (ISSUE 6).  Fused B=8 must never lose
# to the legacy vmap path it replaced, and must hold B=1's aggregate
# throughput.  The B8/B1 parity gate presumes >=2 cores (the reference box
# and every GitHub runner); on a single-core box the independent update
# chain is purely compute-bound — the work grows linearly with B and
# batching has nothing left to amortize — so a small concession is
# allowed there instead of skipping the gate entirely.
FUSED_VS_VMAP_FLOOR = 1.0         # fused B=8 vs vmap B=8, always
B8_PARITY_FLOOR = 1.0             # fused B=8 vs B=1 aggregate, >=2 cores
B8_PARITY_FLOOR_1CORE = 0.85      # same gate on a single-core box


def _merge_runtime_json(payload: dict) -> str:
    """Merge ``payload`` into experiments/bench/runtime.json (both the
    per-slot and throughput sections write the same file)."""
    path = os.path.join(OUT_DIR, "runtime.json")
    existing = {}
    if os.path.exists(path):
        try:
            with open(path) as f:
                existing = json.load(f)
        except (OSError, ValueError):
            existing = {}
    existing.update(payload)
    return save_json("runtime.json", existing)


def _time_fn(fn, *args, iters: int = 50) -> float:
    fn(*args)  # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e3  # ms


def run(users=(10, 12, 14, 16, 18), seed: int = 0, verbose=True):
    """Table 3: per-slot inference time of each allocator vs U."""
    out = {"users": list(users), "ms_per_slot": {}}
    key = jax.random.PRNGKey(seed)
    for U in users:
        env = EnvCfg(U=U, M=10)
        models = make_models(key, env)
        state = env_reset(key, env)
        state = state._replace(rho=jnp.ones(env.M))
        s = observe(state, env, models)

        for method in ("t2drl", "ddpg"):
            cfg = T2DRLCfg(env=env, allocator="d3pg" if method == "t2drl"
                           else "ddpg")
            d3 = cfg.d3pg_cfg()
            sched = make_actor_schedule(d3)
            ts = t2drl_init(key, cfg)
            act = jax.jit(lambda p, s, k: actor_act(p, d3, sched, s, k))
            ms = _time_fn(act, ts["d3pg"]["actor"], s, key)
            out["ms_per_slot"][f"{method}_U{U}"] = ms

        ga = GACfg()
        ga_fn = jax.jit(lambda k, st: ga_allocate(k, st, env, models, ga))
        ms = _time_fn(ga_fn, key, state, iters=10)
        out["ms_per_slot"][f"schrs_U{U}"] = ms
        if verbose:
            g = out["ms_per_slot"]
            print(f"U={U:2d}  T2DRL {g[f't2drl_U{U}']:8.3f} ms   "
                  f"DDPG {g[f'ddpg_U{U}']:8.3f} ms   "
                  f"SCHRS {g[f'schrs_U{U}']:9.3f} ms", flush=True)
    _merge_runtime_json(out)
    return out


def _throughput_cfg(policy: str, impl: str = "fused") -> T2DRLCfg:
    """The paper workload the throughput section (and its pre-refactor
    baseline) is pinned to.  ``impl`` selects the independent-mode
    execution path (DESIGN.md §13): "fused" (the default batched program)
    or "vmap" (the legacy reference — the ISSUE-6 "before" numbers)."""
    return T2DRLCfg(env=EnvCfg(U=10, M=10, T=10, K=10), policy=policy,
                    warmup=100, lr_actor=1e-4, lr_critic=1e-3,
                    lr_ddqn=1e-3, L=5, independent_impl=impl)


def _measure(cfg: T2DRLCfg, B: int, episodes: int, reps: int, seed: int = 0,
             train: bool = True):
    """(min_seconds, all_times, compile_seconds) for one compiled
    ``run_training`` call of ``episodes`` episodes at batch ``B``.  A fresh
    train state is built per repetition (run_training donates its input);
    compile time is estimated as first call minus steady-state minimum."""
    key = jax.random.PRNGKey(seed)
    idx = jnp.arange(episodes)
    ts = t2drl_init_batch(key, cfg, B)
    jax.block_until_ready(ts)
    t0 = time.perf_counter()
    jax.block_until_ready(run_training(ts, cfg, key, idx, train=train))
    first_call_s = time.perf_counter() - t0                  # compile + run
    times = []
    for _ in range(reps):
        ts = t2drl_init_batch(key, cfg, B)
        jax.block_until_ready(ts)
        t0 = time.perf_counter()
        _, stats = run_training(ts, cfg, key, idx, train=train)
        jax.block_until_ready(stats)
        times.append(time.perf_counter() - t0)
    return min(times), times, max(0.0, first_call_s - min(times))


def run_throughput(num_envs=(1, 8), episodes: int = 4, seed: int = 0,
                   policies=("shared", "independent"), reps: int = 4,
                   verbose=True):
    """Vector-env training throughput: episodes·envs/sec for B parallel
    edge cells, one fully-jitted ``run_training`` call per repetition
    (compile excluded, min over ``reps``; the paper's U=M=T=K=10 setup)."""
    out = {"episodes": episodes, "reps": reps, "throughput": {},
           "compile_s": {}, "spread_s": {},
           "host": {"cpu_count": os.cpu_count(),
                    "device_count": jax.device_count()}}
    for policy in policies:
        cfg = _throughput_cfg(policy)
        for B in num_envs:
            dt, times, compile_s = _measure(cfg, B, episodes, reps, seed)
            thr = episodes * B / dt
            out["throughput"][f"{policy}_B{B}"] = thr
            out["compile_s"][f"{policy}_B{B}"] = compile_s
            out["spread_s"][f"{policy}_B{B}"] = [round(t, 3) for t in times]
            if verbose:
                print(f"{policy:12s} B={B}: min {dt:6.2f}s for {episodes} "
                      f"eps -> {thr:7.2f} ep*envs/s "
                      f"(compile {compile_s:.1f}s, "
                      f"spread {min(times):.2f}-{max(times):.2f}s)",
                      flush=True)
        if len(num_envs) > 1:
            b_lo, b_hi = min(num_envs), max(num_envs)
            lo = out["throughput"][f"{policy}_B{b_lo}"]
            hi = out["throughput"][f"{policy}_B{b_hi}"]
            out["throughput"][f"{policy}_speedup"] = hi / lo
            if verbose:
                print(f"{policy:12s} aggregate speedup B={b_hi} vs "
                      f"B={b_lo}: {hi / lo:.2f}x", flush=True)
        if policy == "independent":
            # the ISSUE-6 "before": the legacy per-learner vmap program at
            # the largest B (B=1 bypasses to the same single-learner
            # program in both impls, so only the batched point differs)
            b_hi = max(num_envs)
            vcfg = _throughput_cfg("independent", impl="vmap")
            dt, times, compile_s = _measure(vcfg, b_hi, episodes, reps, seed)
            thr = episodes * b_hi / dt
            out["throughput"][f"independent_vmap_B{b_hi}"] = thr
            out["compile_s"][f"independent_vmap_B{b_hi}"] = compile_s
            out["spread_s"][f"independent_vmap_B{b_hi}"] = [
                round(t, 3) for t in times]
            fused = out["throughput"][f"independent_B{b_hi}"]
            out["throughput"][f"independent_fused_vs_vmap_B{b_hi}"] = (
                fused / thr)
            if verbose:
                print(f"{'indep vmap':12s} B={b_hi}: min {dt:6.2f}s for "
                      f"{episodes} eps -> {thr:7.2f} ep*envs/s "
                      f"(compile {compile_s:.1f}s)", flush=True)
                print(f"{'independent':12s} fused vs vmap at B={b_hi}: "
                      f"{fused / thr:.2f}x", flush=True)
    # always (re)write the baseline keys so a rerun with different episode
    # counts can't leave a stale speedup next to fresh throughput numbers;
    # the comparison is only valid under the baseline's exact protocol
    # (4 episodes — warmup amortization changes per-episode throughput)
    out["pre_refactor_shared_B8"] = PRE_REFACTOR_SHARED_B8
    if "shared_B8" in out["throughput"] and episodes == 4:
        out["speedup_vs_pre_refactor"] = (
            out["throughput"]["shared_B8"] / PRE_REFACTOR_SHARED_B8)
        if verbose:
            print(f"shared B=8 vs pre-refactor baseline "
                  f"({PRE_REFACTOR_SHARED_B8:.2f}): "
                  f"{out['speedup_vs_pre_refactor']:.2f}x", flush=True)
    else:
        # different episode count than the baseline protocol: incomparable
        out["speedup_vs_pre_refactor"] = None
    _merge_runtime_json(out)
    save_json("throughput.json", out)   # legacy location, same payload
    return out


def run_breakdown(num_envs=(1, 8), episodes: int = 4, reps: int = 3,
                  seed: int = 0, impls=("fused", "vmap"), verbose=True):
    """Per-stage timing attribution for the independent training path.

    Stages (per configuration, min over ``reps``):

    - ``compile_s``   — first jitted call minus steady state, per program
    - ``rollout_s``   — a full ``train=False`` episode batch: env stepping,
      acting, and replay writes (the stores run unconditionally in the
      episode scan; only learner updates are gated out), i.e. everything
      EXCEPT the update chain
    - ``train_s``     — the full ``train=True`` program
    - ``update_s``    — train minus rollout: the learner-update chain the
      fused batching rewrite attacks

    Writes a ``breakdown`` section into runtime.json keyed
    ``independent[_vmap]_B{n}``."""
    out = {"breakdown": {"episodes": episodes, "reps": reps,
                         "host": {"cpu_count": os.cpu_count(),
                                  "device_count": jax.device_count()}}}
    rows = out["breakdown"]
    for impl in impls:
        cfg = _throughput_cfg("independent", impl=impl)
        tag = "independent" if impl == "fused" else "independent_vmap"
        for B in num_envs:
            if impl == "vmap" and B == min(num_envs) and len(num_envs) > 1:
                continue   # B=1 bypasses to the same program in both impls
            roll, _, c_roll = _measure(cfg, B, episodes, reps, seed,
                                       train=False)
            full, _, c_full = _measure(cfg, B, episodes, reps, seed,
                                       train=True)
            upd = max(0.0, full - roll)
            rows[f"{tag}_B{B}"] = {
                "compile_s": round(c_full, 2),
                "compile_rollout_s": round(c_roll, 2),
                "rollout_s": round(roll, 3),
                "train_s": round(full, 3),
                "update_s": round(upd, 3),
                "update_frac": round(upd / full, 3) if full else None,
            }
            if verbose:
                r = rows[f"{tag}_B{B}"]
                print(f"{tag:18s} B={B}: compile {r['compile_s']:5.1f}s  "
                      f"rollout {r['rollout_s']:6.2f}s  "
                      f"train {r['train_s']:6.2f}s  "
                      f"update {r['update_s']:6.2f}s "
                      f"({100 * r['update_frac']:.0f}% of train)",
                      flush=True)
    _merge_runtime_json(out)
    return out


def run_obs_overhead(episodes: int = 4, reps: int = 3, seed: int = 0,
                     verbose=True) -> dict:
    """Telemetry cost: the fully-tapped in-scan diagnostics program
    (``obs=ObsCfg(enabled=True)``, DESIGN.md §15) vs the identical
    telemetry-off training run, at B=1 on the paper workload.  The ISSUE-8
    acceptance bound is <5% wall-clock overhead.

    Writes an ``obs_overhead`` section into runtime.json."""
    base = _throughput_cfg("independent")            # obs off by default
    tapped = dataclasses.replace(base, obs=ObsCfg(enabled=True))
    t_off, off_times, c_off = _measure(base, 1, episodes, reps, seed)
    t_on, on_times, c_on = _measure(tapped, 1, episodes, reps, seed)
    overhead = t_on / t_off - 1.0
    out = {"obs_overhead": {
        "episodes": episodes, "reps": reps,
        "off_s": round(t_off, 3), "on_s": round(t_on, 3),
        "off_spread_s": [round(t, 3) for t in off_times],
        "on_spread_s": [round(t, 3) for t in on_times],
        "compile_off_s": round(c_off, 2), "compile_on_s": round(c_on, 2),
        "overhead_frac": round(overhead, 4),
        "host": {"cpu_count": os.cpu_count(),
                 "device_count": jax.device_count()}}}
    if verbose:
        print(f"obs overhead: off {t_off:.2f}s, on {t_on:.2f}s -> "
              f"{100 * overhead:+.1f}% (acceptance < +5%)", flush=True)
    _merge_runtime_json(out)
    return out


def run_smoke(floor: float = SMOKE_FLOOR, episodes: int = 2, reps: int = 2,
              verbose=True) -> dict:
    """CI gates, all on the same 2-episode compiled paths the full bench
    measures:

    1. shared-learner B=8 throughput above ``floor`` (absolute);
    2. independent fused B=8 at least ``FUSED_VS_VMAP_FLOOR``x the legacy
       vmap program — the ISSUE-6 regression gate (vmap at B=8 used to run
       ~0.6x of B=1's aggregate; the fused path must never fall back);
    3. independent fused B=8 aggregate throughput at parity with B=1
       (``B8_PARITY_FLOOR``) when the host has 2+ cores; on a 1-core box
       the compute-bound update chain makes parity unattainable and the
       relaxed ``B8_PARITY_FLOOR_1CORE`` applies;
    4. when >1 XLA device is visible (CI forces 2 host devices), one tiny
       ``run_training_sharded`` call so the shard_map placement path keeps
       compiling.

    Writes the results into runtime.json; raises SystemExit on any gate."""
    failures = []
    cfg = _throughput_cfg("shared")
    dt, times, compile_s = _measure(cfg, 8, episodes, reps)
    thr = episodes * 8 / dt
    smoke = {"shared_B8": thr, "compile_s": compile_s,
             "episodes": episodes, "floor": floor,
             "spread_s": [round(t, 3) for t in times],
             "host": {"cpu_count": os.cpu_count(),
                      "device_count": jax.device_count()}}
    if verbose:
        print(f"smoke: shared B=8 {thr:.2f} ep*envs/s "
              f"(floor {floor}, compile {compile_s:.1f}s)", flush=True)
    if thr < floor:
        failures.append(f"shared B=8 {thr:.2f} ep*envs/s below the pinned "
                        f"floor {floor}")

    # ISSUE-6 independent-mode gates: fused vs vmap at B=8, fused B8 vs B1.
    fused = _throughput_cfg("independent")
    b1, _, _ = _measure(fused, 1, episodes, reps)
    b8, _, _ = _measure(fused, 8, episodes, reps)
    v8, _, _ = _measure(_throughput_cfg("independent", impl="vmap"),
                        8, episodes, reps)
    thr_b1, thr_b8, thr_v8 = (episodes / b1, episodes * 8 / b8,
                              episodes * 8 / v8)
    vs_vmap, vs_b1 = thr_b8 / thr_v8, thr_b8 / thr_b1
    parity_floor = (B8_PARITY_FLOOR if (os.cpu_count() or 1) >= 2
                    else B8_PARITY_FLOOR_1CORE)
    smoke.update(independent_B1=thr_b1, independent_B8=thr_b8,
                 independent_vmap_B8=thr_v8,
                 fused_vs_vmap_B8=vs_vmap, fused_B8_vs_B1=vs_b1,
                 parity_floor=parity_floor)
    if verbose:
        print(f"smoke: independent B=1 {thr_b1:.2f}, fused B=8 "
              f"{thr_b8:.2f}, vmap B=8 {thr_v8:.2f} ep*envs/s", flush=True)
        print(f"smoke: fused-vs-vmap {vs_vmap:.2f}x "
              f"(floor {FUSED_VS_VMAP_FLOOR}), B8-vs-B1 {vs_b1:.2f}x "
              f"(floor {parity_floor})", flush=True)
    if vs_vmap < FUSED_VS_VMAP_FLOOR:
        failures.append(f"independent fused B=8 is {vs_vmap:.2f}x the vmap "
                        f"path (floor {FUSED_VS_VMAP_FLOOR})")
    if vs_b1 < parity_floor:
        failures.append(f"independent fused B=8 aggregate is {vs_b1:.2f}x "
                        f"B=1 (floor {parity_floor})")

    # keep the shard_map placement path compiling (a small env keeps the
    # extra compile cheap; correctness vs the fused path is pinned in
    # tests/test_fused.py — this only guards "still builds and runs")
    if jax.device_count() > 1:
        scfg = dataclasses.replace(
            _throughput_cfg("independent"), env=EnvCfg(U=6, M=6, T=6, K=6),
            warmup=10)
        key = jax.random.PRNGKey(0)
        ts = t2drl_init_batch(key, scfg, jax.device_count())
        _, stats = run_training_sharded(ts, scfg, key, jnp.arange(1))
        jax.block_until_ready(stats)
        smoke["sharded_devices"] = jax.device_count()
        if verbose:
            print(f"smoke: shard_map path ran on {jax.device_count()} "
                  f"host devices", flush=True)

    _merge_runtime_json({"smoke": smoke})
    if failures:
        raise SystemExit("throughput smoke FAILED: " + "; ".join(failures))
    return {"smoke": smoke}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--users", type=int, nargs="+",
                    default=[10, 12, 14, 16, 18])
    ap.add_argument("--num-envs", type=int, nargs="+", default=[1, 8])
    ap.add_argument("--episodes", type=int, default=4)
    ap.add_argument("--reps", type=int, default=4,
                    help="timed repetitions per configuration (min is used)")
    ap.add_argument("--skip-slot", action="store_true",
                    help="skip the per-slot Table 3 section")
    ap.add_argument("--skip-throughput", action="store_true",
                    help="skip the vector-env training throughput section")
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: throughput floor + independent-mode "
                         "fused gates only")
    ap.add_argument("--floor", type=float, default=SMOKE_FLOOR,
                    help="episodes*envs/sec floor for --smoke")
    ap.add_argument("--breakdown", action="store_true",
                    help="per-stage timing attribution (compile / rollout+"
                         "replay-write / update) for the independent path")
    ap.add_argument("--obs-overhead", action="store_true",
                    help="telemetry-on vs telemetry-off wall-clock cost of "
                         "the in-scan diagnostics (DESIGN.md §15)")
    args = ap.parse_args()
    if args.smoke:
        run_smoke(floor=args.floor)
        return
    if args.obs_overhead:
        run_obs_overhead(episodes=args.episodes, reps=args.reps)
        return
    if args.breakdown:
        run_breakdown(tuple(args.num_envs), episodes=args.episodes)
        return
    if not args.skip_slot:
        run(tuple(args.users))
    if not args.skip_throughput:
        run_throughput(tuple(args.num_envs), episodes=args.episodes,
                       reps=args.reps)


if __name__ == "__main__":
    main()
