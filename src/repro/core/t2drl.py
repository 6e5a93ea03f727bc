"""T2DRL — the paper's Algorithm 1: outer long-timescale caching (frames) +
inner short-timescale allocation (slots), fully jitted per episode.

The driver is written against the agent protocol (``repro.agents``,
DESIGN.md §12): a per-slot allocator Agent and a per-frame cacher Agent,
selected once by ``allocator``/``cacher``, covering the paper's benchmarks:

  T2DRL             allocator="d3pg",  cacher="ddqn"
  DDPG-based T2DRL  allocator="ddpg",  cacher="ddqn"
  SCHRS             allocator="schrs", cacher="static"
  RCARS             allocator="rcars", cacher="random"

plus the classical cache-hierarchy baselines (DESIGN.md §14):
cacher in {"lru", "lfu", "lru-ghost", "arc"} — stateful non-learned
cachers whose array state machine lives in the ``"cache"`` TrainState
slot and advances once per frame on the frame's request stream
(``Agent.step_frame``), combinable with any allocator.

Vectorized training core (DESIGN.md §6): the per-episode logic lives in
``_episode_core`` (single env, optionally user-masked).  ``run_training``
vmaps it over a leading batch axis of B independent edge cells — each with
its own model zoo, replay buffers, agent parameters, and popularity /
location Markov chains — and scans over episodes, so an entire multi-seed,
multi-episode run is ONE compiled call.  ``run_episode`` remains the public
single-env entry point, and B=1 bypasses vmap entirely, so the legacy path
is reproduced exactly (cell 0 of any batch uses the same keys as a legacy
single-env run with the same seed).

Compiled-path engineering (DESIGN.md §12): scan carries hold only what a
timescale mutates (agent state, env, carried observation — replay buffers
are scan constants within a frame), replay writes are batched once per
frame, epsilon/sigma/LR schedules are precomputed scan inputs, the train
state is donated through ``run_training``.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.api_util import shaped_abstractify

# only repro.agents.base (which has no repro.core dependency) is safe to
# import at module level; the factory dispatch is imported lazily inside
# _agents so either package may be imported first without a cycle
from repro.agents.base import FrameObs, SlotObs, vmap_agent
from repro.obs.profiling import record_compile
from repro.obs.taps import (ObsCfg, broadcast_diag, combine_updates,
                            reduce_update_diag)
from repro.obs.writer import progress_line
from .baselines import GACfg
from .buffers import (buffer_add, buffer_add_batch, buffer_add_many,
                      buffer_add_many_batch, buffer_add_many_stacked,
                      buffer_init, buffer_occupancy, buffer_sample,
                      buffer_sample_batch, buffer_sample_stacked)
from .cache_policies import cache_state_init
from .d3pg import D3PGCfg, d3pg_init
from .ddqn import DDQNCfg, ddqn_init
from .env import (EnvCfg, EnvState, ModelParams, ScenarioSchedule,
                  env_advance_frame, env_reset, env_reset_batch,
                  env_set_cache, env_step_slot, make_models, make_user_masks,
                  masked_mean, observe, schedule_frame_P, schedule_slot_mod)


@dataclasses.dataclass(frozen=True)
class T2DRLCfg:
    """Static configuration of the two-timescale driver (jit-static).

    Attributes
    ----------
    env : EnvCfg
        Environment configuration (scenario transforms replace this).
    allocator : {"d3pg", "ddpg", "schrs", "rcars"}
        Short-timescale per-slot resource allocator.
    cacher : {"ddqn", "static", "random", "lru", "lfu", "lru-ghost", "arc"}
        Long-timescale per-frame caching agent.  The last four are the
        classical cache-hierarchy baselines (DESIGN.md §14): stateful
        non-learned array state machines advanced per frame by the
        request stream via ``Agent.step_frame``.
    policy : {"independent", "shared"}
        Vector-env mode (DESIGN.md §6): B independent learners vs one
        learner fed by all cells.
    independent_impl : {"fused", "vmap"}
        How B > 1 independent learners execute (DESIGN.md §13).
        ``"fused"`` (default) runs all B learners as ONE batched program —
        stacked einsum contractions, a fused optimizer pass, scalar
        (branch-skipping) update gates — and is what population training
        requires.  ``"vmap"`` is the legacy ``jax.vmap`` of the single-env
        episode, kept as the bit-identity reference the fused path is
        pinned against (``tests/test_fused.py``).  B == 1 always runs the
        unbatched legacy program.
    episodes : int
        Default training episode count (paper: 500).
    warmup : int
        Stored slot transitions before D3PG minibatch updates begin.
    eps_start, eps_end, eps_decay_episodes : float, float, int
        DDQN epsilon-greedy schedule over episodes.
    eps_schedule : {"linear", "cosine"}
        Epsilon (and exploration-sigma) decay shape over
        ``eps_decay_episodes`` — "linear" is the paper's schedule;
        "cosine" holds exploration longer before annealing (DESIGN.md §12).
    lr_actor, lr_critic, lr_ddqn : float
        Adam learning rates (paper default 1e-6; see DESIGN.md §8 for the
        tuned CI-scale values).
    lr_schedule : {"const", "linear", "cosine"}
        Actor/critic learning-rate warmdown over ``lr_warmdown_episodes``
        episodes, from the configured rate down to ``lr_end_scale`` times
        it.  "const" (default) reproduces the fixed-rate paper setup
        exactly; schedules are materialized as precomputed per-episode
        scan inputs (no python re-entry).
    lr_warmdown_episodes : int
        Horizon of the LR warmdown (ignored for ``lr_schedule="const"``).
    lr_end_scale : float
        Final LR as a fraction of the initial rate.
    updates_per_slot : int
        Gradient steps per rollout slot once past warmup (default 1 — the
        paper's 1:1 update:data ratio, using the exact legacy per-slot
        key stream).  Values > 1 run an inner ``lax.scan`` of minibatch
        updates per slot, letting long-horizon runs trade rollout steps
        for gradient steps without re-entering Python (DESIGN.md §12).
    L : int
        Diffusion-actor denoising steps (paper Fig. 6a).
    seed : int
        Root PRNG seed for init and episode keys.
    ga : GACfg
        Genetic-algorithm parameters for the SCHRS baseline.
    obs : ObsCfg
        In-scan telemetry switches (DESIGN.md §15).  The default
        (``enabled=False``) keeps every tap site a python-level no-op, so
        the episode cores compile the exact pre-telemetry program; with
        telemetry on, per-update learner diagnostics and replay occupancy
        ride the history dict under ``"diag/..."`` keys.
    """
    env: EnvCfg = EnvCfg()
    allocator: str = "d3pg"     # d3pg | ddpg | schrs | rcars
    cacher: str = "ddqn"        # ddqn | static | random
    policy: str = "independent"  # vector-env mode: independent | shared
    independent_impl: str = "fused"  # B>1 independent learners: fused | vmap
    episodes: int = 500
    warmup: int = 200           # slot transitions before D3PG updates
    eps_start: float = 1.0      # DDQN epsilon-greedy schedule (per episode)
    eps_end: float = 0.05
    eps_decay_episodes: int = 300
    eps_schedule: str = "linear"    # linear | cosine
    lr_actor: float = 1e-6      # paper default; benchmarks also run tuned lr
    lr_critic: float = 1e-6
    lr_ddqn: float = 1e-6
    lr_schedule: str = "const"      # const | linear | cosine
    lr_warmdown_episodes: int = 0
    lr_end_scale: float = 0.1
    updates_per_slot: int = 1
    L: int = 5                  # D3PG denoising steps
    seed: int = 0
    ga: GACfg = GACfg()
    obs: ObsCfg = ObsCfg()      # telemetry taps (DESIGN.md §15)

    def d3pg_cfg(self) -> D3PGCfg:
        return D3PGCfg(state_dim=self.env.state_dim,
                       action_dim=self.env.action_dim, L=self.L,
                       actor_kind="mlp" if self.allocator == "ddpg"
                       else "diffusion",
                       lr_actor=self.lr_actor, lr_critic=self.lr_critic)

    def ddqn_cfg(self) -> DDQNCfg:
        return DDQNCfg(M=self.env.M, J=len(self.env.gammas),
                       lr=self.lr_ddqn)


def _agents(cfg: T2DRLCfg):
    """The (allocator, cacher) Agent pair for ``cfg`` — the single place
    method names are dispatched (DESIGN.md §12)."""
    # lazy: repro.agents.{allocators,cachers} import repro.core submodules,
    # so a module-level import here would cycle when repro.agents loads first
    from repro.agents.allocators import make_allocator
    from repro.agents.cachers import make_cacher
    if cfg.updates_per_slot < 1:
        raise ValueError("updates_per_slot must be >= 1")
    diag = cfg.obs.learner_on
    return (make_allocator(cfg.allocator, cfg.env, cfg.d3pg_cfg(), cfg.ga,
                           diag=diag),
            make_cacher(cfg.cacher, cfg.ddqn_cfg(), cfg.env, diag=diag))


def t2drl_init(key, cfg: T2DRLCfg):
    """Fresh unified train-state pytree (DESIGN.md §12).

    The layout is FIXED regardless of method — ``{"models", "d3pg",
    "ddqn", "ebuf", "fbuf", "cache"}`` — so vector-env squeeze/expand,
    checkpoints (``repro.checkpoint.save_train_state``), and fleet policy
    export never branch on agent kinds; non-learned methods simply never
    read their (still initialized) learner slots.  ``"cache"`` is the
    classical-cacher array state machine (DESIGN.md §14) — keyless init,
    so adding it left every PRNG stream untouched."""
    km, kq, kd = jax.random.split(key, 3)
    env = cfg.env
    models = make_models(km, env)
    d3 = cfg.d3pg_cfg()
    dq = cfg.ddqn_cfg()
    S, A, U, M = env.state_dim, env.action_dim, env.U, env.M
    slot_item = {
        "s": jnp.zeros(S), "a": jnp.zeros(A), "r": jnp.float32(0.0),
        "s1": jnp.zeros(S), "req": jnp.zeros(U, jnp.int32),
        "rho": jnp.zeros(M), "req1": jnp.zeros(U, jnp.int32),
        "rho1": jnp.zeros(M),
    }
    frame_item = {"s": jnp.int32(0), "a": jnp.int32(0),
                  "r": jnp.float32(0.0), "s1": jnp.int32(0)}
    return {
        "models": models,
        "d3pg": d3pg_init(kd, d3),
        "ddqn": ddqn_init(kq, dq),
        "ebuf": buffer_init(d3.buffer, slot_item),
        "fbuf": buffer_init(dq.buffer, frame_item),
        "cache": cache_state_init(M),
    }


def _batch_keys(key, num_envs: int):
    """Per-cell keys with the invariant cell0 == ``key``: cell 0 of any
    batch replays the legacy single-env run for the same seed."""
    if num_envs == 1:
        return key[None]
    return jnp.stack([key] + [jax.random.fold_in(key, i)
                              for i in range(1, num_envs)])


def t2drl_init_batch(key, cfg: T2DRLCfg, num_envs: int, *,
                     share_models: bool = False):
    """Train state for B parallel cells as one pytree.  Models and replay
    buffers always carry a leading (B,) axis; with ``cfg.policy ==
    "independent"`` the agent parameters do too (B fully independent
    seeds), while ``"shared"`` keeps ONE set of agent parameters (cell 0's
    init) learning from all cells' experience.

    Each cell draws its own model zoo (heterogeneous across the batch);
    ``share_models=True`` broadcasts cell 0's zoo to every cell instead
    (pure multi-seed variance studies on one scenario)."""
    if cfg.policy not in ("independent", "shared"):
        raise ValueError(f"unknown policy {cfg.policy!r}; "
                         "expected 'independent' or 'shared'")
    if num_envs < 1:
        raise ValueError("num_envs must be >= 1")
    ts = jax.vmap(lambda k: t2drl_init(k, cfg))(_batch_keys(key, num_envs))
    if share_models:
        ts["models"] = jax.tree.map(
            lambda x: jnp.repeat(x[:1], num_envs, axis=0), ts["models"])
    if cfg.policy == "shared":
        ts["d3pg"] = jax.tree.map(lambda x: x[0], ts["d3pg"])
        ts["ddqn"] = jax.tree.map(lambda x: x[0], ts["ddqn"])
    return ts


# -- exploration / learning-rate schedules (precomputed scan inputs) ----------

def _eps_frac(cfg: T2DRLCfg, episode):
    """Annealing fraction in [0, 1] under ``cfg.eps_schedule`` (validated —
    an unknown name must raise, not silently fall back to linear)."""
    frac = jnp.clip(episode / max(cfg.eps_decay_episodes, 1), 0.0, 1.0)
    if cfg.eps_schedule == "cosine":
        return 0.5 * (1.0 - jnp.cos(jnp.pi * frac))
    if cfg.eps_schedule != "linear":
        raise ValueError(f"unknown eps_schedule {cfg.eps_schedule!r}; "
                         "expected 'linear' or 'cosine'")
    return frac


def episode_epsilon(cfg: T2DRLCfg, episode):
    """DDQN epsilon at ``episode`` (scalar or array of episode indices)."""
    frac = _eps_frac(cfg, episode)
    return cfg.eps_start + (cfg.eps_end - cfg.eps_start) * frac


def episode_sigma(cfg: T2DRLCfg, episode):
    """Exploration-noise schedule: decays from explore_sigma to 0.02 on the
    same schedule as epsilon; zero for the non-learned allocators."""
    episode = jnp.asarray(episode, jnp.float32)
    if cfg.allocator not in ("d3pg", "ddpg"):
        return jnp.zeros_like(episode)
    d3 = cfg.d3pg_cfg()
    frac = _eps_frac(cfg, episode)
    return (d3.explore_sigma * (1.0 - frac) + 0.02 * frac).astype(jnp.float32)


def episode_lr_scale(cfg: T2DRLCfg, episode):
    """Actor/critic LR warmdown factor at ``episode``: 1 -> lr_end_scale
    over ``lr_warmdown_episodes`` (identically 1 for "const")."""
    episode = jnp.asarray(episode, jnp.float32)
    if cfg.lr_schedule == "const":
        return jnp.ones_like(episode)
    if cfg.lr_schedule not in ("linear", "cosine"):
        raise ValueError(f"unknown lr_schedule {cfg.lr_schedule!r}; "
                         "expected 'const', 'linear' or 'cosine'")
    if cfg.lr_warmdown_episodes < 1:
        # silently clamping would collapse the LR to lr_end_scale right
        # after episode 0 — an instant cliff, not a warmdown
        raise ValueError(f"lr_schedule={cfg.lr_schedule!r} requires "
                         "lr_warmdown_episodes >= 1")
    frac = jnp.clip(episode / cfg.lr_warmdown_episodes, 0.0, 1.0)
    if cfg.lr_schedule == "cosine":
        frac = 0.5 * (1.0 - jnp.cos(jnp.pi * frac))
    return 1.0 + (cfg.lr_end_scale - 1.0) * frac


def _update_aux(step, mask):
    """Reserved minibatch auxiliaries for Agent.update (DESIGN.md §12):
    the active-user mask and any schedule-driven learning rates."""
    aux = {}
    if mask is not None:
        aux["mask"] = mask
    if "lr_actor" in step:
        aux["lr_actor"] = step["lr_actor"]
        aux["lr_critic"] = step["lr_critic"]
    return aux


def _slot_updates(alloc, cfg: T2DRLCfg, state, ks, step, aux_mask, sample,
                  tap: bool = False):
    """``updates_per_slot`` sample+update steps of the allocator, shared by
    both episode cores (``sample(key) -> minibatch`` is the only part that
    differs).  N == 1 consumes ``ks[2]``/``ks[3]`` directly — the exact
    legacy per-slot key stream; N > 1 runs an inner ``lax.scan`` over
    ``split(ks[2], N)`` / ``split(ks[3], N)`` (DESIGN.md §12).

    ``tap=True`` (telemetry, DESIGN.md §15) returns ``(state, metrics)`` —
    the update's diagnostics dict, combined over the N inner updates —
    instead of just ``state``."""
    def one(state, kk):
        k_samp, k_upd = kk
        batch = sample(k_samp)
        state, m = alloc.update(state,
                                {**batch, **_update_aux(step, aux_mask)},
                                k_upd)
        return state, (m if tap else None)
    if cfg.updates_per_slot == 1:
        state, m = one(state, (ks[2], ks[3]))
        return (state, m) if tap else state
    state, ms = jax.lax.scan(
        one, state, (jax.random.split(ks[2], cfg.updates_per_slot),
                     jax.random.split(ks[3], cfg.updates_per_slot)))
    return (state, combine_updates(ms)) if tap else state


def _slot_updates_stacked(alloc, cfg: T2DRLCfg, state, ks, step, aux_mask,
                          sample, tap: bool = False):
    """Fused-core counterpart of :func:`_slot_updates`: ``alloc`` is the
    stacked agent, ``ks`` the per-cell key quads ``(B, 4, 2)``, and
    ``sample(keys) -> minibatch`` draws every cell's own minibatch
    (``(B, n, ...)`` leaves) in one fused gather.  Key derivations mirror
    the per-cell ``_slot_updates`` exactly (DESIGN.md §13).  ``tap=True``
    returns ``(state, metrics)`` with per-learner ``(B,)``-leading
    diagnostics."""
    def one(state, kk):
        k_samp, k_upd = kk                  # (B, 2) each
        batch = sample(k_samp)
        state, m = alloc.update(state,
                                {**batch, **_update_aux(step, aux_mask)},
                                k_upd)
        return state, (m if tap else None)
    if cfg.updates_per_slot == 1:
        state, m = one(state, (ks[:, 2], ks[:, 3]))
        return (state, m) if tap else state
    split_n = lambda k: jax.random.split(k, cfg.updates_per_slot)
    state, ms = jax.lax.scan(
        one, state,
        (jnp.moveaxis(jax.vmap(split_n)(ks[:, 2]), 1, 0),
         jnp.moveaxis(jax.vmap(split_n)(ks[:, 3]), 1, 0)))
    return (state, combine_updates(ms)) if tap else state


# -- episode cores ------------------------------------------------------------

def _episode_core(ts, cfg: T2DRLCfg, key, step, *, train: bool = True,
                  mask=None, mods: Optional[ScenarioSchedule] = None):
    """One episode of Algorithm 1 for a single env.

    ``step`` is the per-episode schedule dict (``eps``, ``sigma``, optional
    ``lr_*``); ``mask`` an optional (U,) 0/1 vector of active users
    (heterogeneous-population cells); ``mods`` an optional per-episode
    ScenarioSchedule (unbatched leaves) whose slices are fed to the env at
    every draw (DESIGN.md §9).  The PRNG stream is identical to the
    pre-protocol driver; replay writes are batched once per frame, so a
    slot's minibatch samples from the buffer as of the frame start
    (DESIGN.md §12).  Returns (ts, stats)."""
    env_cfg = cfg.env
    d3 = cfg.d3pg_cfg()
    dq = cfg.ddqn_cfg()
    alloc, cacher = _agents(cfg)
    stateful = cacher.step_frame is not None   # classical cacher (§14);
    # python-static, so stateless methods compile the exact pre-§14 program
    # telemetry taps (DESIGN.md §15): python-static, so with telemetry off
    # (the default) the episode traces the exact pre-telemetry program
    tap_a = train and alloc.diag_zero is not None
    tap_c = train and cacher.diag_zero is not None
    models: ModelParams = ts["models"]
    cap_e = d3.buffer
    k_env, key = jax.random.split(key)
    env = env_reset(k_env, env_cfg, schedule_slot_mod(mods, 0))

    def slot_stats(r, m):
        return {"r": r, "hit": masked_mean(m["cached"], mask),
                "G": masked_mean(m["G"], mask),
                "delay": masked_mean(m["d_tl"], mask),
                "quality": masked_mean(m["quality"], mask),
                "viol": masked_mean(
                    (m["d_tl"] > env_cfg.tau).astype(jnp.float32), mask)}

    def frame_step(carry, xs):
        k_frame, t = xs                # t: frame index into the schedule
        if stateful:
            carry, cstate = carry[:-1], carry[-1]
        if alloc.learns:
            alloc_state, ebuf, env = carry
        else:
            alloc_state, (env,) = ts["d3pg"], carry
        kf = jax.random.split(k_frame, 3)
        env = env_advance_frame(env, env_cfg, schedule_frame_P(mods, t),
                                schedule_slot_mod(mods, t * env_cfg.K))
        gamma_t = env.gamma_idx
        a_int, rho = cacher.act(cstate if stateful else ts["ddqn"],
                                FrameObs(gamma_t, models), kf[0], step)
        env = env_set_cache(env, rho)
        size0 = ebuf["size"] if alloc.learns else None

        def slot_step(carry, xs):
            k_slot, g = xs             # g: global slot index t*K + k
            if alloc.learns:
                alloc_state, env, s = carry
            else:
                alloc_state, (env,), s = ts["d3pg"], carry, None
            ks = jax.random.split(k_slot, 4)
            b, xi = alloc.act(alloc_state, SlotObs(s, env, models, mask),
                              ks[:2], step)
            env1, r, m = env_step_slot(env, env_cfg, models, b, xi, mask,
                                       schedule_slot_mod(mods, g + 1))
            if not alloc.learns:
                out = slot_stats(r, m)
                # a stateful cacher needs the frame's served requests
                # (env.req, pre-advance) replayed at frame end
                return (env1,), ((out, env.req) if stateful else out)
            s1 = observe(env1, env_cfg, models, mask)
            item = {"s": s, "a": jnp.concatenate([b, xi]), "r": r, "s1": s1,
                    "req": env.req, "rho": env.rho, "req1": env1.req,
                    "rho1": env1.rho}
            if train:
                # transitions stored so far = frame-start size + slot count
                # (the write itself is batched at frame end); sampling past
                # warmup therefore sees the buffer as of the frame start
                k_in = g - t * env_cfg.K
                stored = jnp.minimum(size0 + k_in + 1, cap_e)
                pred = (stored > cfg.warmup) & (size0 > 0)
                if tap_a:
                    alloc_state, adiag = jax.lax.cond(
                        pred,
                        lambda st: _slot_updates(
                            alloc, cfg, st, ks, step, mask,
                            lambda k: buffer_sample(ebuf, k, d3.batch),
                            tap=True),
                        lambda st: (st, alloc.diag_zero()), alloc_state)
                    return ((alloc_state, env1, s1),
                            (slot_stats(r, m), item,
                             (adiag, pred.astype(jnp.float32))))
                alloc_state = jax.lax.cond(
                    pred,
                    lambda st: _slot_updates(
                        alloc, cfg, st, ks, step, mask,
                        lambda k: buffer_sample(ebuf, k, d3.batch)),
                    lambda st: st, alloc_state)
            return (alloc_state, env1, s1), (slot_stats(r, m), item)

        g_idx = t * env_cfg.K + jnp.arange(env_cfg.K)
        slot_keys = jax.random.split(kf[1], env_cfg.K)
        reqs = adiag = None
        if alloc.learns:
            s = observe(env, env_cfg, models, mask)
            if tap_a:
                (alloc_state, env, _), (stats, items, adiag) = jax.lax.scan(
                    slot_step, (alloc_state, env, s), (slot_keys, g_idx))
            else:
                (alloc_state, env, _), (stats, items) = jax.lax.scan(
                    slot_step, (alloc_state, env, s), (slot_keys, g_idx))
            ebuf = buffer_add_many(ebuf, items)
            reqs = items["req"]                           # (K, U)
        elif stateful:
            (env,), (stats, reqs) = jax.lax.scan(slot_step, (env,),
                                                 (slot_keys, g_idx))
        else:
            (env,), stats = jax.lax.scan(slot_step, (env,),
                                         (slot_keys, g_idx))
        if stateful:
            cstate = cacher.step_frame(cstate, reqs, models, mask)
        # frame reward (32): average slot reward minus storage penalty
        # (erratum-corrected sign — see DESIGN.md §8)
        storage_viol = (jnp.sum(rho * models.c) > env_cfg.C).astype(jnp.float32)
        r_frame = jnp.mean(stats["r"]) - storage_viol * env_cfg.Xi
        out = {"gamma": gamma_t, "a_int": a_int, "r_frame": r_frame,
               "slot": stats, "storage_viol": storage_viol}
        if tap_a:
            out["adiag"] = adiag               # ((K, ...) metrics, (K,) did)
        carry = ((alloc_state, ebuf, env) if alloc.learns else (env,))
        if stateful:
            carry = carry + (cstate,)
        return carry, out

    frame_xs = (jax.random.split(key, env_cfg.T), jnp.arange(env_cfg.T))
    init = ((ts["d3pg"], ts["ebuf"], env) if alloc.learns else (env,))
    if stateful:
        init = init + (ts["cache"],)
    final, frames = jax.lax.scan(frame_step, init, frame_xs)
    cache_state = final[-1] if stateful else ts["cache"]
    if stateful:
        final = final[:-1]
    if alloc.learns:
        alloc_state, ebuf, env = final
    else:
        (env,) = final
        alloc_state, ebuf = ts["d3pg"], ts["ebuf"]

    # DDQN frame transitions: (gamma_t, a_t, r_t, gamma_{t+1}) for t < T-1
    cacher_state, fbuf = ts["ddqn"], ts["fbuf"]
    cdiag = None
    if cacher.learns and train:
        def add_and_update(carry, t):
            cacher_state, fbuf = carry
            item = {"s": frames["gamma"][t], "a": frames["a_int"][t],
                    "r": frames["r_frame"][t], "s1": frames["gamma"][t + 1]}
            fbuf = buffer_add(fbuf, item)
            pred = fbuf["size"] > dq.batch

            def do_update(cs):
                kb = jax.random.fold_in(key, t)
                batch = buffer_sample(fbuf, kb, dq.batch)
                cs, m = cacher.update(cs, batch, kb)
                return (cs, m) if tap_c else cs
            if tap_c:
                cacher_state, m = jax.lax.cond(
                    pred, do_update,
                    lambda cs: (cs, cacher.diag_zero()), cacher_state)
                return ((cacher_state, fbuf),
                        (m, pred.astype(jnp.float32)))
            cacher_state = jax.lax.cond(pred, do_update,
                                        lambda cs: cs, cacher_state)
            return (cacher_state, fbuf), None
        (cacher_state, fbuf), cdiag = jax.lax.scan(
            add_and_update, (cacher_state, fbuf),
            jnp.arange(env_cfg.T - 1))

    slot = frames["slot"]
    stats = {
        "episode_reward": jnp.sum(slot["r"]),
        "mean_reward": jnp.mean(slot["r"]),
        "hit_ratio": jnp.mean(slot["hit"]),
        "utility": jnp.mean(slot["G"]),
        "delay": jnp.mean(slot["delay"]),
        "quality": jnp.mean(slot["quality"]),
        "deadline_viol": jnp.mean(slot["viol"]),
        "storage_viol": jnp.mean(frames["storage_viol"]),
    }
    if tap_a:
        stats.update(reduce_update_diag(*frames["adiag"], prefix="diag/"))
    if tap_c:
        stats.update(reduce_update_diag(*cdiag, prefix="diag/ddqn_"))
    if train and cfg.obs.replay_on:
        occ = {**buffer_occupancy(ebuf, "ebuf", capacity=d3.buffer),
               **buffer_occupancy(fbuf, "fbuf", capacity=dq.buffer)}
        stats.update({"diag/" + k: v for k, v in occ.items()})
    ts = {"models": models, "d3pg": alloc_state, "ddqn": cacher_state,
          "ebuf": ebuf, "fbuf": fbuf, "cache": cache_state}
    return ts, stats


def _batch_mean(x, masks=None):
    """Per-env mean over the trailing user axis; masks: (B, U) or None."""
    if masks is None:
        return jnp.mean(x, axis=-1)
    return jnp.sum(x * masks, axis=-1) / jnp.maximum(
        jnp.sum(masks, axis=-1), 1.0)


def _episode_core_shared(ts, cfg: T2DRLCfg, keys, step, *,
                         train: bool = True, masks=None,
                         mods: Optional[ScenarioSchedule] = None):
    """One episode in shared-learner vector-env mode: B cells roll out in
    lockstep feeding per-cell replay buffers, and ONE shared policy takes a
    single optimizer step per slot on a fixed-size minibatch pooled evenly
    across the cells' buffers.  Per-step learner cost is independent of B —
    the standard vector-env trade (update:data ratio scales as 1/B).
    ``mods``: optional ScenarioSchedule with per-cell (B,)-leading leaves.
    Returns (ts, stats) with per-cell stats of shape (B,)."""
    env_cfg = cfg.env
    d3 = cfg.d3pg_cfg()
    dq = cfg.ddqn_cfg()
    alloc, cacher = _agents(cfg)
    stateful = cacher.step_frame is not None   # classical cacher (§14)
    # telemetry taps (DESIGN.md §15): the shared learner takes ONE pooled
    # update per slot/frame, so its diagnostics are scalars — broadcast to
    # (B,) at episode end to match the per-cell stats layout
    tap_a = train and alloc.diag_zero is not None
    tap_c = train and cacher.diag_zero is not None
    models: ModelParams = ts["models"]
    cap_e = d3.buffer
    B = keys.shape[0]
    k_env = jax.vmap(lambda k: jax.random.split(k)[0])(keys)
    key = jax.random.split(keys[0])[1]     # driver key (frames, updates)
    env = env_reset_batch(k_env, env_cfg, schedule_slot_mod(mods, 0))
    n_slot = max(1, d3.batch // B)         # per-cell slice of the minibatch
    n_frame = max(1, dq.batch // B)
    row_masks = (None if masks is None
                 else jnp.repeat(masks, n_slot, axis=0))
    act = alloc.batch_act or alloc.act
    cact = cacher.batch_act or cacher.act

    def pool(batch_be):
        """(B, n, ...) per-cell samples -> one (B*n, ...) minibatch."""
        return jax.tree.map(
            lambda x: x.reshape((x.shape[0] * x.shape[1],) + x.shape[2:]),
            batch_be)

    def observe_b(env):
        return jax.vmap(lambda e, m, mk: observe(e, env_cfg, m, mk))(
            env, models, masks)                               # (B, S)

    def slot_stats(r, m):
        return {"r": r, "hit": _batch_mean(m["cached"], masks),
                "G": _batch_mean(m["G"], masks),
                "delay": _batch_mean(m["d_tl"], masks),
                "quality": _batch_mean(m["quality"], masks),
                "viol": _batch_mean(
                    (m["d_tl"] > env_cfg.tau).astype(jnp.float32), masks)}

    def frame_step(carry, xs):
        k_frame, t = xs                # t: frame index into the schedule
        if stateful:
            carry, cstate = carry[:-1], carry[-1]
        if alloc.learns:
            alloc_state, ebuf, env = carry
        else:
            alloc_state, (env,) = ts["d3pg"], carry
        kf = jax.random.split(k_frame, 3)
        env = jax.vmap(lambda e, P, md: env_advance_frame(e, env_cfg, P, md))(
            env, schedule_frame_P(mods, t),
            schedule_slot_mod(mods, t * env_cfg.K))
        gamma_t = env.gamma_idx                               # (B,)
        a_int, rho = cact(cstate if stateful else ts["ddqn"],
                          FrameObs(gamma_t, models), kf[0], step)
        env = jax.vmap(env_set_cache)(env, rho)
        size0 = ebuf["size"] if alloc.learns else None        # (B,)

        def slot_step(carry, xs):
            k_slot, g = xs             # g: global slot index t*K + k
            if alloc.learns:
                alloc_state, env, s = carry
            else:
                alloc_state, (env,), s = ts["d3pg"], carry, None
            ks = jax.random.split(k_slot, 4)
            b, xi = act(alloc_state, SlotObs(s, env, models, masks),
                        ks[:2], step)
            env1, r, m = jax.vmap(
                lambda e, mo, bb, xx, mk, md: env_step_slot(
                    e, env_cfg, mo, bb, xx, mk, md))(
                env, models, b, xi, masks, schedule_slot_mod(mods, g + 1))
            if not alloc.learns:
                out = slot_stats(r, m)
                return (env1,), ((out, env.req) if stateful else out)
            s1 = observe_b(env1)
            item = {"s": s, "a": jnp.concatenate([b, xi], axis=-1), "r": r,
                    "s1": s1, "req": env.req, "rho": env.rho,
                    "req1": env1.req, "rho1": env1.rho}
            if train:
                k_in = g - t * env_cfg.K
                stored = jnp.sum(jnp.minimum(size0 + k_in + 1, cap_e))
                pred = (stored > cfg.warmup) & (jnp.min(size0) > 0)
                sample = lambda k: pool(buffer_sample_batch(
                    ebuf, jax.random.split(k, B), n_slot))
                if tap_a:
                    alloc_state, adiag = jax.lax.cond(
                        pred,
                        lambda st: _slot_updates(alloc, cfg, st, ks, step,
                                                 row_masks, sample, tap=True),
                        lambda st: (st, alloc.diag_zero()), alloc_state)
                    return ((alloc_state, env1, s1),
                            (slot_stats(r, m), item,
                             (adiag, pred.astype(jnp.float32))))
                alloc_state = jax.lax.cond(
                    pred,
                    lambda st: _slot_updates(alloc, cfg, st, ks, step,
                                             row_masks, sample),
                    lambda st: st, alloc_state)
            return (alloc_state, env1, s1), (slot_stats(r, m), item)

        g_idx = t * env_cfg.K + jnp.arange(env_cfg.K)
        slot_keys = jax.random.split(kf[1], env_cfg.K)
        reqs = adiag = None
        if alloc.learns:
            s = observe_b(env)
            if tap_a:
                (alloc_state, env, _), (stats, items, adiag) = jax.lax.scan(
                    slot_step, (alloc_state, env, s), (slot_keys, g_idx))
            else:
                (alloc_state, env, _), (stats, items) = jax.lax.scan(
                    slot_step, (alloc_state, env, s), (slot_keys, g_idx))
            # one batched write per frame per cell: (K, B, ...) -> (B, K, ...)
            ebuf = buffer_add_many_batch(
                ebuf, jax.tree.map(lambda x: jnp.swapaxes(x, 0, 1), items))
            reqs = items["req"]                               # (K, B, U)
        elif stateful:
            (env,), (stats, reqs) = jax.lax.scan(slot_step, (env,),
                                                 (slot_keys, g_idx))
        else:
            (env,), stats = jax.lax.scan(slot_step, (env,),
                                         (slot_keys, g_idx))
        if stateful:
            cstate = jax.vmap(cacher.step_frame)(
                cstate, jnp.swapaxes(reqs, 0, 1), models, masks)
        storage_viol = (jnp.sum(rho * models.c, axis=-1)
                        > env_cfg.C).astype(jnp.float32)      # (B,)
        r_frame = jnp.mean(stats["r"], axis=0) - storage_viol * env_cfg.Xi
        out = {"gamma": gamma_t, "a_int": a_int, "r_frame": r_frame,
               "slot": stats, "storage_viol": storage_viol}
        if tap_a:
            out["adiag"] = adiag               # ((K, ...) metrics, (K,) did)
        carry = ((alloc_state, ebuf, env) if alloc.learns else (env,))
        if stateful:
            carry = carry + (cstate,)
        return carry, out

    frame_xs = (jax.random.split(key, env_cfg.T), jnp.arange(env_cfg.T))
    init = ((ts["d3pg"], ts["ebuf"], env) if alloc.learns else (env,))
    if stateful:
        init = init + (ts["cache"],)
    final, frames = jax.lax.scan(frame_step, init, frame_xs)
    cache_state = final[-1] if stateful else ts["cache"]
    if stateful:
        final = final[:-1]
    if alloc.learns:
        alloc_state, ebuf, env = final
    else:
        (env,) = final
        alloc_state, ebuf = ts["d3pg"], ts["ebuf"]

    cacher_state, fbuf = ts["ddqn"], ts["fbuf"]
    cdiag = None
    if cacher.learns and train:
        def add_and_update(carry, t):
            cacher_state, fbuf = carry
            item = {"s": frames["gamma"][t], "a": frames["a_int"][t],
                    "r": frames["r_frame"][t], "s1": frames["gamma"][t + 1]}
            fbuf = buffer_add_batch(fbuf, item)
            pred = jnp.sum(fbuf["size"]) > dq.batch

            def do_update(cs):
                kb = jax.random.fold_in(key, t)
                batch = pool(buffer_sample_batch(
                    fbuf, jax.random.split(kb, B), n_frame))
                cs, m = cacher.update(cs, batch, kb)
                return (cs, m) if tap_c else cs
            if tap_c:
                cacher_state, m = jax.lax.cond(
                    pred, do_update,
                    lambda cs: (cs, cacher.diag_zero()), cacher_state)
                return ((cacher_state, fbuf),
                        (m, pred.astype(jnp.float32)))
            cacher_state = jax.lax.cond(
                pred, do_update, lambda cs: cs, cacher_state)
            return (cacher_state, fbuf), None
        (cacher_state, fbuf), cdiag = jax.lax.scan(
            add_and_update, (cacher_state, fbuf),
            jnp.arange(env_cfg.T - 1))

    slot = frames["slot"]                  # leaves (T, K, B)
    stats = {
        "episode_reward": jnp.sum(slot["r"], axis=(0, 1)),
        "mean_reward": jnp.mean(slot["r"], axis=(0, 1)),
        "hit_ratio": jnp.mean(slot["hit"], axis=(0, 1)),
        "utility": jnp.mean(slot["G"], axis=(0, 1)),
        "delay": jnp.mean(slot["delay"], axis=(0, 1)),
        "quality": jnp.mean(slot["quality"], axis=(0, 1)),
        "deadline_viol": jnp.mean(slot["viol"], axis=(0, 1)),
        "storage_viol": jnp.mean(frames["storage_viol"], axis=0),
    }
    if tap_a or tap_c:
        # the shared learner takes ONE pooled update per slot/frame, so its
        # diagnostics are cell-agnostic — broadcast to a leading (B,) so
        # the per-cell history layout stays uniform.
        diag = {}
        if tap_a:
            diag.update(reduce_update_diag(*frames["adiag"], prefix="diag/"))
        if tap_c:
            diag.update(reduce_update_diag(*cdiag, prefix="diag/ddqn_"))
        stats.update({k: jnp.broadcast_to(v, (B,) + v.shape)
                      for k, v in diag.items()})
    if train and cfg.obs.replay_on:
        # per-cell buffers: size/fill already carry the (B,) axis
        occ = {**buffer_occupancy(ebuf, "ebuf", capacity=d3.buffer),
               **buffer_occupancy(fbuf, "fbuf", capacity=dq.buffer)}
        stats.update({"diag/" + k: v for k, v in occ.items()})
    ts = {"models": models, "d3pg": alloc_state, "ddqn": cacher_state,
          "ebuf": ebuf, "fbuf": fbuf, "cache": cache_state}
    return ts, stats


def _episode_core_fused(ts, cfg: T2DRLCfg, keys, step, *,
                        train: bool = True, masks=None,
                        mods: Optional[ScenarioSchedule] = None):
    """One episode of B INDEPENDENT learners as a single fused batched
    program (DESIGN.md §13) — the scaling rewrite of
    ``jax.vmap(_episode_core)``.

    Every learner/buffer leaf carries a leading ``(B,)`` axis; the B
    per-cell network applies run as single batched contractions
    (``*_stacked`` paths), the B Adam steps as one fused pass, and the B
    replay gathers/scatters as one indexed op per leaf.  Per-cell PRNG
    derivations are replayed verbatim — every split/fold_in of the
    single-env core is vmapped over the per-cell keys.

    Equivalence contract vs ``jax.vmap(_episode_core)`` (pinned by
    ``tests/test_fused.py``): every stacked primitive/agent closure is
    bit-identical leaf for leaf, and all discrete decisions (caching
    actions, hit ratios, minibatch indices) stay exact at episode level;
    full episodes agree to float32 round-off only — slot-reward
    accumulations at the ULP level, trained parameters at ~1e-5 after
    one episode.  The residue is not a math difference — the minibatch
    indices, update inputs, and single update steps are bitwise equal —
    but XLA CPU codegen being context-dependent: two different
    whole-programs (including the vmap reference vs an isolated replay
    of its own update chain, measured at ~1e-10/update) fuse the reward
    sums and chained update arithmetic differently at ULP level, and
    training's discrete branches (eps-greedy, argmax, feasibility
    amenders) then amplify ULPs across episodes.

    The update gates use SCALAR predicates (``jnp.all`` over cells) inside
    real ``lax.cond``s: in independent mode every cell writes exactly K
    slot items per frame and T-1 frame items per episode in lockstep, so
    ptr/size are equal across cells and the per-cell predicates of the
    vmapped reference (which vmap degrades to compute-both-branches
    ``select``s) always agree — the scalar gate picks the same branch
    while actually skipping the update work pre-warmup.

    ``step`` values may be per-learner ``(B,)`` arrays (population
    training): ``eps``/``sigma``/``lr_actor``/``lr_critic`` as in the
    scalar case, plus ``lr_ddqn`` (cacher learning rate) and ``shape_hit``
    (a beyond-paper reward-shaping coefficient adding ``shape_hit *
    mean(hit)`` to the stored slot rewards and the frame reward — the
    reported stats stay unshaped).  Returns (ts, stats) with per-cell
    stats of shape (B,)."""
    env_cfg = cfg.env
    d3 = cfg.d3pg_cfg()
    dq = cfg.ddqn_cfg()
    alloc0, cacher0 = _agents(cfg)
    alloc = vmap_agent(alloc0, impl="fused")
    cacher = vmap_agent(cacher0, impl="fused")
    stateful = cacher0.step_frame is not None  # classical cacher (§14)
    # telemetry taps (DESIGN.md §15): python-static — off compiles the
    # exact pre-telemetry program.  The fused gates are scalar (jnp.all),
    # so one did flag covers all B learners; the zeros branch stacks the
    # single-learner diag_zero to (B,) to match the stacked update metrics
    tap_a = train and alloc0.diag_zero is not None
    tap_c = train and cacher0.diag_zero is not None
    models: ModelParams = ts["models"]
    cap_e = d3.buffer
    B = keys.shape[0]
    kk = jax.vmap(jax.random.split)(keys)                 # (B, 2, 2)
    k_env, keyd = kk[:, 0], kk[:, 1]    # per-cell env-reset / driver keys
    env = env_reset_batch(k_env, env_cfg, schedule_slot_mod(mods, 0))
    shape_hit = step.get("shape_hit")

    def observe_b(env):
        return jax.vmap(lambda e, m, mk: observe(e, env_cfg, m, mk))(
            env, models, masks)                           # (B, S)

    def slot_stats(r, m):
        return {"r": r, "hit": _batch_mean(m["cached"], masks),
                "G": _batch_mean(m["G"], masks),
                "delay": _batch_mean(m["d_tl"], masks),
                "quality": _batch_mean(m["quality"], masks),
                "viol": _batch_mean(
                    (m["d_tl"] > env_cfg.tau).astype(jnp.float32), masks)}

    def frame_step(carry, xs):
        k_frame, t = xs               # k_frame: (B, 2); t: frame index
        if stateful:
            carry, cstate = carry[:-1], carry[-1]
        if alloc0.learns:
            alloc_state, ebuf, env = carry
        else:
            alloc_state, (env,) = ts["d3pg"], carry
        kf = jax.vmap(lambda k: jax.random.split(k, 3))(k_frame)  # (B, 3, 2)
        env = jax.vmap(lambda e, P, md: env_advance_frame(e, env_cfg, P, md))(
            env, schedule_frame_P(mods, t),
            schedule_slot_mod(mods, t * env_cfg.K))
        gamma_t = env.gamma_idx                           # (B,)
        a_int, rho = cacher.act(cstate if stateful else ts["ddqn"],
                                FrameObs(gamma_t, models), kf[:, 0], step)
        env = jax.vmap(env_set_cache)(env, rho)
        size0 = ebuf["size"] if alloc0.learns else None   # (B,) lockstep

        def slot_step(carry, xs):
            k_slot, g = xs             # k_slot: (B, 2); g: global slot index
            if alloc0.learns:
                alloc_state, env, s = carry
            else:
                alloc_state, (env,), s = ts["d3pg"], carry, None
            ks = jax.vmap(lambda k: jax.random.split(k, 4))(k_slot)
            b, xi = alloc.act(alloc_state, SlotObs(s, env, models, masks),
                              ks[:, :2], step)
            env1, r, m = jax.vmap(
                lambda e, mo, bb, xx, mk, md: env_step_slot(
                    e, env_cfg, mo, bb, xx, mk, md))(
                env, models, b, xi, masks, schedule_slot_mod(mods, g + 1))
            st = slot_stats(r, m)
            if not alloc0.learns:
                return (env1,), ((st, env.req) if stateful else st)
            s1 = observe_b(env1)
            r_store = r if shape_hit is None else r + shape_hit * st["hit"]
            item = {"s": s, "a": jnp.concatenate([b, xi], axis=-1),
                    "r": r_store, "s1": s1, "req": env.req, "rho": env.rho,
                    "req1": env1.req, "rho1": env1.rho}
            if train:
                # transitions stored so far = frame-start size + slot count
                # (writes are batched at frame end); lockstep across cells,
                # so the scalar all() gate agrees with every per-cell
                # predicate of the vmapped reference
                k_in = g - t * env_cfg.K
                stored = jnp.minimum(size0 + k_in + 1, cap_e)
                pred = jnp.all((stored > cfg.warmup) & (size0 > 0))
                sample = lambda k: buffer_sample_stacked(ebuf, k, d3.batch)
                if tap_a:
                    alloc_state, adiag = jax.lax.cond(
                        pred,
                        lambda st_: _slot_updates_stacked(
                            alloc, cfg, st_, ks, step, masks, sample,
                            tap=True),
                        lambda st_: (st_, broadcast_diag(
                            alloc0.diag_zero(), B)), alloc_state)
                    return ((alloc_state, env1, s1),
                            (st, item, (adiag, pred.astype(jnp.float32))))
                alloc_state = jax.lax.cond(
                    pred,
                    lambda st_: _slot_updates_stacked(
                        alloc, cfg, st_, ks, step, masks, sample),
                    lambda st_: st_, alloc_state)
            return (alloc_state, env1, s1), (st, item)

        g_idx = t * env_cfg.K + jnp.arange(env_cfg.K)
        slot_keys = jnp.moveaxis(
            jax.vmap(lambda k: jax.random.split(k, env_cfg.K))(kf[:, 1]),
            1, 0)                                         # (K, B, 2)
        reqs = adiag = None
        if alloc0.learns:
            s = observe_b(env)
            if tap_a:
                (alloc_state, env, _), (stats, items, adiag) = jax.lax.scan(
                    slot_step, (alloc_state, env, s), (slot_keys, g_idx))
            else:
                (alloc_state, env, _), (stats, items) = jax.lax.scan(
                    slot_step, (alloc_state, env, s), (slot_keys, g_idx))
            # one fused write per frame: (K, B, ...) -> (B, K, ...)
            ebuf = buffer_add_many_stacked(
                ebuf, jax.tree.map(lambda x: jnp.swapaxes(x, 0, 1), items))
            reqs = items["req"]                           # (K, B, U)
        elif stateful:
            (env,), (stats, reqs) = jax.lax.scan(slot_step, (env,),
                                                 (slot_keys, g_idx))
        else:
            (env,), stats = jax.lax.scan(slot_step, (env,),
                                         (slot_keys, g_idx))
        if stateful:
            cstate = jax.vmap(cacher0.step_frame)(
                cstate, jnp.swapaxes(reqs, 0, 1), models, masks)
        storage_viol = (jnp.sum(rho * models.c, axis=-1)
                        > env_cfg.C).astype(jnp.float32)  # (B,)
        r_frame = jnp.mean(stats["r"], axis=0) - storage_viol * env_cfg.Xi
        if shape_hit is not None:
            r_frame = r_frame + shape_hit * jnp.mean(stats["hit"], axis=0)
        out = {"gamma": gamma_t, "a_int": a_int, "r_frame": r_frame,
               "slot": stats, "storage_viol": storage_viol}
        if tap_a:
            out["adiag"] = adiag           # ((K, B, ...) metrics, (K,) did)
        carry = ((alloc_state, ebuf, env) if alloc0.learns else (env,))
        if stateful:
            carry = carry + (cstate,)
        return carry, out

    frame_keys = jnp.moveaxis(
        jax.vmap(lambda k: jax.random.split(k, env_cfg.T))(keyd), 1, 0)
    frame_xs = (frame_keys, jnp.arange(env_cfg.T))
    init = ((ts["d3pg"], ts["ebuf"], env) if alloc0.learns else (env,))
    if stateful:
        init = init + (ts["cache"],)
    final, frames = jax.lax.scan(frame_step, init, frame_xs)
    cache_state = final[-1] if stateful else ts["cache"]
    if stateful:
        final = final[:-1]
    if alloc0.learns:
        alloc_state, ebuf, env = final
    else:
        (env,) = final
        alloc_state, ebuf = ts["d3pg"], ts["ebuf"]

    cacher_state, fbuf = ts["ddqn"], ts["fbuf"]
    cdiag = None
    if cacher0.learns and train:
        def add_and_update(carry, t):
            cacher_state, fbuf = carry
            item = {"s": frames["gamma"][t], "a": frames["a_int"][t],
                    "r": frames["r_frame"][t], "s1": frames["gamma"][t + 1]}
            fbuf = buffer_add_batch(fbuf, item)
            pred = jnp.all(fbuf["size"] > dq.batch)

            def do_update(cs):
                kb = jax.vmap(lambda k: jax.random.fold_in(k, t))(keyd)
                batch = buffer_sample_stacked(fbuf, kb, dq.batch)
                if "lr_ddqn" in step:
                    batch = {**batch, "lr": step["lr_ddqn"]}
                cs, m = cacher.update(cs, batch, kb)
                return (cs, m) if tap_c else cs
            if tap_c:
                cacher_state, m = jax.lax.cond(
                    pred, do_update,
                    lambda cs: (cs, broadcast_diag(cacher0.diag_zero(), B)),
                    cacher_state)
                return ((cacher_state, fbuf),
                        (m, pred.astype(jnp.float32)))
            cacher_state = jax.lax.cond(pred, do_update,
                                        lambda cs: cs, cacher_state)
            return (cacher_state, fbuf), None
        (cacher_state, fbuf), cdiag = jax.lax.scan(
            add_and_update, (cacher_state, fbuf),
            jnp.arange(env_cfg.T - 1))

    slot = frames["slot"]                  # leaves (T, K, B)
    stats = {
        "episode_reward": jnp.sum(slot["r"], axis=(0, 1)),
        "mean_reward": jnp.mean(slot["r"], axis=(0, 1)),
        "hit_ratio": jnp.mean(slot["hit"], axis=(0, 1)),
        "utility": jnp.mean(slot["G"], axis=(0, 1)),
        "delay": jnp.mean(slot["delay"], axis=(0, 1)),
        "quality": jnp.mean(slot["quality"], axis=(0, 1)),
        "deadline_viol": jnp.mean(slot["viol"], axis=(0, 1)),
        "storage_viol": jnp.mean(frames["storage_viol"], axis=0),
    }
    if tap_a or tap_c:
        # per-learner metric leaves reduce to (B,) / (B, L); the shared
        # scalar `updates` counts are broadcast so every diag leaf leads
        # with the cell axis
        diag = {}
        if tap_a:
            diag.update(reduce_update_diag(*frames["adiag"], prefix="diag/"))
        if tap_c:
            diag.update(reduce_update_diag(*cdiag, prefix="diag/ddqn_"))
        stats.update({k: (jnp.broadcast_to(v, (B,)) if v.ndim == 0 else v)
                      for k, v in diag.items()})
    if train and cfg.obs.replay_on:
        # stacked buffers: size is already per-cell (B,)
        occ = {**buffer_occupancy(ebuf, "ebuf", capacity=d3.buffer),
               **buffer_occupancy(fbuf, "fbuf", capacity=dq.buffer)}
        stats.update({"diag/" + k: v for k, v in occ.items()})
    ts = {"models": models, "d3pg": alloc_state, "ddqn": cacher_state,
          "ebuf": ebuf, "fbuf": fbuf, "cache": cache_state}
    return ts, stats


def _episode_batch(ts, cfg: T2DRLCfg, keys, step, *, train: bool,
                   masks=None, mods=None):
    """One episode across the batch; keys: (B,) per-cell episode keys.

    ``cfg.policy == "independent"`` runs B independent learners — as ONE
    fused batched program (``independent_impl="fused"``, the default) or
    as the legacy vmap of the single-env episode (``"vmap"``, the
    bit-identity reference).  B=1 bypasses both so the single-env program
    (and its cond-based update gating) is preserved exactly — unless the
    ``step`` dict carries per-cell ``(B,)`` schedule values (population
    training), which only the fused core understands.  ``"shared"``
    delegates to the shared-learner lockstep core.  ``mods``: optional
    ScenarioSchedule with per-cell (B,)-leading leaves."""
    if cfg.policy == "shared":
        return _episode_core_shared(ts, cfg, keys, step, train=train,
                                    masks=masks, mods=mods)
    if cfg.independent_impl not in ("fused", "vmap"):
        raise ValueError(
            f"unknown independent_impl {cfg.independent_impl!r}; "
            "expected 'fused' or 'vmap'")
    B = keys.shape[0]
    pop_step = any(jnp.ndim(v) for v in step.values())
    if pop_step and cfg.independent_impl != "fused":
        raise ValueError("per-cell (population) schedules require "
                         "independent_impl='fused'")
    if cfg.independent_impl == "fused" and (B > 1 or pop_step):
        return _episode_core_fused(ts, cfg, keys, step, train=train,
                                   masks=masks, mods=mods)
    if B == 1:
        mask = None if masks is None else masks[0]
        mods1 = None if mods is None else jax.tree.map(lambda x: x[0], mods)
        ts1, stats = _episode_core(
            jax.tree.map(lambda x: x[0], ts), cfg, keys[0], step,
            train=train, mask=mask, mods=mods1)
        expand = functools.partial(jax.tree.map, lambda x: x[None])
        return expand(ts1), expand(stats)
    return jax.vmap(
        lambda t, k, m, md: _episode_core(t, cfg, k, step, train=train,
                                          mask=m, mods=md))(
        ts, keys, masks, mods)


# -- compiled entry points ----------------------------------------------------
#
# run_episode and run_training dispatch through one counted jit call, so
# every compile reaches the recompile counter (DESIGN.md §15).


def _args_signature(tree):
    leaves, treedef = jax.tree.flatten(tree)
    return (treedef,) + tuple(shaped_abstractify(l) for l in leaves)


def _episode_call(tag, jitted, static_kw, dyn_args):
    """Call ``jitted`` and report a compile (jit cache growth) to the
    ``repro.obs.profiling`` recompile counter (DESIGN.md §15).  The
    counter tag is namespaced per static config so distinct experiment
    configs don't read as retraces of one another; within one config the
    expected program count is two (full chunk + ragged remainder), and the
    counter warns beyond that."""
    statics = tuple(sorted(static_kw.items()))
    full_tag = f"{tag}:{hash(statics) & 0xFFFFFFFF:08x}"
    before = jitted._cache_size()
    out = jitted(*dyn_args, **static_kw)
    if jitted._cache_size() > before:
        record_compile(full_tag, repr(_args_signature(dyn_args)))
    return out


def _run_episode_impl(ts, key, eps, sigma, mods=None, *, cfg: T2DRLCfg,
                      train: bool = True):
    return _episode_core(ts, cfg, key, {"eps": eps, "sigma": sigma},
                         train=train, mods=mods)


_run_episode_jit = functools.partial(
    jax.jit, static_argnames=("cfg", "train"))(_run_episode_impl)


def run_episode(ts, cfg: T2DRLCfg, key, eps, sigma, *, train: bool = True,
                mods: Optional[ScenarioSchedule] = None):
    """One episode of Algorithm 1 (single env).  ``mods``: optional
    unbatched ScenarioSchedule (DESIGN.md §9).  Returns (ts, stats)."""
    return _episode_call("episode", _run_episode_jit,
                         {"cfg": cfg, "train": train},
                         (ts, key, eps, sigma, mods))


def _training_xs(cfg: T2DRLCfg, key, ep_idx, B: int, *, train: bool,
                 pop=None):
    """Precomputed per-episode scan inputs: per-cell episode keys
    ``(E, B, 2)`` plus the eps/sigma (and any LR-warmdown) schedule arrays.
    ``pop`` entries (validated ``(E, B)`` arrays, see ``run_training``)
    override/extend the scalar schedules with per-member ones."""
    alloc, _ = _agents(cfg)
    e = ep_idx.astype(jnp.float32)
    xs = {"keys": jax.vmap(
              lambda ep: _batch_keys(jax.random.fold_in(key, ep), B))(ep_idx),
          "eps": episode_epsilon(cfg, e),
          "sigma": episode_sigma(cfg, e)}
    if train and alloc.learns and cfg.lr_schedule != "const":
        scale = episode_lr_scale(cfg, e)
        xs["lr_actor"] = cfg.lr_actor * scale
        xs["lr_critic"] = cfg.lr_critic * scale
    if pop:
        xs.update(pop)
    return xs


def _scan_episodes(ts, cfg: T2DRLCfg, xs, *, train: bool, masks=None,
                   mods=None):
    """Scan the batched episode over precomputed per-episode inputs."""
    def ep_step(ts, x):
        step = {k: v for k, v in x.items() if k != "keys"}
        return _episode_batch(ts, cfg, x["keys"], step, train=train,
                              masks=masks, mods=mods)

    return jax.lax.scan(ep_step, ts, xs)


def _run_training_impl(ts, key, ep_idx, masks=None, mods=None, pop=None, *,
                       cfg: T2DRLCfg, train: bool = True):
    B = ts["models"].a1.shape[0]
    xs = _training_xs(cfg, key, ep_idx, B, train=train, pop=pop)
    return _scan_episodes(ts, cfg, xs, train=train, masks=masks, mods=mods)


_run_training_jit = functools.partial(
    jax.jit, static_argnames=("cfg", "train"),
    donate_argnums=(0,))(_run_training_impl)


_POP_KEYS = ("eps", "sigma", "lr_actor", "lr_critic", "lr_ddqn", "shape_hit")


def _validate_pop(pop, cfg: T2DRLCfg, B: int, E: int):
    """Normalize a population-schedule dict to ``(E, B)`` float arrays.

    Allowed keys (DESIGN.md §13): ``eps``, ``sigma``, ``lr_actor``,
    ``lr_critic``, ``lr_ddqn``, ``shape_hit``.  Entries may be ``(B,)``
    (constant per member) or ``(E, B)`` (full per-member schedules).
    Population schedules exist only on the fused independent path."""
    if pop is None:
        return None
    unknown = set(pop) - set(_POP_KEYS)
    if unknown:
        raise ValueError(f"unknown population keys {sorted(unknown)}; "
                         f"expected a subset of {_POP_KEYS}")
    if cfg.policy != "independent" or cfg.independent_impl != "fused":
        raise ValueError(
            "population schedules require policy='independent' and "
            "independent_impl='fused' (DESIGN.md §13)")
    out = {}
    for k, v in pop.items():
        v = jnp.asarray(v, jnp.float32)
        if v.ndim == 1:
            v = jnp.broadcast_to(v[None], (E,) + v.shape)
        if v.shape != (E, B):
            raise ValueError(f"population key {k!r} must be (B,)=({B},) or "
                             f"(E, B)=({E}, {B}); got {v.shape}")
        out[k] = v
    # Agent.update consumes lr_actor/lr_critic as a pair — fill a missing
    # partner with the configured constant so the aux dict stays complete
    if ("lr_actor" in out) != ("lr_critic" in out):
        k_have = "lr_actor" if "lr_actor" in out else "lr_critic"
        k_miss = "lr_critic" if k_have == "lr_actor" else "lr_actor"
        const = cfg.lr_critic if k_miss == "lr_critic" else cfg.lr_actor
        out[k_miss] = jnp.full((E, B), const, jnp.float32)
    return out


def run_training(ts, cfg: T2DRLCfg, key, ep_idx, masks=None, mods=None, *,
                 train: bool = True, pop=None):
    """Scan the batched episode over the (absolute) episode indices
    ``ep_idx`` — a whole multi-episode, multi-cell run in one compiled call.
    Epsilon/sigma (and any LR-warmdown) schedules are precomputed arrays
    fed to the scan as inputs.  ``mods``: optional ScenarioSchedule with
    per-cell (B,)-leading leaves, replayed every episode.

    ``pop``: optional population-schedule dict (DESIGN.md §13) giving each
    of the B cells its OWN hyperparameters — keys among ``eps``, ``sigma``,
    ``lr_actor``, ``lr_critic``, ``lr_ddqn``, ``shape_hit``; values
    ``(B,)`` or ``(E, B)`` arrays.  One compiled call then trains B
    population members that differ in those knobs (fused independent
    mode only).

    ``ts`` is DONATED to the computation (its buffers are reused in place);
    use the returned state and do not touch the argument afterwards.
    Returns (ts, history) with history leaves of shape (len(ep_idx), B)."""
    B = ts["models"].a1.shape[0]
    pop = _validate_pop(pop, cfg, B, len(ep_idx))
    return _episode_call("train", _run_training_jit,
                         {"cfg": cfg, "train": train},
                         (ts, key, ep_idx, masks, mods, pop))


def run_training_sharded(ts, cfg: T2DRLCfg, key, ep_idx, masks=None, *,
                         train: bool = True, pop=None, mesh=None):
    """``run_training`` with the B independent cells sharded across devices
    via ``jax.shard_map`` (opt-in, DESIGN.md §13).

    Each device runs the fused episode program on its contiguous slice of
    cells; there is no cross-cell communication (independent learners), so
    the result equals the single-device ``run_training`` — per-cell episode
    keys are derived from GLOBAL cell indices *before* sharding, and each
    shard replays exactly its cells' PRNG streams
    (``tests/test_fused.py`` pins the equivalence under a forced host
    device count).

    ``mesh`` defaults to a 1-D ``("cells",)`` mesh over every visible
    device (``repro.launch.mesh.make_cells_mesh``); on CPU, set
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` before first
    jax use to expose N devices.  B must divide evenly across the mesh.
    ``mods`` schedules are not supported on this path; ``ts`` is not
    donated."""
    from jax.sharding import PartitionSpec as P
    if cfg.policy != "independent" or cfg.independent_impl != "fused":
        raise ValueError("run_training_sharded requires policy="
                         "'independent' and independent_impl='fused'")
    B = ts["models"].a1.shape[0]
    if mesh is None:
        from repro.launch.mesh import make_cells_mesh
        mesh = make_cells_mesh()
    n = int(mesh.devices.size)
    if B % n:
        raise ValueError(f"num_envs={B} must be divisible by the mesh's "
                         f"{n} devices")
    pop = _validate_pop(pop, cfg, B, len(ep_idx))
    xs = _training_xs(cfg, key, ep_idx, B, train=train, pop=pop)
    xs_specs = {k: (P(None, "cells") if jnp.ndim(v) > 1 else P(None))
                for k, v in xs.items()}

    def local(ts_, xs_, masks_):
        return _scan_episodes(ts_, cfg, xs_, train=train, masks=masks_)

    # check_vma=False: the cells are independent (no collectives); the
    # check would only demand that constant scan-carry inits (the frame
    # scan's rho) be cast as varying over "cells"
    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(P("cells"), xs_specs, P("cells")),
                       out_specs=(P("cells"), P(None, "cells")),
                       check_vma=False)
    return jax.jit(fn)(ts, xs, masks)


@functools.partial(jax.jit, static_argnames=("cfg",))
def run_eval(ts, cfg: T2DRLCfg, key, ep_idx, masks=None, mods=None):
    """Greedy evaluation scan: eps = sigma = 0, no updates, ``ts`` is not
    threaded between episodes (and, unlike ``run_training``, not donated).
    Returns history leaves (len(ep_idx), B)."""
    B = ts["models"].a1.shape[0]
    zero = jnp.float32(0.0)
    step = {"eps": zero, "sigma": zero}

    def ep_step(_, ep):
        k_ep = jax.random.fold_in(key, ep)
        _, stats = _episode_batch(ts, cfg, _batch_keys(k_ep, B), step,
                                  train=False, masks=masks, mods=mods)
        return None, stats

    _, stats = jax.lax.scan(ep_step, None, ep_idx)
    return stats


_ENV_AXIS_KEYS = ("models", "ebuf", "fbuf", "cache")  # always batched in
#                         batch mode (cache state is per-cell even when the
#                         learner parameters are shared, DESIGN.md §14)


def _squeeze_env_axis(ts, cfg: T2DRLCfg):
    """Drop the leading B=1 axis, giving a legacy-shaped train state.  In
    shared-policy mode the agent parameters never had an env axis."""
    keys = (_ENV_AXIS_KEYS if cfg.policy == "shared" else ts.keys())
    return {k: (jax.tree.map(lambda x: x[0], v) if k in keys else v)
            for k, v in ts.items()}


def _expand_env_axis(ts, cfg: T2DRLCfg):
    keys = (_ENV_AXIS_KEYS if cfg.policy == "shared" else ts.keys())
    return {k: (jax.tree.map(lambda x: x[None], v) if k in keys else v)
            for k, v in ts.items()}


def _broadcast_mods(mods: Optional[ScenarioSchedule], num_envs: int):
    """Give an unbatched schedule a leading (num_envs,) cell axis (no-op for
    already-batched schedules or ``None``)."""
    if mods is None:
        return None
    if mods.h_scale.ndim == 2:
        if mods.h_scale.shape[0] != num_envs:
            raise ValueError(
                f"per-cell schedule was built for {mods.h_scale.shape[0]} "
                f"cells but num_envs={num_envs}; rebuild with "
                f"build_scenario(..., num_envs={num_envs})")
        return mods
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x, (num_envs,) + x.shape), mods)


def _chunk_summary(stats):
    """Host-side summary of one logical chunk's history for the telemetry
    record: per-key means as python floats, except per-step diffusion
    magnitudes (``*denoise_mag``) which keep their trailing chain axis as
    an L-vector (mean over episodes/cells only)."""
    out = {}
    for k, v in stats.items():
        if k.endswith("denoise_mag") and v.ndim >= 2:
            out[k] = [float(x) for x in
                      jnp.mean(v.reshape(-1, v.shape[-1]), axis=0)]
        else:
            out[k] = float(jnp.mean(v))
    return out


def train_t2drl(cfg: T2DRLCfg, *, episodes: Optional[int] = None,
                num_envs: int = 1, user_counts: Optional[Sequence[int]] = None,
                share_models: bool = False, log_every: int = 0,
                callback=None, mods: Optional[ScenarioSchedule] = None,
                writer=None):
    """Full training run over ``num_envs`` parallel edge cells (multi-seed).

    Parameters
    ----------
    cfg : T2DRLCfg
        Method + environment configuration (jit-static).
    episodes : int, optional
        Episode count (defaults to ``cfg.episodes``).
    num_envs : int
        Number of parallel edge cells B trained through the vectorized core
        (DESIGN.md §6).  ``cfg.policy`` selects independent vs shared
        learners.
    user_counts : sequence of int, optional
        Per-cell active-user counts (len ``num_envs``) — heterogeneous
        populations via masking.
    share_models : bool
        Broadcast cell 0's model zoo to every cell (pure multi-seed runs).
    log_every : int
        Print a progress line every N episodes (chunks the episode scan;
        results are unchanged because keys derive from absolute indices).
    callback : callable, optional
        ``callback(episode, mean_stats)`` after every episode.
    mods : ScenarioSchedule, optional
        Scenario modulation schedule (DESIGN.md §9), e.g. from
        ``repro.scenarios.build_scenario``.  Unbatched leaves are broadcast
        to all cells; per-cell leaves (leading ``(num_envs,)`` axis) give
        heterogeneous scenarios.
    writer : repro.obs.MetricWriter, optional
        Structured telemetry sink (DESIGN.md §15).  When given, a run
        manifest is stamped once and a ``train_chunk`` record (episode
        cursor, wall-clock, per-key chunk statistics) is emitted after
        every logical chunk.  Purely host-side — the compiled programs
        and results are identical with or without a writer.

    Returns
    -------
    (dict, dict)
        Final train-state pytree and history dict of stacked arrays.
        History leaves have shape ``(episodes,)`` for ``num_envs=1``
        (legacy layout) and ``(episodes, num_envs)`` otherwise; likewise
        the train state keeps its leading batch axis only for
        ``num_envs > 1``.
    """
    episodes = episodes or cfg.episodes
    key = jax.random.PRNGKey(cfg.seed)
    k_init, key = jax.random.split(key)
    ts = t2drl_init_batch(k_init, cfg, num_envs, share_models=share_models)
    masks = None
    if user_counts is not None:
        if len(user_counts) != num_envs:
            raise ValueError("user_counts must have one entry per env")
        masks = make_user_masks(cfg.env, user_counts)
    mods = _broadcast_mods(mods, num_envs)
    if writer is not None:
        writer.ensure_manifest(cfg, extra={"episodes": int(episodes),
                                           "num_envs": int(num_envs)})
    chunk = episodes if not (log_every or callback) else (log_every or 1)
    chunks, ep0 = [], 0
    while ep0 < episodes:
        n = min(chunk, episodes - ep0)
        # ragged-tail fix (DESIGN.md §15): a final chunk of n < chunk used
        # to trace a THIRD program per config (silent retrace).  Run the
        # remainder as size-1 calls instead, so a chunked run compiles
        # exactly two episode programs: chunk-sized and size-1.  Episode
        # keys derive from absolute indices, so the split leaves results
        # bit-identical.
        sizes = [n] if n == chunk else [1] * n
        t0 = time.perf_counter()
        parts, e = [], ep0
        for m in sizes:
            ts, part = run_training(ts, cfg, key, jnp.arange(e, e + m),
                                    masks, mods, train=True)
            parts.append(part)
            e += m
        stats = (parts[0] if len(parts) == 1 else
                 {k: jnp.concatenate([p[k] for p in parts])
                  for k in parts[0]})
        chunks.append(stats)
        if writer is not None:
            jax.block_until_ready(stats)
            writer.write("train_chunk", episode=ep0 + n,
                         episodes=int(episodes),
                         wall_s=time.perf_counter() - t0,
                         stats=_chunk_summary(stats))
        if log_every:
            last = {k: float(jnp.mean(v[-1])) for k, v in stats.items()}
            print(progress_line(ep0 + n, last))
        if callback is not None:
            for i in range(n):
                callback(ep0 + i,
                         jax.tree.map(lambda x: jnp.mean(x[i]), stats))
        ep0 += n
    history = {k: jnp.concatenate([c[k] for c in chunks])
               for k in chunks[0]}
    if num_envs == 1:
        ts = _squeeze_env_axis(ts, cfg)
        history = {k: v[:, 0] for k, v in history.items()}
    return ts, history


def eval_t2drl(ts, cfg: T2DRLCfg, *, episodes: int = 10, seed: int = 10_000,
               user_counts: Optional[Sequence[int]] = None,
               mods: Optional[ScenarioSchedule] = None):
    """Greedy evaluation (no exploration, no updates).

    Parameters
    ----------
    ts : dict
        Train-state pytree — single (legacy layout) or batched (leading
        ``(B,)`` axis, as returned by ``train_t2drl(..., num_envs=B)``).
    cfg : T2DRLCfg
        Method + environment configuration (jit-static).
    episodes : int
        Number of greedy evaluation episodes.
    seed : int
        PRNG seed for the evaluation episode keys (disjoint from training
        seeds by default).
    user_counts : sequence of int, optional
        Per-cell active-user counts (one entry per cell in ``ts``).
    mods : ScenarioSchedule, optional
        Scenario modulation schedule; unbatched leaves are broadcast to all
        cells.  Evaluating under a different schedule than training
        measures out-of-scenario generalization.

    Returns
    -------
    dict
        Scalar means over episodes and cells: ``episode_reward``,
        ``mean_reward``, ``hit_ratio``, ``utility``, ``delay``,
        ``quality``, ``deadline_viol``, ``storage_viol``.
    """
    batched = ts["models"].a1.ndim == 2
    if not batched:
        ts = _expand_env_axis(ts, cfg)
    B = ts["models"].a1.shape[0]
    masks = None
    if user_counts is not None:
        if len(user_counts) != B:
            raise ValueError("user_counts must have one entry per env")
        masks = make_user_masks(cfg.env, user_counts)
    stats = run_eval(ts, cfg, jax.random.PRNGKey(seed),
                     jnp.arange(episodes), masks, _broadcast_mods(mods, B))
    return {k: jnp.mean(v) for k, v in stats.items()}


# -- policy deployment (inference-only, DESIGN.md §11/§12) --------------------
#
# ``export_policy`` asks each Agent for its inference-only parameter slice
# (``Agent.export``), so checkpointing (repro.checkpoint.save_train_state)
# and the request-level fleet twin (repro.fleet) never branch on agent
# kinds.  ``greedy_slot_action`` / ``greedy_frame_cache`` are the greedy
# inference entry points every allocator/cacher combination shares,
# delegating to ``Agent.greedy``.


def export_policy(ts, cfg: T2DRLCfg, cell: int = 0):
    """Extract the inference-only policy pytree from a train state.

    Parameters
    ----------
    ts : dict
        Train state — legacy single-env layout or batched (leading ``(B,)``
        axis) as returned by ``train_t2drl(..., num_envs=B)``.
    cfg : T2DRLCfg
        The configuration the state was trained under (selects which agent
        parameters exist).
    cell : int
        For batched *independent*-policy states, which cell's learner to
        export.  Shared-policy states have a single learner; ``cell`` is
        then ignored and the shared parameters are taken as-is.

    Returns
    -------
    dict
        ``{"actor": ..., "ddqn": {"q": ...}}`` with keys present only for
        the learned components of ``cfg`` (empty dict for RCARS/SCHRS);
        classical cachers (DESIGN.md §14) export ``{"cache": {"rho":
        ...}}`` — the frozen resident set the twin serves greedily.
        Model zoos are *not* included — they are environment state, passed
        to the twin separately.
    """
    alloc, cacher = _agents(cfg)
    batched_agents = (ts["models"].a1.ndim == 2 and cfg.policy != "shared")
    take = ((lambda x: jax.tree.map(lambda v: v[cell], x))
            if batched_agents else (lambda x: x))
    pol = {}
    if alloc.learns:
        pol.update(alloc.export(take(ts["d3pg"])))
    if cacher.learns:
        pol.update(cacher.export(take(ts["ddqn"])))
    elif cacher.step_frame is not None:
        # cache state is per-cell even in shared mode (_ENV_AXIS_KEYS),
        # so slice on the models axis, not the agent axis
        take_cell = ((lambda x: jax.tree.map(lambda v: v[cell], x))
                     if ts["models"].a1.ndim == 2 else (lambda x: x))
        pol.update(cacher.export(take_cell(ts["cache"])))
    return pol


def greedy_slot_action(policy, cfg: T2DRLCfg, env: EnvState,
                       models: ModelParams, key, mask=None):
    """Greedy (no exploration noise) per-slot allocation for any allocator.

    Returns one ``(2, U)`` float32 array: row 0 is the bandwidth shares
    ``b``, row 1 the compute shares ``xi``, amended exactly as the
    training-time slot step would under ``sigma = 0``; ``key`` drives the
    diffusion actor's reverse chain (D3PG) or the GA (SCHRS).  One array,
    not the tuple ``(b, xi)``: a jitted call then allocates and copies back
    one device buffer per decision (DESIGN.md §11).  Callers unpack it as
    ``b, xi = greedy_slot_action(...)``."""
    alloc, _ = _agents(cfg)
    s = observe(env, cfg.env, models, mask) if alloc.learns else None
    return jnp.stack(alloc.greedy(policy, SlotObs(s, env, models, mask), key))


def greedy_frame_cache(policy, cfg: T2DRLCfg, models: ModelParams,
                       gamma_idx, key):
    """Greedy (eps = 0) per-frame caching vector rho for any cacher."""
    _, cacher = _agents(cfg)
    return cacher.greedy(policy, FrameObs(gamma_idx, models), key)
