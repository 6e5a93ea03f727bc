"""Faults planted in the program underneath a run, to show that the
comparison deciding ``correct`` catches them (``chipbench/tests`` and
``chipbench/calibrate.py``).  Never used by a benchmark run.

- ``frozen``: every optimizer step returns its parameters and moments
  unchanged (a step that returns its state unchanged).
- ``frozen_ddqn``: the same for the cacher's optimizer alone; the
  allocator's learners step as they should.
- ``half_batch``: every replay minibatch keeps its first half of rows,
  and the losses average over those.
- ``action``: each slot allocation is altered where it is produced (the
  users' bandwidth shares rotated by one).
- ``reward``: each slot reward is altered where the environment produces
  it (scaled by 1.01).
- ``cache``: each frame's cache action is altered where the cacher
  produces it (the next action id).
- ``twin_cache``: each frame's cache vector in the fleet twin is rolled by
  one model where the twin takes it.
- ``frame_cache``: the greedy cache vector of each popularity state is
  altered where the program produces it (its last model's bit flipped),
  for every caller: the twin and whoever reads the vector.
- ``twin_mix``: the twin's arrival mix is uniform over the models, not
  the popularity state's Zipf mix.
- ``twin_service``: the generation delays the twin reads from each slot
  are halved.
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

from chipbench import program  # noqa: F401  (puts the program on the path)

import repro.agents.allocators as allocators  # noqa: E402
import repro.agents.cachers as cachers  # noqa: E402
import repro.core as core  # noqa: E402
import repro.core.d3pg as d3pg  # noqa: E402
import repro.core.ddqn as ddqn  # noqa: E402
import repro.core.t2drl as t2drl  # noqa: E402
import repro.fleet.twin as twin  # noqa: E402


def _frozen(orig):
    def step(grads, state, params, **kw):
        _, _, m = orig(grads, state, params, **kw)
        return params, state, m
    return step


def _half(orig):
    def sample(buf, keys, batch):
        return jax.tree.map(lambda x: x[:, :batch // 2], orig(buf, keys, batch))
    return sample


def _action(orig):
    def amend(*a, **kw):
        b, xi = orig(*a, **kw)
        return jnp.roll(b, 1, axis=-1), xi
    return amend


def _reward(orig):
    def step(*a, **kw):
        env, r, m = orig(*a, **kw)
        return env, r * 1.01, m
    return step


def _cache(orig, n_actions):
    def act(*a, **kw):
        return (orig(*a, **kw) + 1) % n_actions
    return act


def _roll_cache(orig):
    def cache(*a, **kw):
        return jnp.roll(orig(*a, **kw), 1, axis=-1)
    return cache


def _flip_last(orig):
    def cache(*a, **kw):
        rho = orig(*a, **kw)
        return rho.at[..., -1].set(1 - rho[..., -1])
    return cache


def _uniform_mix(orig):
    def mix(gamma_idx, cfg):
        p = orig(gamma_idx, cfg)
        return jnp.full_like(p, 1.0 / p.shape[-1])
    return mix


def _half_service(orig):
    def step(*a, **kw):
        env, r, m = orig(*a, **kw)
        return env, r, {**m, "delay_gt": 0.5 * m["delay_gt"]}
    return step


def _targets(name, n_actions):
    if name == "frozen":
        return [(d3pg, "adam_update_stacked", _frozen),
                (ddqn, "adam_update_stacked", _frozen),
                (d3pg, "adam_update", _frozen), (ddqn, "adam_update", _frozen)]
    if name == "frozen_ddqn":
        return [(ddqn, "adam_update_stacked", _frozen),
                (ddqn, "adam_update", _frozen)]
    if name == "half_batch":
        return [(t2drl, "buffer_sample_stacked", _half)]
    if name == "action":
        return [(allocators, "amend_actions", _action)]
    if name == "reward":
        return [(t2drl, "env_step_slot", _reward)]
    if name == "cache":
        return [(cachers, "ddqn_act_stacked",
                 lambda f: _cache(f, n_actions))]
    if name == "twin_cache":
        return [(twin, "greedy_frame_cache", _roll_cache)]
    if name == "frame_cache":
        return [(mod, "greedy_frame_cache", _flip_last)
                for mod in (t2drl, core, twin)]
    if name == "twin_mix":
        return [(twin, "_zipf_mix", _uniform_mix)]
    if name == "twin_service":
        return [(twin, "env_step_slot", _half_service)]
    raise ValueError(f"unknown fault {name!r}")


FAULTS = ("frozen", "frozen_ddqn", "half_batch", "action", "reward", "cache",
          "twin_cache", "frame_cache", "twin_mix", "twin_service")


@contextlib.contextmanager
def planted(name: str | None, n_actions: int = 2 ** 10):
    """Run the ``with`` block with fault ``name`` in the program (``None``:
    no fault); ``n_actions`` is the cacher's action count.  Jitted programs
    are dropped on entry and exit, so no program compiled with the fault
    outlives the block."""
    if name is None:
        yield
        return
    targets = _targets(name, n_actions)
    saved = [(mod, attr, getattr(mod, attr)) for mod, attr, _ in targets]
    for mod, attr, wrap in targets:
        setattr(mod, attr, wrap(getattr(mod, attr)))
    jax.clear_caches()
    try:
        yield
    finally:
        for mod, attr, orig in saved:
            setattr(mod, attr, orig)
        jax.clear_caches()
