"""Decision traffic: a closed loop of greedy per-slot allocations for one
cell, one at a time, each sent when the previous one is back on the host
(the paper's Table 3 running time).

The policy and the model zoo are the program's, built from the seed in one
jitted call and kept on the device.  Each request is a slot state, sent
from the host as the radio's measurements would be: a pool of
``pool_states`` consecutive states of one greedy episode, generated from
the seed by the benchmark's own reference environment, cycled, with
``pool_keys`` (coprime to it) reverse-chain keys cycled beside it.  The
pool is made in ``prepare``, whose time is the benchmark's and is left
out of ``setup_s`` and ``compile_s``.

``correct`` compares the ``(b, xi)`` of a sample of the window's decisions,
drawn from the seed with the last one in it, with the reference's greedy
allocation for the same state and key (``decision_numbers``).
"""
from __future__ import annotations

import gc
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import flops, reference
from chipbench.program import t2drl_cfg
from repro.core import EnvState, export_policy, greedy_slot_action, t2drl_init

_FIELDS = {"key": "key", "gamma_idx": "gamma", "lambda_idx": "lam",
           "pos": "pos", "h": "h", "req": "req", "d_in": "d_in", "rho": "rho"}


def pool(c, seed, n_states, n_keys):
    """The request pool of ``seed``: ``n_states`` slot states (host arrays
    with a leading state axis) and ``n_keys`` reverse-chain keys."""
    k_init, k_pool = jax.random.split(reference.seed_key(seed))
    init, make_pool, _ = reference.decision_fns(c)
    envs = make_pool(init(k_init), k_pool, n_states)
    keys = jax.random.split(jax.random.fold_in(k_pool, 2), n_keys)
    return jax.device_get(envs), np.asarray(jax.device_get(keys))


def reference_decisions(c, seed, envs, keys, state_idx, key_idx, dt=jnp.float32):
    """The reference's greedy ``(b, xi)`` for each (state, key) pair, from
    its own weights for ``seed``, and the normaliser of each ``xi``."""
    k_init, _ = jax.random.split(reference.seed_key(seed))
    init, _, many = reference.decision_fns(c, dt)
    st = init(k_init)
    b, xi, xsum = jax.device_get(many(st["actor"], st["models"],
                                      {k: v[state_idx] for k, v in envs.items()},
                                      keys[key_idx]))
    return np.concatenate([b, xi], axis=-1), xsum


# Below this raw sum the compute shares are ill-conditioned: every cached
# user's raw share sits in tanh's saturated tail, where 0.5*(1+tanh(x))
# keeps only a few significant bits in float32, and xi = raw/sum turns
# that round-off into gaps of 1e-3 or more on sound runs.
XI_SUM_MIN = 0.01


def decision_numbers(U, outs, ref, xsum) -> dict:
    """``decision_gap``: the largest difference of the bandwidth shares,
    and of the compute shares where their normaliser is at least
    ``XI_SUM_MIN``; ``xi_left_out`` counts the decisions whose compute
    shares were not compared."""
    gap = np.abs(outs - ref)
    ok = xsum >= XI_SUM_MIN
    return {"decision_gap": float(max(gap[:, :U].max(),
                                      gap[ok, U:].max(initial=0.0))),
            "xi_left_out": int((~ok).sum())}


class Driver:
    def __init__(self, config: dict, traffic: dict, seed: int, span):
        self.c = reference.shapes(config)
        self.cfg = t2drl_cfg(config)
        self.seed, self.span = int(seed), span
        self.n_states = traffic["pool_states"]
        self.n_keys = traffic["pool_keys"]
        self.warm_calls = traffic["warm_calls"]
        self.n_check = traffic["check_sample"]
        if np.gcd(self.n_states, self.n_keys) != 1:
            raise ValueError("pool_states and pool_keys must be coprime")

    def _decide(self, j):
        env, key = self.states[j % self.n_states], self.keys[j % self.n_keys]
        return self.fn(self.policy, self.cfg, env, self.models, key)

    def prepare(self):
        self.envs, self.keys = pool(self.c, self.seed, self.n_states, self.n_keys)

    def setup(self):
        cfg = self.cfg
        k_init, _ = jax.random.split(reference.seed_key(self.seed))
        ts = jax.jit(lambda k: t2drl_init(k, cfg))(k_init)
        self.policy, self.models = export_policy(ts, cfg), ts["models"]
        self.states = [EnvState(**{f: self.envs[r][i] for f, r in _FIELDS.items()})
                       for i in range(self.n_states)]
        self.fn = jax.jit(greedy_slot_action, static_argnames="cfg")
        for j in range(self.warm_calls):
            jax.device_get(self._decide(j))

    def window(self, seconds: float) -> dict:
        span, outs, lat = self.span, [], []
        t0 = time.perf_counter()
        while True:
            t = time.perf_counter()
            with span("bench.dispatch"):
                out = self._decide(len(outs))
            with span("bench.block"):
                b, xi = jax.device_get(out)
            lat.append(time.perf_counter() - t)
            outs.append(np.concatenate([b, xi]))
            if time.perf_counter() - t0 >= seconds:
                break
        window_s = time.perf_counter() - t0
        self.outs = np.stack(outs)
        U = self.c["U"]
        b, xi = self.outs[:, :U], self.outs[:, U:]
        bad = (~np.all(np.isfinite(self.outs), axis=1) | np.any(b < 0, axis=1)
               | np.any(xi < 0, axis=1) | (np.abs(b.sum(1) - 1.0) > 1e-3)
               | (xi.sum(1) > 1.0 + 1e-3))
        lat_ms = 1e3 * np.asarray(lat)
        print(f"[decide] {len(outs)} decisions in {window_s} s; latency ms "
              f"p50 {np.percentile(lat_ms, 50)} p95 {np.percentile(lat_ms, 95)} "
              f"max {lat_ms.max()}", file=sys.stderr, flush=True)
        return {"units": len(outs), "attempted": len(outs),
                "failed": int(bad.sum()), "window_s": window_s}

    def end_to_end(self, res: dict) -> dict:
        return {"decide_ms": 1e3 * res["window_s"] / res["units"]}

    def counters(self, res: dict) -> dict:
        return {"units": res["units"], "flops_per_unit": flops.decision(self.c)}

    def release(self):
        self.policy = self.models = self.fn = None
        gc.collect()

    def sample(self):
        """Indices of the checked decisions: drawn from the seed, the last
        decision of the window among them."""
        n = len(self.outs)
        rng = np.random.default_rng(self.seed)
        idx = rng.choice(n, size=min(n, self.n_check), replace=False)
        return np.unique(np.append(idx, n - 1))

    def check(self) -> dict:
        idx = self.sample()
        ref, xsum = reference_decisions(self.c, self.seed, self.envs, self.keys,
                                        idx % self.n_states, idx % self.n_keys)
        return decision_numbers(self.c["U"], self.outs[idx], ref, xsum)


def control(config: dict, traffic: dict, seed: int) -> dict:
    """The number of the control: the reference in bfloat16, put in the
    program's place, over the first ``check_sample`` requests of ``seed``."""
    c = reference.shapes(config)
    envs, keys = pool(c, seed, traffic["pool_states"], traffic["pool_keys"])
    j = np.arange(traffic["check_sample"])
    si, ki = j % traffic["pool_states"], j % traffic["pool_keys"]
    low, _ = reference_decisions(c, seed, envs, keys, si, ki, dt=jnp.bfloat16)
    ref, xsum = reference_decisions(c, seed, envs, keys, si, ki)
    return decision_numbers(c["U"], low, ref, xsum)
