"""Fleet twin traffic: back-to-back calls of the request-level queueing
twin (``repro.fleet.simulate_fleet``), each one episode horizon of Poisson
request traffic in ``cells`` edge cells served by one greedy policy.

Set-up follows an operator's path: the train state is built from the seed
in one jitted call, saved with ``save_train_state``, restored with
``load_train_state`` and put on the device; the policy is exported once.
Warm-up calls (with seeds of their own) compile every program the window
runs.  Each call of the window has its own traffic seed, drawn from the
run's seed and the call's index; ``twin_rate`` is the simulated requests
of the calls completed in the window over the window's length.

``correct`` replays the last call of the window and ``check_calls`` calls
drawn from the seed in the plain reference (``chipbench.reference_twin``)
and compares their summaries; it also checks the program's greedy cache
vector of each popularity state against the reference's DDQN.  A call
whose summary holds a non-finite latency sum, mean or quantile, or a
negative count, is ``failed``.
"""
from __future__ import annotations

import gc
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import reference, reference_twin
from chipbench.program import t2drl_cfg
import repro.core as core
from repro.checkpoint import load_train_state, save_train_state
from repro.core import export_policy, t2drl_init
from repro.fleet import FleetCfg, simulate_fleet

# what the check keeps of each call's summary
_KEEP = ("requests", "admitted", "dropped", "truncated", "slo_viol_rate",
         "deadline_miss_rate", "mean_latency_s", "mean_wait_s", "hist",
         "backlog_curve")
_FINITE = ("mean_latency_s", "mean_wait_s", "p50_s", "p95_s", "p99_s")
_COUNTS = ("requests", "admitted", "dropped", "truncated")


def call_seed(seed: int, j: int, warm: bool = False) -> int:
    """The traffic seed of call ``j`` of a run with ``seed``: a whole
    number below 2^31, as ``simulate_fleet`` takes it."""
    seed = int(seed)
    ss = np.random.SeedSequence([seed % 2**32, seed // 2**32, int(warm), j])
    return int(ss.generate_state(1)[0] >> 1)


def is_failed(summary: dict) -> bool:
    return (not all(np.isfinite(summary[k]) for k in _FINITE)
            or any(summary[k] < 0 for k in _COUNTS))


def as_counts(summary: dict) -> dict:
    """A program summary in the reference's terms: counts and sums."""
    adm = max(summary["admitted"], 1.0)
    return {"arrivals": summary["requests"], "truncated": summary["truncated"],
            "admitted": summary["admitted"], "dropped": summary["dropped"],
            "slo_viol": round(summary["slo_viol_rate"] * adm),
            "deadline_miss": round(summary["deadline_miss_rate"] * adm),
            "lat_sum": summary["mean_latency_s"] * adm,
            "wait_sum": summary["mean_wait_s"] * adm,
            "hist": np.asarray(summary["hist"], np.float64),
            "backlog_curve": np.asarray(summary["backlog_curve"], np.float64)}


def _rel(x, y):
    return abs(x - y) / max(abs(y), 1.0)


def _worst(a, b):
    """The larger of two gaps, NaN if either is (``max`` may drop it)."""
    return a if np.isnan(a) or b <= a else b


def compare(prog: list, ref: list, q_gap: float, bin_s: float) -> dict:
    """The numbers that decide ``correct``, each the worst over the checked
    calls: ``prog`` and ``ref`` are their summaries in ``as_counts``'
    terms, ``q_gap`` the cache vectors' Q margin, ``bin_s`` the width of a
    histogram bin in seconds.  ``hist_w1`` is the distance between the two
    latency histograms (seconds a request moves, on average, to turn one
    into the other), which a rounding that moves latencies across one bin
    edge barely raises."""
    out = {"arrivals_gap": 0, "admission_gap": 0, "violation_gap": 0,
           "latency_gap": 0.0, "hist_w1": 0.0, "backlog_gap": 0.0}
    for p, r in zip(prog, ref):
        gaps = {
            "arrivals_gap": (abs(p["arrivals"] - r["arrivals"])
                             + abs(p["truncated"] - r["truncated"])),
            "admission_gap": max(abs(p["admitted"] - r["admitted"]),
                                 abs(p["dropped"] - r["dropped"])),
            "violation_gap": max(abs(p["slo_viol"] - r["slo_viol"]),
                                 abs(p["deadline_miss"] - r["deadline_miss"])),
            "latency_gap": max(_rel(p["lat_sum"], r["lat_sum"]),
                               _rel(p["wait_sum"], r["wait_sum"])),
            "hist_w1": (float(np.abs(np.cumsum(p["hist"] - r["hist"])).sum())
                        * bin_s / max(float(r["hist"].sum()), 1.0)),
            "backlog_gap": float(np.max(np.abs(p["backlog_curve"]
                                               - r["backlog_curve"]))
                                 / max(float(np.max(r["backlog_curve"])), 1.0)),
        }
        out = {k: _worst(out[k], gaps[k]) for k in out}
    return {**out, "cache_q_gap": q_gap}


def q_gap(c, st, rho_table) -> float:
    """The largest margin, over the popularity states, by which the
    reference's best cache action's Q-value lies above that of the given
    vector, in units of the state's Q-value spread."""
    qv = np.asarray(jax.device_get(reference_twin.q_values(c, st["q"])),
                    np.float64)
    M = c["M"]
    a = (np.rint(rho_table).astype(np.int64) << (M - 1 - np.arange(M))).sum(-1)
    gap = (qv.max(-1) - qv[np.arange(len(a)), a]) / (qv.std(-1) + 1e-30)
    return float(gap.max())


def reference_calls(c, f, seed, cells, rho_table, call_seeds, dt=jnp.float32):
    """The reference's cell state for ``seed`` and its summaries of the
    calls with ``call_seeds``, executing ``rho_table``."""
    k_init, _ = jax.random.split(reference.seed_key(seed))
    st = reference.decision_fns(c)[0](k_init)
    return st, [reference_twin.fleet(c, f, st, rho_table, s, cells, dt)
                for s in call_seeds]


class Driver:
    def __init__(self, config: dict, traffic: dict, seed: int, span):
        self.c = reference.shapes(config)
        self.cfg = t2drl_cfg(config)
        self.seed, self.span = int(seed), span
        self.f = dict(traffic["fleet"])
        self.fcfg = FleetCfg(**self.f)
        self.cells = traffic["cells"]
        self.warm_calls = traffic["warm_calls"]
        self.n_check = traffic["check_calls"]

    def _call(self, s):
        return simulate_fleet(self.ts, self.cfg, self.fcfg,
                              num_cells=self.cells, seed=s, policy=self.policy)

    def setup(self):
        cfg = self.cfg
        k_init, _ = jax.random.split(reference.seed_key(self.seed))
        ts = jax.jit(lambda k: t2drl_init(k, cfg))(k_init)
        d = tempfile.mkdtemp(prefix="chipbench-ckpt-")
        try:
            path = save_train_state(os.path.join(d, "state.msgpack"), ts)
            ts, _ = load_train_state(path)
        finally:
            shutil.rmtree(d, ignore_errors=True)
        self.ts = jax.device_put(ts)
        self.policy = export_policy(self.ts, cfg)
        key = jax.random.PRNGKey(0)    # eps = 0: the key draws nothing used
        self.rho_table = np.stack([np.asarray(core.greedy_frame_cache(
            self.policy, cfg, self.ts["models"], j, key))
            for j in range(self.c["J"])])
        for j in range(self.warm_calls):
            self._call(call_seed(self.seed, j, warm=True))

    def window(self, seconds: float) -> dict:
        span, kept, requests, bad = self.span, [], 0.0, 0
        t0 = time.perf_counter()
        while True:
            with span("bench.call"):
                out = self._call(call_seed(self.seed, len(kept)))
            with span("bench.host"):
                requests += out["requests"]
                bad += int(is_failed(out))
                kept.append({k: out[k] for k in _KEEP})
            if time.perf_counter() - t0 >= seconds:
                break
        window_s = time.perf_counter() - t0
        self.kept = kept
        print(f"[twin] {len(kept)} calls, {requests} simulated requests in "
              f"{window_s} s; {bad} calls failed", file=sys.stderr, flush=True)
        return {"units": len(kept), "attempted": len(kept), "failed": bad,
                "requests": requests, "window_s": window_s}

    def end_to_end(self, res: dict) -> dict:
        return {"twin_rate": res["requests"] / res["window_s"]}

    def counters(self, res: dict) -> dict:
        return {"units": res["units"], "flops_per_unit": None}

    def release(self):
        self.ts = self.policy = None
        gc.collect()

    def sample(self):
        """Indices of the checked calls: drawn from the seed, the last call
        of the window among them."""
        last = len(self.kept) - 1
        rng = np.random.default_rng(self.seed)
        idx = rng.choice(last, size=min(last, self.n_check), replace=False)
        return np.unique(np.append(idx, last)).astype(int)

    def check(self) -> dict:
        idx = self.sample()
        seeds = [call_seed(self.seed, int(j)) for j in idx]
        st, ref = reference_calls(self.c, self.f, self.seed, self.cells,
                                  self.rho_table, seeds)
        prog = [as_counts(self.kept[j]) for j in idx]
        return compare(prog, ref, q_gap(self.c, st, self.rho_table),
                       self.f["hist_max"] / self.f["hist_bins"])


def control(config: dict, traffic: dict, seed: int) -> dict:
    """The numbers of the control: the reference in bfloat16, its own
    cache vectors included, put in the program's place for the first
    ``check_calls + 1`` calls of ``seed``."""
    c = reference.shapes(config)
    f, cells = dict(traffic["fleet"]), traffic["cells"]
    seeds = [call_seed(seed, j) for j in range(traffic["check_calls"] + 1)]
    k_init, _ = jax.random.split(reference.seed_key(seed))
    st = reference.decision_fns(c)[0](k_init)
    low_q = reference_twin.q_values(c, jax.tree.map(
        lambda x: x.astype(jnp.bfloat16), st["q"]), jnp.bfloat16)
    rho_table = reference_twin.cache_vector_of(
        np.asarray(jnp.argmax(low_q, -1)), c["M"])
    _, low = reference_calls(c, f, seed, cells, rho_table, seeds, jnp.bfloat16)
    st, ref = reference_calls(c, f, seed, cells, rho_table, seeds)
    return compare(low, ref, q_gap(c, st, rho_table),
                   f["hist_max"] / f["hist_bins"])
