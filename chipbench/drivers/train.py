"""Training traffic: ``run_training`` in fixed chunks of episodes over B
independent fused learners, the train state carried (donated) from chunk
to chunk, one chunk blocked on before the next is dispatched, as
``train_t2drl`` does.

Set-up builds the state from the seed in one jitted call and drives the
first ``setup_chunks`` chunks through the window's own call; they pass
the D3PG warm-up and open the DDQN gate, so every slot of the window
updates.  They are what ``correct`` is decided on: the reference
(``chipbench.reference``) replays them from the same seed with the
program's executed actions and compares the rollout, the cache
decisions, the episode statistics, each learner's parameter change and
Adam's first moment.
"""
from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import flops, reference
from chipbench.program import t2drl_cfg
from repro.core import run_training, t2drl_init_batch

STATS = ("episode_reward", "mean_reward", "hit_ratio", "utility", "delay",
         "quality", "deadline_viol", "storage_viol")


def cache_action(rho):
    """Integer cache action of each caching vector (bit m of M, MSB first)."""
    M = rho.shape[-1]
    return (np.rint(rho).astype(np.int64) << (M - 1 - np.arange(M))).sum(-1)


def _leaves(tree):
    return [np.asarray(x, np.float64) for x in jax.tree.leaves(tree)]


class Driver:
    def __init__(self, config: dict, traffic: dict, seed: int, span):
        self.c = reference.shapes(config)
        self.cfg = t2drl_cfg(config)
        self.seed, self.span = int(seed), span
        self.B = traffic["cells"]
        self.E = traffic["episodes_per_chunk"]
        self.setup_chunks = traffic["setup_chunks"]
        c = self.c
        gate = c["ddqn_batch"] // (c["T"] - 1) + 1
        if (self.setup_chunks * self.E * c["T"] * c["K"] <= c["warmup"] + c["K"]
                or self.setup_chunks * self.E < gate):
            raise ValueError("set-up must pass the D3PG warm-up and open the "
                             f"DDQN gate ({gate} episodes)")
        if self.setup_chunks * self.E * c["T"] * c["K"] > c["replay"]:
            raise ValueError("the set-up chunks must fit the replay buffer")
        self.chunk = 0
        self.ts = None

    def _call(self):
        ep = np.arange(self.chunk * self.E, (self.chunk + 1) * self.E,
                       dtype=np.int32)
        self.ts, hist = run_training(self.ts, self.cfg, self.k_train, ep)
        self.chunk += 1
        return hist

    def _params(self):
        d3, dq = self.ts["d3pg"], self.ts["ddqn"]
        return {"p": _leaves([d3["actor"], d3["critic"], dq["q"]]),
                "mu": _leaves([d3["opt_a"]["mu"], d3["opt_c"]["mu"],
                               dq["opt"]["mu"]])}

    def setup(self):
        cfg, B = self.cfg, self.B
        k_init, self.k_train = jax.random.split(reference.seed_key(self.seed))
        self.ts = jax.jit(lambda k: t2drl_init_batch(k, cfg, B))(k_init)
        p0 = self._params()["p"]
        hists = [jax.device_get(self._call()) for _ in range(self.setup_chunks)]
        self.artifact = self._snapshot(p0, hists)

    def _snapshot(self, p0, hists):
        """What the set-up chunks produced, on the host."""
        c = self.c
        E = self.setup_chunks * self.E
        N, F = E * c["T"] * c["K"], E * (c["T"] - 1)
        eb, fb = self.ts["ebuf"]["data"], self.ts["fbuf"]["data"]
        rows = jax.device_get({k: eb[k][:, :N] for k in ("s", "a", "r", "s1", "rho")})
        frames = jax.device_get({k: fb[k][:, :F] for k in ("s", "a", "r", "s1")})
        hist = {k: np.concatenate([h[k] for h in hists]) for k in STATS}
        return {"slots": rows, "frames": frames,
                "cache": cache_action(rows["rho"][:, ::c["K"]]),
                "hist": hist, "p0": p0, **{k + "3": v for k, v in
                                           self._params().items()}}

    def window(self, seconds: float) -> dict:
        span, n, bad = self.span, 0, 0
        t0 = time.perf_counter()
        while True:
            with span("bench.dispatch"):
                hist = self._call()
            with span("bench.block"):
                h = jax.device_get(hist)
            with span("bench.host"):
                n += 1
                bad += int(np.sum(~np.all(np.isfinite(
                    np.stack([h[k] for k in STATS])), axis=0)))
            if time.perf_counter() - t0 >= seconds:
                break
        window_s = time.perf_counter() - t0
        units = n * self.E * self.B
        return {"units": units, "attempted": units, "failed": bad,
                "window_s": window_s, "chunks": n}

    def end_to_end(self, res: dict) -> dict:
        return {"train_rate": res["units"] / res["window_s"]}

    def counters(self, res: dict) -> dict:
        return {"units": res["units"], "flops_per_unit": flops.cell_episode(self.c)}

    def release(self):
        self.ts = None
        gc.collect()

    def check(self) -> dict:
        return compare(self.c, self.artifact,
                       replay(self.c, self.seed, self.B,
                              self.setup_chunks * self.E, self.artifact))


# -- the comparison ------------------------------------------------------------------

def _by_cell(x):
    """(E, B, n, ...) episode records -> (B, E*n, ...)."""
    x = np.asarray(x)
    x = np.moveaxis(x, 0, 1)
    return x.reshape((x.shape[0], -1) + x.shape[3:])


def replay(c, seed, cells, episodes, artifact, dt=jnp.float32):
    """The reference's run of the same seed, replaying the executed
    actions of ``artifact``."""
    teacher = {"a": jnp.asarray(artifact["slots"]["a"]),
               "c": jnp.asarray(artifact["cache"], jnp.int32)}
    return run_reference(c, seed, cells, episodes, dt, teacher)


def run_reference(c, seed, cells, episodes, dt, teacher=None) -> dict:
    st0, st, recs = reference.train(c, seed, cells, episodes, dt=dt,
                                    teacher=teacher)
    recs = jax.device_get(recs)
    sl, fr = recs["slots"], recs["frames"]
    per_frame = lambda x: np.moveaxis(np.asarray(x), 0, 1)   # (B, E, T)
    flat = lambda x: per_frame(x).reshape(cells, -1)         # (B, E*T)
    nets = lambda t: [t[k] for k in ("actor", "critic", "q")]
    return {"s": _by_cell(sl["s"]), "s1": _by_cell(sl["s1"]),
            "own_a": _by_cell(sl["own_a"]), "r": _by_cell(sl["r"]),
            "gamma": per_frame(fr["gamma"]), "a_c": per_frame(fr["a_c"]),
            "r_frame": per_frame(fr["r_frame"]), "rand": flat(fr["rand"]),
            "explore": flat(fr["explore"]), "q_gap": flat(fr["q_gap"]),
            "stats": {k: np.asarray(v) for k, v in recs["stats"].items()},
            "p0": _leaves(nets(st0)), "p3": _leaves(nets(st)),
            "mu3": _leaves([st["opt_a"]["mu"], st["opt_c"]["mu"],
                            st["opt_q"]["mu"]])}


def as_artifact(ref: dict) -> dict:
    """A reference run in the shape of a program snapshot (the control is
    the reference, put in the program's place)."""
    B, E, T = ref["gamma"].shape
    g = ref["gamma"]
    return {"slots": {"s": ref["s"], "a": ref["own_a"], "r": ref["r"],
                      "s1": ref["s1"]},
            "frames": {"s": g[:, :, :-1].reshape(B, -1),
                       "a": ref["a_c"][:, :, :-1].reshape(B, -1),
                       "r": ref["r_frame"][:, :, :-1].reshape(B, -1),
                       "s1": g[:, :, 1:].reshape(B, -1)},
            "cache": ref["a_c"].reshape(B, -1), "hist": ref["stats"],
            "p0": ref["p0"], "p3": ref["p3"], "mu3": ref["mu3"]}


def _rel(x, y):
    x, y = np.asarray(x, np.float64), np.asarray(y, np.float64)
    return float(np.max(np.abs(x - y) / np.maximum(np.abs(y), 1.0), initial=0.0))


def _norm_gaps(prog, ref, keep):
    """Worst leaf of |‖prog‖ - ‖ref‖| over max(‖ref‖, median leaf ‖ref‖)."""
    pn = np.array([np.linalg.norm(x) for x in prog])[keep]
    rn = np.array([np.linalg.norm(x) for x in ref])[keep]
    return float(np.max(np.abs(pn - rn) / np.maximum(rn, np.median(rn))))



def compare(c, art: dict, ref: dict) -> dict:
    """The numbers that decide ``correct`` in a training cell, each the
    worst case over cells, slots, frames or leaves.

    ``xi_gap``, ``obs_gap`` and ``leaves_left_out`` are reported, not
    compared.  The compute shares are normalised over the users whose
    model is cached, so where one such user's clipped raw share is 0 on
    one side and 1e-4 on the other, its share jumps between 0 and 1 on
    sound runs; the bandwidth shares keep a 0.01 floor and stay
    continuous.  The observations read 0 on every sound run and no fault
    moves them, so they could only fail sound runs."""
    sl = art["slots"]
    fr = art["frames"]
    U = c["U"]
    B, E, T = ref["gamma"].shape
    gamma = ref["gamma"]
    explore = ref["explore"]
    cache = np.asarray(art["cache"])
    mismatch = (np.sum(fr["s"] != gamma[:, :, :-1].reshape(B, -1))
                + np.sum(fr["s1"] != gamma[:, :, 1:].reshape(B, -1))
                + np.sum(explore & (cache != ref["rand"])))
    greedy = ref["q_gap"][~explore]
    # leaves whose reference gradient is nought to rounding move under Adam
    # by round-off alone: keep those above a thousandth of the median leaf
    mu_n = np.array([np.linalg.norm(x) for x in ref["mu3"]])
    keep = mu_n >= 1e-3 * np.median(mu_n)
    gap_a = ref["own_a"] - sl["a"]
    d_prog = [a - b for a, b in zip(art["p3"], art["p0"])]
    d_ref = [a - b for a, b in zip(ref["p3"], ref["p0"])]
    return {
        "obs_gap": float(max(np.max(np.abs(ref["s"] - sl["s"])),
                             np.max(np.abs(ref["s1"] - sl["s1"])))),
        "bandwidth_gap": float(np.max(np.abs(gap_a[..., :U]))),
        "xi_gap": float(np.max(np.abs(gap_a[..., U:]))),
        "reward_gap": max(_rel(sl["r"], ref["r"]),
                          _rel(fr["r"], ref["r_frame"][:, :, :-1].reshape(B, -1))),
        "cache_mismatch": int(mismatch),
        "cache_gap": float(np.max(greedy, initial=0.0)),
        "history_gap": max(_rel(art["hist"][k], ref["stats"][k]) for k in STATS),
        "param_change_gap": _norm_gaps(d_prog, d_ref, keep),
        "grad_gap": _norm_gaps(art["mu3"], ref["mu3"], keep),
        "leaves_left_out": int(np.sum(~keep)),
    }


def control(config: dict, traffic: dict, seed: int) -> dict:
    """The numbers of the control: the reference in bfloat16, put in the
    program's place for the set-up chunks of ``seed``."""
    c = reference.shapes(config)
    B = traffic["cells"]
    E = traffic["setup_chunks"] * traffic["episodes_per_chunk"]
    art = as_artifact(run_reference(c, seed, B, E, jnp.bfloat16))
    return compare(c, art, replay(c, seed, B, E, art))
