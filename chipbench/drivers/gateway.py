"""Gateway traffic: the edge gateway's ``Engine`` serving a language model
in a closed loop at a fixed concurrency (``slots``).  Every slot is busy;
when a request finishes, the next request of the seeded stream is admitted
into its slot (prefill at the prompt's true length, then greedy decoding
in the shared continuous-batching step).

The stream is drawn from the seed: prompt and output lengths lognormal
(``prompt``, ``output``: median, sigma, clipped to [min, max]; the output
clipped further so prompt and output fit ``max_seq``), token ids uniform
over the vocabulary, request i's prompt from its own generator.  The
``slots`` requests in flight when the window opens are admitted in set-up,
each standing at a uniformly drawn point of its output as in steady state,
so requests finish and prefills run from the window's first steps.
``decide_ms``: the window over its engine steps, each deciding the next
token of all ``slots`` requests, the prefills of the requests admitted
between steps included.
Set-up also makes the weights from the seed (the program's ``lm_init`` in
bfloat16) and compiles every prefill bucket and the decode step.

``correct``: the logits the timed path computed (at the prefill's last
position and at every decoded position, at ``logit_sample`` vocabulary ids
drawn from the seed plus the id each step chose) of ``check_requests``
requests drawn from the seed among those served in the window, and of the
last to finish, against the plain reference's teacher-forced forward pass
over prompt and generated tokens (``chipbench/reference_lm.py``).  Also
the picks of held experts the program left uncomputed (``dropped_tokens``),
and, reported, the (token, layer) pairs whose set of picked experts differs
from the reference's (``routing_flips``).

A backend other than the TPU (the CPU rehearsals under ``chipbench/tests``;
the harness never measures there) runs the configuration's and the
traffic's ``rehearsal`` sizes.
"""
from __future__ import annotations

import gc
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import flops_lm, reference_lm
from chipbench.harness import HERE
from chipbench.reference import seed_key
from repro.configs import get_arch
from repro.configs.common import deepseek_lm
from repro.models.lm import lm_init
from repro.nn.rotary import YaRN
from repro.serving import Engine, ServeCfg

def lm_cfg(config: dict):
    """The program's configuration for ``config``: ``deepseek_lm`` at the
    file's sizes, holding the file's share of the routed experts."""
    rs = config["rope_scaling"]
    dep = config["deployment"]
    yarn = YaRN(factor=float(rs["factor"]),
                original_max_positions=int(rs["original_max_position_embeddings"]),
                beta_fast=float(rs["beta_fast"]), beta_slow=float(rs["beta_slow"]),
                mscale=float(rs["mscale"]), mscale_all_dim=float(rs["mscale_all_dim"]))
    return deepseek_lm(
        "deepseek-v2-lite", layers=config["num_hidden_layers"],
        dense_layers=config["first_k_dense_replace"],
        d_model=config["hidden_size"], n_heads=config["num_attention_heads"],
        vocab=config["vocab_size"], moe_d_ff=config["moe_intermediate_size"],
        dense_d_ff=config["intermediate_size"],
        n_experts=dep["routed_experts_published"],
        top_k=config["num_experts_per_tok"], n_shared=config["n_shared_experts"],
        kv_lora_rank=config["kv_lora_rank"], q_lora_rank=config["q_lora_rank"] or 0,
        qk_nope_dim=config["qk_nope_head_dim"], qk_rope_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"], yarn=yarn,
        norm_topk_prob=config["norm_topk_prob"], held=tuple(dep["held_experts"]))


def _lengths(rng, spec: dict, n: int) -> np.ndarray:
    x = np.exp(rng.normal(np.log(spec["median"]), spec["sigma"], n))
    return np.clip(np.round(x), spec["min"], spec["max"]).astype(np.int64)


def _hbm_bytes_per_s():
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)
    return peaks.get(jax.devices()[0].device_kind, {}).get("hbm_bytes_per_s")


def logit_numbers(prog: list, ref: list) -> dict:
    """``logit_rms``: the RMS of program less reference over every compared
    logit of a request, over the RMS of the reference's, worst request;
    ``logit_worst``: the same per position, worst position of any
    request."""
    rms, worst = 0.0, 0.0
    for p, r in zip(prog, ref):
        d2, r2 = (p - r) ** 2, r ** 2
        rms = max(rms, float(np.sqrt(d2.mean() / r2.mean())))
        worst = max(worst, float(np.max(np.sqrt(d2.mean(1) / r2.mean(1)))))
    return {"logit_rms": rms, "logit_worst": worst}


def routing_flips(prog: list, ref: list) -> int:
    """(token, layer) pairs whose set of picked experts differs."""
    return int(sum(np.sum(np.any(np.sort(p, -1) != np.sort(r, -1), -1))
                   for p, r in zip(prog, ref)))


class Driver:
    def __init__(self, config: dict, traffic: dict, seed: int, span):
        self.rehearsal = jax.default_backend() != "tpu"
        if self.rehearsal:
            config = {**config, **config["rehearsal"]}
            traffic = {**traffic, **traffic["rehearsal"]}
        self.c = reference_lm.shapes(config)
        self.lm = lm_cfg(config)
        if not self.rehearsal:
            full = get_arch("deepseek-v2-lite").make_full(
                held=tuple(config["deployment"]["held_experts"]))
            if full != self.lm:
                raise ValueError("the program's deepseek-v2-lite differs from "
                                 f"{config['name']}'s sizes")
        self.t, self.seed, self.span = traffic, int(seed), span
        self.B, self.S = traffic["slots"], traffic["max_seq"]
        rng = np.random.default_rng(self.seed)
        self.vocab_ids = np.sort(rng.choice(self.c["V"], traffic["logit_sample"],
                                            replace=False)).astype(np.int32)
        self._requests = {}

    def request(self, i: int):
        """Request i of the stream: (prompt tokens, output length), from its
        own generator."""
        if i not in self._requests:
            rng = np.random.default_rng([self.seed, i])
            L = int(_lengths(rng, self.t["prompt"], 1)[0])
            n = min(int(_lengths(rng, self.t["output"], 1)[0]), self.S - L)
            if i < self.B:                   # in flight when the window opens
                n = int(rng.integers(2, n + 1))
            self._requests[i] = (rng.integers(0, self.c["V"], L).astype(np.int32), n)
        return self._requests[i]

    # -- the program -----------------------------------------------------------

    def setup(self):
        params = jax.jit(lambda k: lm_init(k, self.lm, dtype=jnp.bfloat16))(
            seed_key(self.seed))
        self.engine = e = Engine(self.lm, params, ServeCfg(max_batch=self.B,
                                                           max_seq=self.S))
        ids = jnp.asarray(self.vocab_ids)
        # the chosen id is the largest logit's (greedy decoding)
        self._sample = jax.jit(lambda logits: jnp.concatenate(
            [jnp.take(logits, ids, axis=-1),
             jnp.max(logits, axis=-1, keepdims=True)], axis=-1))
        e.warm_up(range(self.t["prompt"]["min"], self.t["prompt"]["max"] + 1))
        # what each request produced: its prefill's and each decode step's
        # logits sample (with the chosen id's logit last) and picked
        # experts, kept on the device until the window is over
        self.rec = {}
        self.next_req = 0
        while e.free_slot() is not None:
            self._admit()
        self._step()
        self.start = e.counters()
        self.first_window_req = self.next_req

    def _admit(self):
        i = self.next_req
        self.next_req += 1
        e = self.engine
        prompt, n = self.request(i)
        e.admit(i, prompt, n - 1)
        self.rec[i] = {"prefill": (self._sample(e.logits), e.route),
                       "steps": [], "tokens": None}

    def _step(self):
        e = self.engine
        owners = {i: s.uid for i, s in enumerate(e.slots) if s.uid is not None}
        finished = e.step()
        sample = self._sample(e.logits)
        for i, uid in owners.items():
            self.rec[uid]["steps"].append((sample, e.route, i))
        for uid, toks in finished:
            self.rec[uid]["tokens"] = toks
        return finished

    def window(self, seconds: float) -> dict:
        t0 = time.perf_counter()
        self.finish_order = []
        while time.perf_counter() - t0 < seconds:
            self.finish_order += [uid for uid, _ in self._step()]
            while self.engine.free_slot() is not None:
                self._admit()
        jax.block_until_ready(self.engine.cache)
        window_s = time.perf_counter() - t0
        for s in self.engine.slots:          # requests still being served
            if s.uid is not None:
                self.rec[s.uid]["tokens"] = list(s.generated)
        end = self.engine.counters()
        self.win = {k: v - self.start[k] for k, v in end.items()}
        self.win["expert_picks_max"] = end.get("expert_picks_max")
        self.served = sorted(i for i, r in self.rec.items() if r["steps"])
        w = self.win
        print(f"[gateway] {w['decode_steps']} decode steps, {w['prefills']} "
              f"prefills, {len(self.finish_order)} requests finished in "
              f"{window_s} s; counters {w}", file=sys.stderr, flush=True)
        short = sum(len(self.rec[u]["tokens"]) != self.request(u)[1]
                    for u in self.finish_order)
        return {"units": w["prefill_tokens"] + w["decode_tokens"],
                "attempted": len(self.finish_order), "failed": int(short),
                "window_s": window_s}

    def end_to_end(self, res: dict) -> dict:
        # one decision is one engine step: the next token of every busy
        # slot, with the admissions (prefills) the step's finishes make
        steps = max(self.win["decode_steps"], 1)
        return {"decide_ms": 1e3 * res["window_s"] / steps}

    def counters(self, res: dict) -> dict:
        c, w = self.c, self.win
        firsts = range(self.first_window_req, self.next_req)
        total = (sum(flops_lm.prefill(c, len(self.request(i)[0])) for i in firsts)
                 + flops_lm.decode(c, w["decode_tokens"], w["live_tokens"])
                 + flops_lm.routed(c, w.get("expert_picks", 0)
                                   + w.get("prefill_expert_picks", 0)))
        steps = max(w["decode_steps"], 1)
        return {"units": res["units"],
                "flops_per_unit": total / max(res["units"], 1),
                "decode_steps": w["decode_steps"], "prefills": w["prefills"],
                "decode_bytes_per_step": flops_lm.decode_step_bytes(
                    c, w["decode_tokens"] / steps,
                    w.get("experts_touched", 0) / steps,
                    w["live_tokens"] / steps),
                "hbm_bytes_per_s": _hbm_bytes_per_s(), "engine": w}

    def release(self):
        self.engine = self._sample = None
        gc.collect()

    # -- the check ------------------------------------------------------------

    def sample(self) -> list:
        """The checked requests: ``check_requests`` drawn from the seed among
        those served in the window, and the last to finish."""
        rng = np.random.default_rng(self.seed + 1)
        served = sorted(self.served)
        pick = rng.choice(len(served), min(len(served), self.t["check_requests"]),
                          replace=False)
        chosen = {served[j] for j in pick}
        if self.finish_order:
            chosen.add(self.finish_order[-1])
        return sorted(chosen)

    def program_record(self, uid: int):
        """(tokens generated, logits at the sampled ids and at the chosen
        one per position (n, M + 1), picked experts per position or None)
        of request ``uid``: n tokens, from its prefill and n - 1 steps."""
        r = self.rec[uid]
        L = len(self.request(uid)[0])
        toks = r["tokens"][: 1 + len(r["steps"])]
        logits = [self._host(r["prefill"][0])]
        logits += [self._host(sample)[slot] for sample, _, slot in r["steps"]]
        ids = None
        if r["prefill"][1] is not None:
            ids = [self._host(r["prefill"][1])[:, :L].transpose(1, 0, 2)]
            ids += [self._host(route)[slot][None] for _, route, slot in r["steps"]]
            ids = np.concatenate(ids)
        return np.asarray(toks, np.int32), np.stack(logits)[: len(toks)], ids

    def _host(self, arr) -> np.ndarray:
        """``arr`` on the host, fetched once however many requests share it."""
        if id(arr) not in self._fetched:
            self._fetched[id(arr)] = (arr, np.asarray(arr))
        return self._fetched[id(arr)][1]

    def check(self) -> dict:
        uids = self.sample()
        self._fetched = {}
        tokens, rows, prog, prog_ids = [], [], [], []
        for u in uids:
            gen, lg, ids = self.program_record(u)
            prompt = self.request(u)[0]
            n, L = len(gen), len(prompt)
            seq = np.concatenate([prompt, gen[:-1]])
            vids = np.concatenate([np.broadcast_to(self.vocab_ids,
                                                   (n, len(self.vocab_ids))),
                                   gen[:, None]], axis=1)
            tokens.append(np.pad(seq, (0, self.S - len(seq))))
            rows.append((np.arange(L - 1, L - 1 + n), vids))
            prog.append(lg)
            prog_ids.append(None if ids is None else ids[: len(seq)])
        self.rec = self._fetched = None
        ref, ref_ids = reference_lm.forward(seed_key(self.seed), self.c,
                                            np.stack(tokens), rows)
        out = logit_numbers(prog, ref)
        out["dropped_tokens"] = self.win.get("dropped", 0)
        out["routing_flips"] = routing_flips(
            [p for p in prog_ids if p is not None],
            [r[: len(p)] for p, r in zip(prog_ids, ref_ids) if p is not None])
        out["checked_requests"] = len(uids)
        out["checked_positions"] = int(sum(len(p) for p in prog))
        return out


def control(config: dict, traffic: dict, seed: int) -> dict:
    """The control: the reference with weights and the residual stream
    between layers in float8 (e4m3), one step below the configuration's
    bfloat16, in the program's place, over the first ``check_requests``
    requests of the stream teacher-forced on tokens drawn from the seed."""
    drv = Driver(config, traffic, seed, None)
    rng = np.random.default_rng(seed + 2)
    tokens, rows = [], []
    for i in range(drv.B, drv.B + traffic["check_requests"]):
        prompt, n = drv.request(i)
        L = len(prompt)
        seq = np.concatenate([prompt,
                              rng.integers(0, drv.c["V"], n - 1).astype(np.int32)])
        tokens.append(np.pad(seq, (0, drv.S - len(seq))))
        rows.append((np.arange(L - 1, L - 1 + n),
                     np.broadcast_to(drv.vocab_ids, (n, len(drv.vocab_ids)))))
    key = seed_key(seed)
    low, low_ids = reference_lm.forward(key, drv.c, np.stack(tokens), rows,
                                        dt=jnp.float8_e4m3fn)
    ref, ref_ids = reference_lm.forward(key, drv.c, np.stack(tokens), rows)
    out = logit_numbers(low, ref)
    out["dropped_tokens"] = 0
    out["routing_flips"] = routing_flips(low_ids, ref_ids)
    return out
