"""Continuous-batching serving engine for CompositeLM models.

Slot-based: a fixed ``max_batch`` of independent sequences share one decode
step, each slot at its own position.
Prefill runs per request at the prompt's true length: the prompt is padded
on the right to a power-of-two bucket, which only bounds the number of
compiled shapes; the first token comes from the last prompt position and
decoding continues from the prompt's length.  Prefill writes straight into
the free slot of the cache, and both the prefill and the decode step donate
the cache, so one copy of it is ever live.

Spans ``engine.prefill`` and ``engine.decode_step`` mark each call on the
profiler's trace (``jax.profiler.TraceAnnotation``, nothing when no trace is
taken); ``counters()`` gives the tokens served and, for a mixture-of-experts
model, the picks per held expert and the picks left uncomputed.

This is the substrate the paper assumes exists at the edge: the thing that
actually executes a cached GenAI model for a user request.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.lm import (LMCfg, lm_decode, lm_init_cache, lm_prefill)
from repro.nn.moe import held_load, held_range


@dataclasses.dataclass
class ServeCfg:
    max_batch: int = 4
    max_seq: int = 512
    eos_id: int = -1            # -1: never stop early
    pad_id: int = 0


def _bucket(n: int) -> int:
    b = 8
    while b < n:
        b *= 2
    return b


@dataclasses.dataclass
class _Slot:
    uid: Optional[int] = None
    budget: int = 0
    generated: Optional[list] = None


def _moe_cfg(cfg: LMCfg):
    """The MoE configuration of ``cfg``'s expert layers, or None."""
    for g in cfg.groups:
        for b in g.cycle:
            if b.ffn == "moe":
                return b.moe
    return None


class Engine:
    def __init__(self, cfg: LMCfg, params, serve_cfg: ServeCfg):
        self.cfg = cfg
        self.params = params
        self.sc = serve_cfg
        B, S = serve_cfg.max_batch, serve_cfg.max_seq
        self.cache = lm_init_cache(cfg, B, S)
        self.pos = np.zeros(B, np.int32)          # next position per slot
        self.slots: List[_Slot] = [_Slot() for _ in range(B)]
        self.last_tok = np.zeros(B, np.int32)
        self.moe = _moe_cfg(cfg)
        self.n_moe_layers = sum(g.repeats * sum(b.ffn == "moe" for b in g.cycle)
                                for g in cfg.groups)
        # device arrays of the latest call: its logits, and the experts each
        # of its tokens picked in every MoE layer (None without MoE layers)
        self.logits = self.route = None
        self._counts = self._zero_counts()
        self._stats = self._zero_stats()

        def decode(params, state, cache, stats):
            tok, pos, active = state[0], state[1], state[2] > 0
            logits, cache, route = lm_decode(params, cfg, tok[:, None], cache,
                                             pos, return_route=True)
            logits = logits[:, 0]
            ids = None
            if route is not None:
                ids = route["ids"][:, :, 0]               # (layers, B, K)
                load = jax.vmap(lambda i: held_load(self.moe, i, active))(ids)
                ids = jnp.moveaxis(ids, 0, 1)
                stats = {**stats,
                         "decode_load": stats["decode_load"] + load,
                         "decode_load_max": jnp.maximum(stats["decode_load_max"],
                                                        load),
                         "decode_touched": stats["decode_touched"]
                         + jnp.sum(load > 0),
                         "dropped": stats["dropped"] + route["dropped"]}
            return jnp.argmax(logits, axis=-1), logits, ids, cache, stats

        self._decode = jax.jit(decode, donate_argnums=(2, 3))

        def prefill(params, toks, length, cache, slot, stats):
            sub = jax.tree.map(
                lambda c: jax.lax.dynamic_slice_in_dim(c, slot, 1, axis=1), cache)
            logits, sub, route = lm_prefill(params, cfg, toks, sub,
                                            length=length, return_route=True)
            cache = jax.tree.map(
                lambda c, s: jax.lax.dynamic_update_slice_in_dim(
                    c, s.astype(c.dtype), slot, axis=1), cache, sub)
            logits = logits[0, 0]
            ids = None
            if route is not None:
                ids = route["ids"][:, 0]                  # (layers, Lb, K)
                valid = jnp.arange(toks.shape[1]) < length
                load = jax.vmap(lambda i: held_load(self.moe, i, valid))(ids)
                stats = {**stats,
                         "prefill_load": stats["prefill_load"] + load,
                         "dropped": stats["dropped"] + route["dropped"]}
            return jnp.argmax(logits), logits, ids, cache, stats

        self._prefill = jax.jit(prefill, donate_argnums=(3, 5))

    # -- counters --------------------------------------------------------------

    @staticmethod
    def _zero_counts() -> dict:
        return {"prefills": 0, "prefill_tokens": 0, "decode_steps": 0,
                "decode_tokens": 0, "live_tokens": 0}

    def _zero_stats(self) -> dict:
        if self.moe is None:
            return {}
        shape = (self.n_moe_layers, held_range(self.moe)[1])
        z = lambda s: jnp.zeros(s, jnp.int32)
        return {"decode_load": z(shape), "decode_load_max": z(shape),
                "decode_touched": z(()), "prefill_load": z(shape),
                "dropped": z(())}

    def counters(self) -> dict:
        """Counts since the engine started (or its warm-up ended): prefills
        and their prompt tokens; decode steps, the tokens they decoded
        (active slots summed over steps) and the cache positions those
        tokens attended (``live_tokens``).  With MoE layers also, from the
        device: ``expert_picks`` (picks of held experts by decoded tokens,
        over every MoE layer), ``expert_picks_max`` (the most one held
        expert of one layer took in one step), ``experts_touched`` (held
        experts, over layers and steps, that a step ran for at least one
        pick), ``prefill_expert_picks`` and ``dropped`` (held picks left
        uncomputed, prefill and decode)."""
        out = dict(self._counts)
        if self._stats:
            st = jax.device_get(self._stats)
            out.update(expert_picks=int(st["decode_load"].sum()),
                       expert_picks_max=int(st["decode_load_max"].max()),
                       experts_touched=int(st["decode_touched"]),
                       prefill_expert_picks=int(st["prefill_load"].sum()),
                       dropped=int(st["dropped"]))
        return out

    # -- admission -------------------------------------------------------------

    def free_slot(self) -> Optional[int]:
        for i, s in enumerate(self.slots):
            if s.uid is None:
                return i
        return None

    def _run_prefill(self, toks, length, slot):
        nxt, self.logits, self.route, self.cache, self._stats = self._prefill(
            self.params, toks, np.int32(length), self.cache, np.int32(slot),
            self._stats)
        return nxt

    def admit(self, uid: int, prompt: np.ndarray, max_new_tokens: int) -> int:
        """Prefill ``prompt`` into a free slot; returns the slot index."""
        slot = self.free_slot()
        assert slot is not None, "no free slot"
        L = int(prompt.shape[-1])
        assert 0 < L < self.sc.max_seq, (L, self.sc.max_seq)
        toks = np.full((1, min(_bucket(L), self.sc.max_seq)), self.sc.pad_id,
                       np.int32)
        toks[0, :L] = prompt
        with jax.profiler.TraceAnnotation("engine.prefill"):
            nxt = int(self._run_prefill(toks, L, slot))
        self._counts["prefills"] += 1
        self._counts["prefill_tokens"] += L
        self.pos[slot] = L
        self.last_tok[slot] = nxt
        self.slots[slot] = _Slot(uid=uid, budget=max_new_tokens,
                                 generated=[nxt])
        return slot

    # -- decode ---------------------------------------------------------------

    def active(self) -> List[int]:
        return [i for i, s in enumerate(self.slots) if s.uid is not None]

    def _run_decode(self, active):
        state = np.stack([self.last_tok, self.pos, active.astype(np.int32)])
        nxt, self.logits, self.route, self.cache, self._stats = self._decode(
            self.params, state, self.cache, self._stats)
        return nxt

    def step(self):
        """One continuous-batching decode step over all slots."""
        active = np.array([s.uid is not None for s in self.slots])
        with jax.profiler.TraceAnnotation("engine.decode_step"):
            nxt = np.asarray(self._run_decode(active), np.int32)
        self._counts["decode_steps"] += 1
        self._counts["decode_tokens"] += int(active.sum())
        self._counts["live_tokens"] += int((self.pos[active] + 1).sum())
        finished = []
        for i, s in enumerate(self.slots):
            if s.uid is None:
                continue
            self.pos[i] += 1
            tok = int(nxt[i])
            s.generated.append(tok)
            s.budget -= 1
            if (s.budget <= 0 or tok == self.sc.eos_id
                    or self.pos[i] >= self.sc.max_seq - 1):
                finished.append((s.uid, list(s.generated)))
                self.slots[i] = _Slot()
            else:
                self.last_tok[i] = tok
        return finished

    def warm_up(self, prompt_lengths):
        """Compile the prefill for the bucket of each of ``prompt_lengths``
        and the decode step, on an idle engine; counters start afresh."""
        assert not self.active(), "warm up an idle engine"
        for Lb in sorted({min(_bucket(int(L)), self.sc.max_seq)
                          for L in prompt_lengths}):
            self._run_prefill(np.full((1, Lb), self.sc.pad_id, np.int32), 1, 0)
        jax.block_until_ready(self._run_decode(np.zeros(self.sc.max_batch, bool)))
        self._counts, self._stats = self._zero_counts(), self._zero_stats()

    # -- convenience ------------------------------------------------------------

    def run(self, requests, *, on_finish: Optional[Callable] = None):
        """Serve a list of (uid, prompt ndarray, max_new_tokens) with
        continuous batching.  Returns {uid: generated tokens} and timing."""
        t0 = time.perf_counter()
        pending = list(requests)
        done = {}
        steps = 0
        while pending or self.active():
            while pending and self.free_slot() is not None:
                uid, prompt, mnt = pending.pop(0)
                self.admit(uid, prompt, mnt)
            for uid, toks in self.step():
                done[uid] = toks
                if on_finish:
                    on_finish(uid, toks)
            steps += 1
        return done, {"wall_s": time.perf_counter() - t0,
                      "decode_steps": steps}
