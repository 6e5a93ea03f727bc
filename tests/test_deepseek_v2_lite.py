"""DeepSeek-V2-Lite through the serving path against a plain float32
reference (``_deepseek_v2_ref``), at a small size with seeded random
weights: prefill at the true prompt length and decoding through the
``Engine``'s cache, expert shares that add up to the whole layer, dropless
routing under skew, YaRN, and top-k renormalisation both ways."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.models.lm import lm_init
from repro.nn.mla import MLACfg
from repro.nn.moe import (MoECfg, moe_apply, moe_decode, moe_init,
                          moe_prefill)
from repro.nn.rotary import rope_cos_sin, rope_freqs
from repro.serving import Engine, ServeCfg

import _deepseek_v2_ref as ref

KEY = jax.random.PRNGKey(0)
F32 = {"compute_dtype": jnp.float32}


def _rel_rms(a, b):
    return float(np.sqrt(np.mean((a - b) ** 2) / np.mean(b ** 2)))


@pytest.fixture(scope="module")
def served():
    """The small config holding experts 2-5 of 8, three requests (prompt
    lengths 3, 9 and 13, none a prefill bucket) through a two-slot engine,
    with the logits of every prefill and decode step the engine ran."""
    lm = get_arch("deepseek-v2-lite").make_smoke(held=(2, 4))
    params = lm_init(KEY, lm)
    eng = Engine(lm, params, ServeCfg(max_batch=2, max_seq=64))
    rng = np.random.default_rng(1)
    reqs = [(u, rng.integers(0, lm.vocab, L).astype(np.int32), n)
            for u, (L, n) in enumerate([(3, 6), (9, 4), (13, 5)])]
    logits = {u: [] for u, _, _ in reqs}
    pending = list(reqs)
    done = {}
    while pending or eng.active():
        while pending and eng.free_slot() is not None:
            u, prompt, n = pending.pop(0)
            eng.admit(u, prompt, n)
            logits[u].append(np.asarray(eng.logits))
        owners = {i: s.uid for i, s in enumerate(eng.slots) if s.uid is not None}
        finished = eng.step()
        for i, u in owners.items():
            logits[u].append(np.asarray(eng.logits[i]))
        done.update(finished)
    return lm, params, reqs, done, logits, eng


def test_engine_prefill_and_decode_match_reference(served):
    lm, params, reqs, done, logits, _ = served
    for u, prompt, n in reqs:
        toks = np.asarray(done[u], np.int32)
        assert len(toks) == n + 1
        seq = np.concatenate([prompt, toks[:-1]])
        full, _ = ref.forward(params, lm, seq)
        want = full[len(prompt) - 1:]          # prefill's last position onwards
        got = np.stack(logits[u])
        assert got.shape == want.shape
        assert _rel_rms(got, want) < 0.02, (u, _rel_rms(got, want))
        # every token the engine chose is the reference's largest logit, or
        # within bfloat16 rounding of it
        top = want.max(-1)
        chosen = want[np.arange(len(toks)), toks]
        assert np.all(top - chosen < 0.05 * np.abs(top).max()), (u, top - chosen)


def test_engine_decodes_from_true_length(served):
    lm, params, reqs, done, logits, eng = served
    assert not eng.active()
    c = eng.counters()
    assert c["prefill_tokens"] == 3 + 9 + 13
    assert c["decode_tokens"] == 6 + 4 + 5
    assert c["dropped"] == 0
    # every decoded token of every MoE layer makes top_k picks, of which
    # those of held experts are counted
    held = sum(np.isin(ref.forward(params, lm, np.concatenate(
        [p, np.asarray(done[u][:-1], np.int32)]))[1][len(p):], range(2, 6)).sum()
        for u, p, _ in reqs)
    assert c["expert_picks"] == held


def _layer(n_experts=8, top_k=3, held=None, norm=False, d=32, f=16, seed=0):
    cfg = MoECfg(d, f, n_experts=n_experts, top_k=top_k, n_shared=2,
                 norm_topk_prob=norm, held=held)
    return cfg, moe_init(jax.random.PRNGKey(seed), cfg)


def _share(p, first, count):
    return {**p, "up": p["up"][first:first + count],
            "gate": p["gate"][first:first + count],
            "down": p["down"][first:first + count]}


def _shared_only(p, cfg, x):
    return ref.swiglu(p["shared"]["gate"]["w"], p["shared"]["up"]["w"],
                      p["shared"]["down"]["w"], x)


@pytest.mark.parametrize("serve,shape", [(moe_prefill, (2, 6)), (moe_decode, (12, 1))],
                         ids=["prefill", "decode"])
def test_four_expert_shares_add_up_to_the_whole_layer(serve, shape):
    cfg, p = _layer()
    x = jax.random.normal(jax.random.PRNGKey(3), shape + (32,))
    with jax.default_matmul_precision("highest"):
        whole, _ = ref.moe(p, x.reshape(12, 32), cfg)
        shared = _shared_only(p, cfg, x.reshape(12, 32))
        total = shared
        for first in range(0, 8, 2):
            c = dataclasses.replace(cfg, held=(first, 2))
            y, info = serve(_share(p, first, 2), c, x, **F32)
            assert int(info["dropped"]) == 0
            total = total + (y.reshape(12, 32) - shared)
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=1e-4, atol=1e-5)


def test_skewed_routing_drops_no_token():
    """Every token picks the same three experts: capacity dispatch
    (training) overflows them, the serving path computes every pick."""
    cfg, p = _layer(held=(0, 8))
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(4), (1, 16, 32)))
    p = {**p, "router": {"w": jnp.zeros((32, 8)).at[:, :3].set(1.0)}}
    with jax.default_matmul_precision("highest"):
        want, ids = ref.moe(p, x[0], cfg)
        assert np.all(np.sort(np.asarray(ids), -1) == [0, 1, 2])
        y, info = moe_prefill(p, cfg, x, **F32)
        capped, _ = moe_apply(p, dataclasses.replace(cfg, held=None,
                                                     capacity_factor=1.0), x, **F32)
    assert int(info["dropped"]) == 0
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    assert float(jnp.abs(capped[0] - want).max()) > 1e-2


def test_yarn_frequencies_and_scale_follow_the_formula():
    y = get_arch("deepseek-v2-lite").make_full().groups[0].cycle[0].mla.yarn
    d, theta = 64, 10000.0
    # correction range: pair index where a pair turns beta times over the
    # original 4096 positions, d * ln(4096 / (2 pi beta)) / (2 ln theta)
    lo = math.floor(64 * math.log(4096 / (2 * math.pi * 32)) / (2 * math.log(theta)))
    hi = math.ceil(64 * math.log(4096 / (2 * math.pi * 1)) / (2 * math.log(theta)))
    assert (lo, hi) == (10, 23)
    base = theta ** (-np.arange(0, d, 2) / d)
    ramp = np.clip((np.arange(32) - lo) / (hi - lo), 0, 1)
    want = base * (1 - ramp) + base / 40 * ramp
    np.testing.assert_allclose(np.asarray(rope_freqs(d, theta, y)), want, rtol=1e-6)
    assert np.allclose(np.asarray(rope_freqs(d, theta, y))[:10], base[:10])
    np.testing.assert_allclose(np.asarray(rope_freqs(d, theta, y))[23:], base[23:] / 40,
                               rtol=1e-6)
    m = 0.1 * 0.707 * math.log(40) + 1
    assert y.softmax_factor == pytest.approx(m * m) and m * m == pytest.approx(1.5896, abs=1e-4)
    assert y.cos_sin_factor == pytest.approx(1.0)
    mla = MLACfg(2048, 16, q_lora_rank=0, yarn=y)
    assert mla.softmax_scale == pytest.approx(m * m / math.sqrt(192))
    # the scaled frequencies apply at every position, not only past 4096
    cos, _ = rope_cos_sin(jnp.arange(8), d, theta, y)
    plain, _ = rope_cos_sin(jnp.arange(8), d, theta)
    assert float(jnp.abs(cos - plain).max()) > 1e-3
    assert ref.yarn_freqs(d, theta, 40, 4096, 32, 1) == pytest.approx(want)


@pytest.mark.parametrize("norm", [True, False])
def test_topk_renormalisation_follows_the_config(norm):
    cfg, p = _layer(held=(1, 5), norm=norm)
    p = _share(p, 0, 5)
    x = jax.random.normal(jax.random.PRNGKey(5), (3, 4, 32))
    with jax.default_matmul_precision("highest"):
        want, _ = ref.moe(p, x.reshape(12, 32), cfg)
        y_pre, _ = moe_prefill(p, cfg, x, **F32)
        y_dec, _ = moe_decode(p, cfg, x.reshape(12, 1, 32), **F32)
        other, _ = ref.moe(p, x.reshape(12, 32),
                           dataclasses.replace(cfg, norm_topk_prob=not norm))
    np.testing.assert_allclose(np.asarray(y_pre).reshape(12, 32), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(y_dec).reshape(12, 32), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    assert float(jnp.abs(want - other).max()) > 1e-3
