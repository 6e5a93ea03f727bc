"""Serving engine slot lifecycle (prefill bucketing, slot reuse after
EOS / budget exhaustion / context cap) and edge-gateway byte-budget
load/evict — previously only smoke-covered via test_system.py."""
import jax
import numpy as np
import pytest

from repro.configs import get_arch
from repro.models import lm as lm_mod
from repro.serving import CatalogEntry, EdgeGateway, Engine, ServeCfg
from repro.serving.engine import _bucket
from repro.serving.gateway import toy_diffusion_builder

KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def lm():
    cfg = get_arch("qwen2-0.5b").make_smoke()
    return cfg, lm_mod.lm_init(KEY, cfg)


# -- prefill length bucketing -------------------------------------------------

def test_bucket_is_pow2_with_floor_8():
    assert _bucket(1) == 8
    assert _bucket(8) == 8
    assert _bucket(9) == 16
    assert _bucket(100) == 128


def test_admit_pads_prompt_to_bucket(lm):
    """The bucket is only the prefill's compile shape: decoding continues
    from the prompt's true length."""
    cfg, params = lm
    eng = Engine(cfg, params, ServeCfg(max_batch=2, max_seq=64))
    slot = eng.admit(7, np.arange(3, dtype=np.int32), 4)
    assert eng.pos[slot] == 3            # prefilled in bucket 8
    slot2 = eng.admit(8, np.arange(9, dtype=np.int32) % cfg.vocab, 4)
    assert eng.pos[slot2] == 9           # prefilled in bucket 16
    assert eng.slots[slot].uid == 7 and eng.slots[slot2].uid == 8


def test_bucketing_does_not_change_greedy_output(lm):
    """The same prompt admitted alone (bucket 8) and after a longer one
    (different engine state) decodes identically — padding and per-slot
    cache isolation don't leak into the logits."""
    cfg, params = lm
    prompt = np.arange(5, dtype=np.int32)
    eng_a = Engine(cfg, params, ServeCfg(max_batch=2, max_seq=64))
    done_a, _ = eng_a.run([(0, prompt, 4)])
    eng_b = Engine(cfg, params, ServeCfg(max_batch=2, max_seq=64))
    done_b, _ = eng_b.run([(0, prompt, 4),
                           (1, np.arange(12, dtype=np.int32) % cfg.vocab, 4)])
    assert done_a[0] == done_b[0]


# -- slot lifecycle -----------------------------------------------------------

def test_budget_exhaustion_frees_and_reuses_slot(lm):
    cfg, params = lm
    eng = Engine(cfg, params, ServeCfg(max_batch=1, max_seq=64))
    assert eng.free_slot() == 0
    eng.admit(0, np.arange(4, dtype=np.int32), 2)
    assert eng.free_slot() is None
    finished = []
    while not finished:
        finished = eng.step()
    (uid, toks), = finished
    assert uid == 0 and len(toks) == 3   # prefill token + 2 decode steps
    assert eng.free_slot() == 0          # slot returned to the pool
    # reuse: generation in the recycled slot matches a fresh engine
    prompt = (np.arange(6, dtype=np.int32) * 3) % cfg.vocab
    done_reuse, _ = eng.run([(1, prompt, 3)])
    fresh = Engine(cfg, params, ServeCfg(max_batch=1, max_seq=64))
    done_fresh, _ = fresh.run([(1, prompt, 3)])
    assert done_reuse[1] == done_fresh[1]


def test_eos_terminates_before_budget(lm):
    cfg, params = lm
    prompt = np.arange(4, dtype=np.int32)
    ref = Engine(cfg, params, ServeCfg(max_batch=1, max_seq=64))
    done, _ = ref.run([(0, prompt, 5)])
    first_decoded = done[0][1]           # token emitted by decode step 1
    eng = Engine(cfg, params,
                 ServeCfg(max_batch=1, max_seq=64, eos_id=first_decoded))
    done_eos, stats = eng.run([(0, prompt, 5)])
    assert done_eos[0] == done[0][:2]    # stops at the EOS token
    assert stats["decode_steps"] == 1
    assert eng.free_slot() == 0


def test_context_cap_finishes_slot(lm):
    """pos >= max_seq - 1 ends generation even with budget remaining."""
    cfg, params = lm
    eng = Engine(cfg, params, ServeCfg(max_batch=1, max_seq=16))
    done, _ = eng.run([(0, np.arange(8, dtype=np.int32), 100)])
    # pos starts at the prompt's length 8; decode steps run pos through 9..15
    assert len(done[0]) == 8
    assert eng.free_slot() == 0


# -- gateway byte budget ------------------------------------------------------

def _catalogue(n=3, counter=None):
    def counted(seed):
        inner = toy_diffusion_builder(seed, 32)
        def build():
            if counter is not None:
                counter[seed] = counter.get(seed, 0) + 1
            return inner()
        return build
    return [CatalogEntry(model_id=i, name=f"m{i}", kind="diffusion",
                         size_gb=4.0 + i, builder=counted(i))
            for i in range(n)]


def test_gateway_load_respects_byte_budget():
    gw = EdgeGateway(_catalogue(), capacity_gb=10.0, image_dim=32,
                     total_steps=50)
    info = gw.apply_caching(np.array([1.0, 1.0, 1.0]))
    # id-order greedy: 4.0 + 5.0 fit, 6.0 would overflow -> skipped
    assert sorted(gw.loaded) == [0, 1]
    assert info["used_gb"] == pytest.approx(9.0)
    assert info["n_loaded"] == 2.0


def test_gateway_evict_then_reload_rebuilds_params():
    counter = {}
    gw = EdgeGateway(_catalogue(counter=counter), capacity_gb=6.0,
                     image_dim=32, total_steps=50)
    gw.apply_caching(np.array([1.0, 0.0, 0.0]))
    assert counter == {0: 1}
    gw.apply_caching(np.array([0.0, 1.0, 0.0]))      # evict 0, load 1
    assert sorted(gw.loaded) == [1] and gw.used_gb() == pytest.approx(5.0)
    gw.apply_caching(np.array([1.0, 0.0, 0.0]))      # reload 0 from scratch
    assert counter == {0: 2, 1: 1}
    assert 0 in gw.loaded and 1 not in gw.loaded


def test_gateway_uncached_serves_modeled_cloud_path():
    cat = _catalogue()
    gw = EdgeGateway(cat, capacity_gb=4.0, image_dim=32, total_steps=50)
    gw.apply_caching(np.array([1.0, 0.0, 0.0]))
    res = gw.serve_slot([0, 2], np.array([0.5, 0.5]), KEY)
    assert res[0].cached and res[0].measured_wall_s > 0.0
    assert not res[1].cached and res[1].measured_wall_s == 0.0
    e = cat[2]
    assert res[1].modeled_quality == e.a4
    assert res[1].modeled_delay == pytest.approx(e.b1 * e.a3 + e.b2)
