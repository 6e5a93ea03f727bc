"""The gateway cell at its CPU rehearsal size: the plain reference's
weights are the program's, the control and each planted fault read
``correct`` false, the FLOP and byte counts match a count by hand, and the
engine-span reduction and the new readers on made-up events."""
import json
import os

import numpy as np
import pytest

from chipbench import engine_trace, faults_gateway, flops_lm, harness, reference_lm
from chipbench.tests.conftest import ROOT, run_cell

CELL = "deepseek-v2-lite-ep4.serve-b64"
CONFIG = os.path.join(ROOT, "chipbench", "models", "deepseek-v2-lite-ep4.json")
ns = 1e-9


def _rehearsal():
    with open(CONFIG) as f:
        config = json.load(f)
    return {**config, **config["rehearsal"]}


def test_reference_weights_are_the_programs(jax_cpu):
    import jax
    import jax.numpy as jnp
    from chipbench.drivers.gateway import lm_cfg
    from chipbench.reference import seed_key
    from repro.models.lm import lm_init
    config = _rehearsal()
    c = reference_lm.shapes(config)
    key = seed_key(2 ** 31 + 17)
    p = lm_init(key, lm_cfg(config), dtype=jnp.bfloat16)
    f32 = lambda a: np.asarray(a, np.float32)
    np.testing.assert_array_equal(f32(reference_lm.embed_weights(key, c)),
                                  f32(p["embed"]["table"]))
    np.testing.assert_array_equal(f32(reference_lm.head_weights(key, c)),
                                  f32(p["lm_head"]["w"]))
    for li, k in enumerate(reference_lm.layer_keys(key, c)):
        moe = li >= c["dense_layers"]
        g, r = (1, li - c["dense_layers"]) if moe else (0, li)
        blk = jax.tree.map(lambda a: a[r], p["groups"][g]["stacked"]["0"])
        w = reference_lm.layer_weights(k, c, moe)
        mix = blk["mixer"]
        for ref_name, prog in [("wq", mix["q_proj"]["w"]), ("wkv_a", mix["kv_down"]["w"]),
                               ("wkv_b", mix["kv_up"]["w"]), ("wo", mix["o"]["w"])]:
            np.testing.assert_array_equal(f32(w[ref_name]), f32(prog))
        ffn = blk["ffn"]
        if moe:
            np.testing.assert_array_equal(f32(w["router"]), f32(ffn["router"]["w"]))
            for name in ("up", "gate", "down"):
                np.testing.assert_array_equal(f32(w[name]), f32(ffn[name]))
                np.testing.assert_array_equal(f32(w["shared"][name]),
                                              f32(ffn["shared"][name]["w"]))
        else:
            for name in ("up", "gate", "down"):
                np.testing.assert_array_equal(f32(w["ffn"][name]), f32(ffn[name]["w"]))


def test_control_is_not_correct(tiny_root, jax_cpu):
    spec = harness.resolve_cell(tiny_root, CELL)
    driver = harness.load_module(spec["driver"], "chipbench_driver")
    numbers = driver.control(spec["config"], spec["traffic"], 11)
    numbers["window_compiles"] = 0
    checks = harness.judge(numbers, spec["limits"])
    assert not harness.all_within(checks), json.dumps(checks)


@pytest.mark.parametrize("fault", faults_gateway.FAULTS)
def test_fault_is_not_correct(tiny_root, jax_cpu, fault):
    with faults_gateway.planted(fault):
        out = run_cell(tiny_root, CELL, jax_cpu, seed=13)
    assert not out["correct"], json.dumps(out["checks"])


def _c():
    return {"D": 4, "V": 10, "H": 2, "C": 3, "dn": 2, "dr": 2, "dv": 2,
            "F_dense": 5, "F": 3, "E": 6, "K": 2, "shared": 2, "layers": 3,
            "dense_layers": 1, "held": 2}


def test_counts_by_hand():
    c = _c()
    # q 4*2*4 + kv_a 4*5 + kv_b 3*2*4 + o 2*2*4 = 32 + 20 + 24 + 16
    assert flops_lm.attn_proj_macs(c) == 92
    # dense 3*4*5; per MoE layer router 4*6 and shared 3*4*6, two layers
    assert flops_lm.ffn_macs(c) == 60 + 2 * (24 + 72)
    assert flops_lm.routed(c, 7) == 2 * 7 * 36
    # L = 3: 6 causal pairs; scores 2*4 and values 2*2 MACs a pair a layer
    assert flops_lm.prefill(c, 3) == 2 * (3 * (3 * 92 + 8 * 6 + 4 * 6)
                                          + 3 * 252 + 40)
    # absorbed decode: q 32, kv_a 20, W_uk and W_uv 2*2*2*3, o 16; per
    # attended position 2*5 + 2*3
    assert flops_lm.decode(c, 2, 9) == 2 * (2 * (3 * 92 + 252 + 40) + 3 * 16 * 9)
    w = flops_lm.weight_bytes(c)
    assert w["expert"] == 2 * 36 and w["experts_held"] == 2 * 2 * 2 * 36
    assert w["router"] == 4 * 2 * 24
    fixed = w["head"] + w["attention"] + w["dense"] + w["router"] + w["shared"]
    # 5 tokens, 3 (layer, expert) pairs touched, 40 positions of 3 layers x 5
    assert flops_lm.decode_step_bytes(c, 5, 3, 40) == fixed + 5 * 8 + 3 * 72 + 40 * 30


def test_paper_sizes_weigh_what_the_program_holds():
    with open(CONFIG) as f:
        c = reference_lm.shapes(json.load(f))
    w = flops_lm.weight_bytes(c)
    total = sum(v for k, v in w.items() if k != "expert")
    assert total == 9_827_507_200
    assert w["experts_held"] + w["shared"] > 0.8 * total


# A 200 ns window: decode steps at 10-60 and 100-150, a prefill at
# 160-190 (one outside the window at 190-230); the device busy 20-50,
# 110-140 and 165-185.
EVENTS = ([(0, 200, "bench.window"), (10, 60, "engine.decode_step"),
           (100, 150, "engine.decode_step"), (160, 190, "engine.prefill"),
           (190, 230, "engine.prefill")],
          {"/device:TPU:0": [(20, 50), (110, 140), (165, 185), (195, 220)]})


def test_engine_spans_hold_their_device_time():
    r = engine_trace.reduce_events(*EVENTS)
    assert r["window_s"] == pytest.approx(200 * ns)
    assert r["engine.decode_step"] == {"spans": 2, "device_s": pytest.approx(60 * ns)}
    assert r["engine.prefill"] == {"spans": 1, "device_s": pytest.approx(20 * ns)}
    assert engine_trace.reduce_events(EVENTS[0][1:], EVENTS[1]) is None
    assert engine_trace.reduce_events(EVENTS[0], {}) is None


@pytest.mark.parametrize("name", ["gateway_step_ms", "gateway_prefill_ms",
                                  "hbm_share.gateway_decode", "device_idle.gateway"])
def test_readers_read_nothing_without_a_trace(name):
    reader = harness.load_module(os.path.join(ROOT, "chipbench", "metrics",
                                              name + ".py"), "r")
    assert reader.read({"trace": None, "run": {"units": 5, "hbm_bytes_per_s": 1.0,
                                               "decode_bytes_per_step": 1.0}}) is None
