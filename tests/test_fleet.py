"""Fleet serving twin (DESIGN.md §11): same-seed determinism pin, request
conservation, histogram quantiles, cloud-fallback semantics, scenario
traffic scaling, and checkpointed policy deployment bit-identity."""
import dataclasses
import re

import jax
import numpy as np
import pytest

from repro.checkpoint import load_train_state, save_train_state
from repro.core import (EnvCfg, T2DRLCfg, eval_t2drl, export_policy,
                        greedy_frame_cache, t2drl_init, t2drl_init_batch,
                        train_t2drl)
from repro.fleet import FleetCfg, latency_quantiles, simulate_fleet
from repro.scenarios import build_scenario

ENV = EnvCfg(U=4, M=4, T=3, K=3)
CFG = T2DRLCfg(env=ENV, warmup=5, lr_actor=1e-4, lr_critic=1e-4,
               lr_ddqn=1e-3, L=2, eps_decay_episodes=4, seed=0)
RCARS = T2DRLCfg(env=ENV, allocator="rcars", cacher="random", L=2, seed=0)
FCFG = FleetCfg(ticks_per_slot=5, arrivals_per_user_s=0.5)

SCALARS = ("requests", "admitted", "dropped", "truncated", "slo_viol_rate",
           "deadline_miss_rate", "mean_latency_s", "mean_wait_s", "p50_s",
           "p95_s", "p99_s", "end_backlog_s", "mean_backlog_s")


@pytest.fixture(scope="module")
def ts_t2drl():
    ts, _ = train_t2drl(CFG, episodes=2)
    return ts


@pytest.fixture(scope="module")
def ts_rcars():
    k_init, _ = jax.random.split(jax.random.PRNGKey(RCARS.seed))
    return t2drl_init(k_init, RCARS)


@pytest.fixture(scope="module")
def fleet_res(ts_t2drl):
    return simulate_fleet(ts_t2drl, CFG, FCFG, num_cells=2, seed=3)


# -- determinism + conservation -----------------------------------------------

def test_same_seed_determinism_pin(ts_t2drl, fleet_res):
    again = simulate_fleet(ts_t2drl, CFG, FCFG, num_cells=2, seed=3)
    for k in SCALARS:
        assert fleet_res[k] == again[k], k
    np.testing.assert_array_equal(fleet_res["hist"], again["hist"])
    np.testing.assert_array_equal(fleet_res["backlog_curve"],
                                  again["backlog_curve"])


def test_different_seed_changes_traffic(ts_t2drl, fleet_res):
    other = simulate_fleet(ts_t2drl, CFG, FCFG, num_cells=2, seed=4)
    assert other["requests"] != fleet_res["requests"]


def test_request_conservation(fleet_res):
    # every truncation-surviving arrival is either admitted or dropped,
    # and every admitted request contributed one histogram entry
    assert fleet_res["requests"] == pytest.approx(
        fleet_res["admitted"] + fleet_res["dropped"])
    assert fleet_res["hist"].sum() == pytest.approx(fleet_res["admitted"])
    assert fleet_res["requests"] > 0


def test_backlog_curve_shape_and_positivity(fleet_res):
    assert fleet_res["backlog_curve"].shape == (2, ENV.T * ENV.K)
    assert fleet_res["peak_backlog_s"] >= fleet_res["mean_backlog_s"] >= 0.0


# -- histogram quantiles ------------------------------------------------------

def test_latency_quantiles_interpolation():
    hist = np.zeros(10)
    hist[2] = 100.0                      # all mass in [2, 3) of [0, 10)
    q = latency_quantiles(hist, 10.0, qs=(0.5,))
    assert q[0.5] == pytest.approx(2.5)


def test_latency_quantiles_overflow_and_empty():
    hist = np.zeros(10)
    hist[-1] = 5.0                       # all mass in the overflow bin
    assert latency_quantiles(hist, 10.0, qs=(0.99,))[0.99] == 10.0
    assert np.isnan(latency_quantiles(np.zeros(4), 1.0, qs=(0.5,))[0.5])


def test_latency_quantiles_single_interior_bucket():
    # all mass in one interior bin: quantiles interpolate linearly
    # within that bin's edges
    hist = np.zeros(4)
    hist[1] = 8.0                        # [1, 2) of [0, 4)
    q = latency_quantiles(hist, 4.0, qs=(0.25, 0.5, 0.75))
    assert q[0.25] == pytest.approx(1.25)
    assert q[0.5] == pytest.approx(1.5)
    assert q[0.75] == pytest.approx(1.75)


def test_latency_quantiles_one_bin_histogram():
    # a 1-bin histogram is all overflow: any mass reports hist_max;
    # no mass still reports NaN, not hist_max
    assert latency_quantiles(np.array([3.0]), 7.0, qs=(0.5,))[0.5] == 7.0
    assert np.isnan(latency_quantiles(np.array([0.0]), 7.0, qs=(0.5,))[0.5])


def test_frame_series_shapes_and_bounds(fleet_res):
    """The per-frame telemetry series (DESIGN.md §15): one entry per
    frame, rates in [0, 1], ordered quantiles where defined."""
    fr = fleet_res["frames"]
    assert fr["frame"] == list(range(ENV.T))
    for k in ("p50_s", "p95_s", "p99_s", "drop_rate", "slo_viol_rate",
              "mean_backlog_s"):
        assert len(fr[k]) == ENV.T, k
    for t in range(ENV.T):
        assert 0.0 <= fr["drop_rate"][t] <= 1.0
        assert 0.0 <= fr["slo_viol_rate"][t] <= 1.0
        assert fr["mean_backlog_s"][t] >= 0.0
        p50, p95, p99 = fr["p50_s"][t], fr["p95_s"][t], fr["p99_s"][t]
        if not np.isnan(p50):            # NaN = no admissions this frame
            assert p50 <= p95 <= p99


# -- policy export ------------------------------------------------------------

def test_export_policy_contents(ts_t2drl, ts_rcars):
    pol = export_policy(ts_t2drl, CFG)
    assert set(pol) == {"actor", "ddqn"}
    assert set(pol["ddqn"]) == {"q"}     # online net only, no target/opt
    assert export_policy(ts_rcars, RCARS) == {}


def test_export_policy_cell_selects_independent_learner():
    k_init, _ = jax.random.split(jax.random.PRNGKey(CFG.seed))
    ts = t2drl_init_batch(k_init, CFG, 2)       # policy="independent"
    for cell in (0, 1):
        pol = export_policy(ts, CFG, cell=cell)
        for a, b in zip(jax.tree.leaves(pol["actor"]),
                        jax.tree.leaves(ts["d3pg"]["actor"])):
            np.testing.assert_array_equal(np.asarray(a),
                                          np.asarray(b[cell]))
    p0 = jax.tree.leaves(export_policy(ts, CFG, cell=0)["actor"])
    p1 = jax.tree.leaves(export_policy(ts, CFG, cell=1)["actor"])
    assert any(not np.array_equal(a, b) for a, b in zip(p0, p1))


def test_greedy_entry_points_match_training_primitives(ts_t2drl):
    """Serving-side dispatch pin (DESIGN.md §11 'same amenders' contract):
    greedy_slot_action / greedy_frame_cache must compose exactly the
    primitives the training episode uses at eps = sigma = 0, for every
    allocator/cacher branch."""
    from repro.core import (actor_act, amend_actions, amend_caching,
                            ddqn_act, greedy_frame_cache,
                            greedy_slot_action, make_actor_schedule,
                            observe)
    from repro.core.baselines import (ga_allocate, random_cache,
                                      rcars_allocate, static_popular_cache)
    from repro.core.env import env_reset, env_set_cache
    models = ts_t2drl["models"]
    env = env_set_cache(env_reset(jax.random.PRNGKey(7), ENV),
                        static_popular_cache(models, ENV))
    ka = jax.random.PRNGKey(8)
    pol = export_policy(ts_t2drl, CFG)
    # d3pg allocator: actor -> amender, no exploration noise
    d3 = CFG.d3pg_cfg()
    raw = actor_act(pol["actor"], d3, make_actor_schedule(d3),
                    observe(env, ENV, models, None), ka)
    b_ref, xi_ref = amend_actions(raw, env.req, env.rho, ENV.U)
    b, xi = greedy_slot_action(pol, CFG, env, models, ka)
    np.testing.assert_array_equal(np.asarray(b), np.asarray(b_ref))
    np.testing.assert_array_equal(np.asarray(xi), np.asarray(xi_ref))
    # rcars / schrs allocators
    b, xi = greedy_slot_action({}, RCARS, env, models, ka)
    b_ref, xi_ref = rcars_allocate(env, ENV)
    np.testing.assert_array_equal(np.asarray(b), np.asarray(b_ref))
    np.testing.assert_array_equal(np.asarray(xi), np.asarray(xi_ref))
    schrs = dataclasses.replace(RCARS, allocator="schrs", cacher="static")
    b, xi = greedy_slot_action({}, schrs, env, models, ka)
    b_ref, xi_ref = ga_allocate(ka, env, ENV, models, schrs.ga)
    np.testing.assert_array_equal(np.asarray(b), np.asarray(b_ref))
    np.testing.assert_array_equal(np.asarray(xi), np.asarray(xi_ref))
    # ddqn cacher at eps = 0, static, random
    dq = CFG.ddqn_cfg()
    a_int = ddqn_act(pol["ddqn"], dq, env.gamma_idx, ka, 0.0)
    rho_ref = amend_caching(a_int, dq, models.c, ENV.C)
    np.testing.assert_array_equal(
        np.asarray(greedy_frame_cache(pol, CFG, models, env.gamma_idx, ka)),
        np.asarray(rho_ref))
    np.testing.assert_array_equal(
        np.asarray(greedy_frame_cache({}, schrs, models, env.gamma_idx, ka)),
        np.asarray(static_popular_cache(models, ENV)))
    np.testing.assert_array_equal(
        np.asarray(greedy_frame_cache({}, RCARS, models, env.gamma_idx, ka)),
        np.asarray(random_cache(ka, models, ENV)))


@pytest.mark.parametrize("allocator", ["d3pg", "rcars", "schrs"])
def test_greedy_slot_action_returns_one_buffer(ts_t2drl, allocator):
    """The public greedy entry returns ``(b, xi)`` as the rows of one
    ``(2, U)`` array, bitwise the allocator's own ``greedy`` tuple, and
    its jitted executable has one non-tuple result (DESIGN.md §11): one
    output buffer and one copy-back per decision."""
    from repro.agents import make_allocator
    from repro.agents.base import SlotObs
    from repro.core import greedy_slot_action, observe
    from repro.core.env import env_reset
    cfg = {"d3pg": CFG, "rcars": RCARS,
           "schrs": dataclasses.replace(RCARS, allocator="schrs",
                                        cacher="static")}[allocator]
    pol = export_policy(ts_t2drl, CFG) if allocator == "d3pg" else {}
    models = ts_t2drl["models"]
    env = env_reset(jax.random.PRNGKey(7), ENV)
    ka = jax.random.PRNGKey(8)
    out = greedy_slot_action(pol, cfg, env, models, ka)
    assert out.shape == (2, ENV.U) and out.dtype == np.float32
    alloc = make_allocator(cfg.allocator, cfg.env, cfg.d3pg_cfg(), cfg.ga)
    s = observe(env, ENV, models, None) if alloc.learns else None
    b_ref, xi_ref = alloc.greedy(pol, SlotObs(s, env, models, None), ka)
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(b_ref))
    np.testing.assert_array_equal(np.asarray(out[1]), np.asarray(xi_ref))
    hlo = jax.jit(greedy_slot_action, static_argnames="cfg").lower(
        pol, cfg, env, models, ka).compile().as_text()
    (result,) = re.findall(r"^ENTRY .*\) -> (.+) \{$", hlo, re.MULTILINE)
    assert result == f"f32[2,{ENV.U}]"


def test_unregistered_namedtuple_raises_clear_error(tmp_path):
    from repro.core import SlotMod
    bad = {"mod": SlotMod(h_scale=np.float32(1.0), din_scale=np.float32(1.0),
                          burst_prob=np.float32(0.0),
                          burst_model=np.int32(0))}
    with pytest.raises(TypeError, match="unregistered NamedTuple"):
        save_train_state(str(tmp_path / "bad.msgpack"), bad)


# -- checkpointed deployment --------------------------------------------------

def test_checkpoint_roundtrip_bit_identity(tmp_path, ts_t2drl, fleet_res):
    """train -> save -> load -> eval/serve is bit-identical to the live
    state (the ISSUE 3 save->load->eval pin)."""
    path = save_train_state(str(tmp_path / "t2drl.msgpack"), ts_t2drl,
                            meta={"method": "t2drl", "seed": CFG.seed})
    back, meta = load_train_state(path)
    assert meta["method"] == "t2drl" and meta["seed"] == CFG.seed
    assert type(back["models"]).__name__ == "ModelParams"
    for a, b in zip(jax.tree.leaves(ts_t2drl), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    ev_live = eval_t2drl(ts_t2drl, CFG, episodes=2)
    ev_back = eval_t2drl(back, CFG, episodes=2)
    for k in ev_live:
        assert float(ev_live[k]) == float(ev_back[k]), k
    served = simulate_fleet(back, CFG, FCFG, num_cells=2, seed=3)
    for k in SCALARS:
        assert served[k] == fleet_res[k], k
    np.testing.assert_array_equal(served["hist"], fleet_res["hist"])


def test_batched_shared_train_state_roundtrip_bit_identity(tmp_path):
    """The unified TrainState layout (DESIGN.md §12) checkpoints uniformly
    across vector-env modes: a batched shared-learner state (per-cell
    models/buffers, single learner) restores bit-identically and evaluates
    identically — no agent-kind or layout special-casing in the codec."""
    cfg = dataclasses.replace(CFG, policy="shared")
    ts, _ = train_t2drl(cfg, episodes=2, num_envs=2)
    path = save_train_state(str(tmp_path / "shared.msgpack"), ts,
                            meta={"policy": "shared", "num_envs": 2})
    back, meta = load_train_state(path)
    assert meta["num_envs"] == 2
    assert set(back) == {"models", "d3pg", "ddqn", "ebuf", "fbuf", "cache"}
    for a, b in zip(jax.tree.leaves(ts), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    ev_live = eval_t2drl(ts, cfg, episodes=2)
    ev_back = eval_t2drl(back, cfg, episodes=2)
    for k in ev_live:
        assert float(ev_live[k]) == float(ev_back[k]), k
    # the exported policy slice is identical too (shared learner: no cell
    # slicing), and serves through the twin deterministically
    pol_live = export_policy(ts, cfg)
    pol_back = export_policy(back, cfg)
    for a, b in zip(jax.tree.leaves(pol_live), jax.tree.leaves(pol_back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    r1 = simulate_fleet(ts, cfg, FCFG, seed=2)
    r2 = simulate_fleet(back, cfg, FCFG, seed=2)
    for k in SCALARS:
        assert r1[k] == r2[k], k


def test_arc_policy_checkpoint_roundtrip(tmp_path):
    """Classical-cacher deployment pin (DESIGN.md §14): train an ARC
    baseline, checkpoint it, restore it, and serve through the twin —
    the frozen resident set survives the round trip bit-identically and
    the restored state serves the exact same traffic outcome."""
    cfg = dataclasses.replace(RCARS, cacher="arc")
    ts, _ = train_t2drl(cfg, episodes=2)
    path = save_train_state(str(tmp_path / "arc.msgpack"), ts,
                            meta={"method": "cacher-arc"})
    back, meta = load_train_state(path)
    assert meta["method"] == "cacher-arc"
    for a, b in zip(jax.tree.leaves(ts), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # exported policy is the frozen resident set, identical across the trip
    pol_live = export_policy(ts, cfg)
    pol_back = export_policy(back, cfg)
    assert set(pol_live) == {"cache"}
    rho_live = np.asarray(pol_live["cache"]["rho"])
    np.testing.assert_array_equal(rho_live,
                                  np.asarray(pol_back["cache"]["rho"]))
    assert rho_live.shape == (ENV.M,)
    assert set(np.unique(rho_live)) <= {0.0, 1.0}
    # the greedy serving entry point reads that set verbatim
    kf = jax.random.PRNGKey(11)
    gi = jax.numpy.zeros((ENV.M,), jax.numpy.int32)
    np.testing.assert_array_equal(
        np.asarray(greedy_frame_cache(pol_back, cfg, ts["models"], gi, kf)),
        rho_live)
    r1 = simulate_fleet(ts, cfg, FCFG, num_cells=1, seed=6)
    r2 = simulate_fleet(back, cfg, FCFG, num_cells=1, seed=6)
    for k in SCALARS:
        assert r1[k] == r2[k], k
    np.testing.assert_array_equal(r1["hist"], r2["hist"])


def test_load_rejects_unknown_format(tmp_path):
    import msgpack
    p = tmp_path / "bad.msgpack"
    p.write_bytes(msgpack.packb({"format": 99, "state": {}}))
    with pytest.raises(ValueError, match="format"):
        load_train_state(str(p))


# -- queueing semantics -------------------------------------------------------

def test_uncached_requests_take_cloud_path_without_queueing(ts_rcars):
    """With zero cache capacity every request goes to the cloud: no edge
    backlog, no queueing wait, no drops — latency is transmission +
    cloud compute only."""
    env0 = dataclasses.replace(ENV, C=0.0)
    cfg0 = dataclasses.replace(RCARS, env=env0)
    k_init, _ = jax.random.split(jax.random.PRNGKey(0))
    ts = t2drl_init(k_init, cfg0)
    res = simulate_fleet(ts, cfg0, FCFG, num_cells=1, seed=0)
    assert res["requests"] > 0
    assert res["dropped"] == 0.0
    assert res["mean_wait_s"] == 0.0
    assert res["end_backlog_s"] == 0.0
    assert res["peak_backlog_s"] == 0.0
    assert res["mean_latency_s"] > 0.0


def test_population_scales_offered_load(ts_rcars):
    """user_counts modulates each cell's arrival rate (fleet 'populations
    are traffic' contract): 4 active users >> 1 active user."""
    lo = simulate_fleet(ts_rcars, RCARS, FCFG, num_cells=2, seed=5,
                        user_counts=(1, 1))
    hi = simulate_fleet(ts_rcars, RCARS, FCFG, num_cells=2, seed=5,
                        user_counts=(4, 4))
    assert hi["requests"] > 2.0 * lo["requests"]


def test_scenario_schedule_is_a_traffic_trace(ts_rcars):
    """A registered scenario drives the twin: flash-crowd's burst schedule
    concentrates arrivals on the hot model and raises offered load
    (din_scale doubles as the load multiplier, DESIGN.md §11)."""
    b = build_scenario("flash-crowd", ENV, num_envs=2)
    res = simulate_fleet(ts_rcars, RCARS, FCFG, num_cells=2, seed=5,
                         mods=b.mods)
    base = simulate_fleet(ts_rcars, RCARS, FCFG, num_cells=2, seed=5)
    assert res["requests"] != base["requests"]
    assert res["requests"] > 0 and base["requests"] > 0


def test_truncation_is_counted_not_silent(ts_rcars):
    stress = FleetCfg(ticks_per_slot=5, arrivals_per_user_s=50.0,
                      max_arrivals=4)
    res = simulate_fleet(ts_rcars, RCARS, stress, num_cells=1, seed=0)
    assert res["truncated"] > 0.0
    assert res["requests"] == pytest.approx(res["admitted"]
                                            + res["dropped"])


# -- batched train states -----------------------------------------------------

def test_batched_ts_fixes_fleet_size(tmp_path):
    cfg = dataclasses.replace(CFG, policy="shared")
    k_init, _ = jax.random.split(jax.random.PRNGKey(cfg.seed))
    ts = t2drl_init_batch(k_init, cfg, 2)
    res = simulate_fleet(ts, cfg, FCFG, seed=0)     # C defaults to B=2
    assert res["num_cells"] == 2
    with pytest.raises(ValueError, match="batched over 2 cells"):
        simulate_fleet(ts, cfg, FCFG, num_cells=3, seed=0)
