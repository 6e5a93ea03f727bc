"""Telemetry subsystem (DESIGN.md §15): off-by-default bit-identity,
in-scan learner diagnostics, JSONL schema validation, run manifests,
the recompile counter, the ragged-final-chunk compile pin, and the
runtime's host events that the benchmark reads per decision."""
import dataclasses
import json
import warnings

import jax
import numpy as np
import pytest

from repro.core import (EnvCfg, EnvState, T2DRLCfg, env_reset, export_policy,
                        greedy_slot_action, t2drl_init, train_t2drl)
from repro.fleet import FleetCfg, simulate_fleet
from repro.obs import (MetricWriter, ObsCfg, compile_events, progress_line,
                       reset_compiles, run_manifest, validate_jsonl,
                       validate_record)

# Small enough for CI but busy enough that both learners actually update:
# warmup=0 opens the slot-learner gate immediately and (T-1)*episodes = 40
# stored frame transitions clear the DDQN batch-size gate (32) with room.
ENV = EnvCfg(U=3, M=3, T=5, K=2)
OBS_CFG = T2DRLCfg(env=ENV, warmup=0, lr_actor=1e-4, lr_critic=1e-3,
                   lr_ddqn=1e-3, L=2, eps_decay_episodes=8, seed=0,
                   obs=ObsCfg(enabled=True))

DIAG_KEYS = (
    # D3PG allocator taps
    "diag/actor_loss", "diag/critic_loss", "diag/actor_grad_norm",
    "diag/critic_grad_norm", "diag/q_mean", "diag/td_abs_mean",
    "diag/td_abs_max", "diag/denoise_mag", "diag/updates",
    # DDQN cacher taps
    "diag/ddqn_loss", "diag/ddqn_q_mean", "diag/ddqn_q_max",
    "diag/ddqn_td_abs_mean", "diag/ddqn_td_abs_max", "diag/ddqn_grad_norm",
    "diag/ddqn_target_div", "diag/ddqn_updates",
    # replay occupancy
    "diag/ebuf_size", "diag/ebuf_fill", "diag/fbuf_size", "diag/fbuf_fill",
)


@pytest.fixture(scope="module")
def obs_hist():
    _, hist = train_t2drl(OBS_CFG, episodes=10)
    return hist


# -- ObsCfg gating ------------------------------------------------------------

def test_obs_cfg_gating_properties():
    assert not ObsCfg().learner_on and not ObsCfg().replay_on
    on = ObsCfg(enabled=True)
    assert on.learner_on and on.replay_on
    assert not ObsCfg(enabled=True, learner=False).learner_on
    assert not ObsCfg(enabled=True, replay=False).replay_on
    # master switch dominates the per-tap flags
    assert not ObsCfg(enabled=False, learner=True).learner_on


def test_all_taps_off_is_bit_identical_to_disabled():
    """enabled=True with every tap flag off gates out all tap sites at
    the python level — the compiled program (and its history) must be
    bit-identical to obs disabled."""
    off = dataclasses.replace(OBS_CFG, obs=ObsCfg(enabled=False))
    none = dataclasses.replace(OBS_CFG, obs=ObsCfg(enabled=True,
                                                   learner=False,
                                                   replay=False))
    _, h_off = train_t2drl(off, episodes=2)
    _, h_none = train_t2drl(none, episodes=2)
    assert set(h_off) == set(h_none)
    assert not any(k.startswith("diag/") for k in h_off)
    for k in h_off:
        np.testing.assert_array_equal(np.asarray(h_off[k]),
                                      np.asarray(h_none[k]))


# -- in-scan learner diagnostics ----------------------------------------------

def test_telemetry_on_emits_learner_diagnostics(obs_hist):
    for k in DIAG_KEYS:
        assert k in obs_hist, k
        assert np.all(np.isfinite(np.asarray(obs_hist[k]))), k
    # every slot cleared the warmup gate, so the allocator updated each
    # of the T*K slots; the DDQN updates once per frame past buffer fill
    assert float(np.asarray(obs_hist["diag/updates"])[-1]) == ENV.T * ENV.K
    assert float(np.asarray(obs_hist["diag/ddqn_updates"])[-1]) > 0
    # masked maxima bound the matching means wherever an update ran
    td_mean = np.asarray(obs_hist["diag/ddqn_td_abs_mean"])
    td_max = np.asarray(obs_hist["diag/ddqn_td_abs_max"])
    did = np.asarray(obs_hist["diag/ddqn_updates"]) > 0
    assert np.all(td_max[did] >= td_mean[did] - 1e-6)
    # denoise magnitudes keep the per-denoising-step axis (L,)
    assert np.asarray(obs_hist["diag/denoise_mag"]).shape[-1] == OBS_CFG.L


def test_replay_occupancy_grows_and_respects_capacity(obs_hist):
    fill = np.asarray(obs_hist["diag/fbuf_fill"])
    size = np.asarray(obs_hist["diag/fbuf_size"])
    assert np.all(np.diff(size) >= 0)           # fills monotonically
    assert size[-1] > size[0]
    assert np.all((fill >= 0.0) & (fill <= 1.0))
    assert np.all(np.asarray(obs_hist["diag/ebuf_fill"]) <= 1.0)


def test_batched_cores_emit_per_cell_diagnostics():
    """Both vector-env modes carry diag keys with the standard leading
    (episodes, B) history layout — pooled shared-learner scalars are
    broadcast across cells, fused independent learners are per-cell."""
    for policy in ("shared", "independent"):
        cfg = dataclasses.replace(OBS_CFG, policy=policy)
        _, hist = train_t2drl(cfg, episodes=2, num_envs=2)
        for k in ("diag/updates", "diag/ddqn_loss", "diag/fbuf_size"):
            assert np.asarray(hist[k]).shape[:2] == (2, 2), (policy, k)
        mag = np.asarray(hist["diag/denoise_mag"])
        assert mag.shape == (2, 2, OBS_CFG.L), policy


# -- ragged final chunk + recompile counter -----------------------------------

def test_ragged_chunk_two_programs_and_bit_identical():
    """A log_every that does not divide episodes used to retrace a
    bespoke remainder-sized program; the fix splits the ragged tail into
    size-1 calls so a chunked run compiles exactly two training programs
    (chunk-size and 1) and stays bit-identical to the unchunked run."""
    cfg = dataclasses.replace(OBS_CFG, env=EnvCfg(U=3, M=4, T=4, K=2),
                              seed=5, obs=ObsCfg())
    _, h_ref = train_t2drl(cfg, episodes=5)
    reset_compiles()
    with warnings.catch_warnings():
        warnings.simplefilter("error")          # no retrace warning allowed
        _, h_chunk = train_t2drl(cfg, episodes=5, log_every=2)
    ev = [e for e in compile_events() if e[0].startswith("train")]
    assert len(ev) == 2, ev                     # chunk-size + size-1 tail
    assert len({s for _, s in ev}) == 2
    assert set(h_ref) == set(h_chunk)
    for k in h_ref:
        np.testing.assert_array_equal(np.asarray(h_ref[k]),
                                      np.asarray(h_chunk[k]))


# -- schema validation --------------------------------------------------------

def test_validate_record_negatives():
    ok = {"schema": "repro-obs/1", "kind": "train_chunk", "episode": 2,
          "episodes": 2, "wall_s": 0.1, "stats": {}}
    validate_record(ok)
    with pytest.raises(ValueError, match="unknown schema"):
        validate_record(dict(ok, schema="repro-obs/999"))
    with pytest.raises(ValueError, match="unknown record kind"):
        validate_record(dict(ok, kind="bogus"))
    with pytest.raises(ValueError, match="missing required fields"):
        validate_record({"schema": "repro-obs/1", "kind": "train_chunk"})
    with pytest.raises(ValueError, match="JSON object"):
        validate_record([1, 2, 3])


def test_validate_jsonl_negatives(tmp_path):
    p = tmp_path / "empty.jsonl"
    p.write_text("")
    with pytest.raises(ValueError, match="empty run log"):
        validate_jsonl(str(p))
    p = tmp_path / "no_manifest.jsonl"
    p.write_text(json.dumps({"schema": "repro-obs/1", "kind": "eval",
                             "metrics": {}}) + "\n")
    with pytest.raises(ValueError, match="first record must be a manifest"):
        validate_jsonl(str(p))
    p = tmp_path / "bad_json.jsonl"
    p.write_text("{not json\n")
    with pytest.raises(ValueError, match="invalid JSON"):
        validate_jsonl(str(p))


def test_metric_writer_validates_and_is_manifest_idempotent(tmp_path):
    path = str(tmp_path / "run.jsonl")
    with MetricWriter(path) as w:
        w.ensure_manifest(OBS_CFG, extra={"note": "t"})
        w.ensure_manifest(OBS_CFG)              # no-op: already stamped
        w.write("eval", metrics={"reward": np.float32(1.5)})
        with pytest.raises(ValueError, match="unknown record kind"):
            w.write("bogus", x=1)
        with pytest.raises(ValueError, match="missing required fields"):
            w.write("train_chunk", episode=1)
    assert validate_jsonl(path) == 2
    recs = [json.loads(l) for l in open(path)]
    assert [r["kind"] for r in recs] == ["manifest", "eval"]
    assert recs[0]["cfg_hash"] and recs[0]["note"] == "t"
    assert recs[1]["metrics"]["reward"] == 1.5  # np scalars mapped to JSON


def test_run_manifest_contents():
    rec = run_manifest(OBS_CFG, extra={"harness": "test"})
    validate_record(rec)
    assert rec["kind"] == "manifest"
    assert rec["jax"] == jax.__version__
    assert rec["seed"] == OBS_CFG.seed
    assert rec["harness"] == "test"
    # cfg hash is stable and sensitive to config changes
    other = run_manifest(dataclasses.replace(OBS_CFG, seed=1))
    assert run_manifest(OBS_CFG)["cfg_hash"] == rec["cfg_hash"]
    assert other["cfg_hash"] != rec["cfg_hash"]


def test_progress_line_matches_legacy_format():
    last = {"episode_reward": -12.345, "hit_ratio": 0.5, "utility": 3.2}
    assert progress_line(7, last) == (
        "ep    7 reward    -12.35 hit 0.500 G    3.20")


# -- runtime host events per decision ----------------------------------------

# A greedy decision reads four slot-state leaves (h, req, d_in, rho) and
# the reverse-chain key; jit drops the leaves it does not read, so each
# call sent from the host moves five arrays to the device.
DECISION_HOST_ARGS = 5


def test_decision_runtime_host_events(tmp_path):
    """The runtime's own host events per greedy decision, the names the
    benchmark's host-dispatch metrics read (PERF.md): one jitted call,
    one ``DevicePut`` per kept host argument, and one copy-back, since
    ``(b, xi)`` comes back as the rows of one array."""
    from jax.profiler import ProfileData
    cfg = T2DRLCfg(env=EnvCfg(U=4, M=4, T=2, K=2), L=2, warmup=5)
    ts = t2drl_init(jax.random.PRNGKey(0), cfg)
    policy, models = export_policy(ts, cfg), ts["models"]
    env = EnvState(*jax.device_get(env_reset(jax.random.PRNGKey(1), cfg.env)))
    key = np.asarray(jax.random.PRNGKey(2))
    fn = jax.jit(greedy_slot_action, static_argnames="cfg")
    jax.device_get(fn(policy, cfg, env, models, key))       # compile first
    calls = 3
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(calls):
            jax.device_get(fn(policy, cfg, env, models, key))
    (path,) = tmp_path.glob("**/*.xplane.pb")
    events = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
              for plane in ProfileData.from_file(str(path)).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events]
    count = lambda name: sum(n == name for n, _, _ in events)
    pjit = [(s, e) for n, s, e in events
            if n == "PjitFunction(greedy_slot_action)"]
    # the runtime nests its dispatch events; count the outermost per call
    outer = [(s, e) for s, e in pjit
             if not any(a <= s and e <= b and (a, b) != (s, e) for a, b in pjit)]
    assert len(outer) == calls
    assert count("DevicePut") == calls * DECISION_HOST_ARGS
    assert count("np.asarray(jax.Array)") == calls


# -- end-to-end run logs ------------------------------------------------------

def test_train_writer_streams_schema_valid_chunks(tmp_path):
    path = str(tmp_path / "train.jsonl")
    with MetricWriter(path) as w:
        train_t2drl(OBS_CFG, episodes=4, log_every=2, writer=w)
    n = validate_jsonl(path)
    recs = [json.loads(l) for l in open(path)]
    assert recs[0]["kind"] == "manifest"
    assert recs[0]["episodes"] == 4
    chunks = [r for r in recs if r["kind"] == "train_chunk"]
    assert [c["episode"] for c in chunks] == [2, 4]
    assert n == 1 + len(chunks)
    for c in chunks:
        assert c["wall_s"] > 0.0
        assert "episode_reward" in c["stats"]
        assert "diag/ddqn_loss" in c["stats"]   # taps ride the chunk stats
        assert len(c["stats"]["diag/denoise_mag"]) == OBS_CFG.L


def test_fleet_writer_streams_frames_and_summary(tmp_path):
    env = EnvCfg(U=4, M=4, T=3, K=3)
    cfg = T2DRLCfg(env=env, allocator="rcars", cacher="random", L=2, seed=0)
    k_init, _ = jax.random.split(jax.random.PRNGKey(cfg.seed))
    ts = t2drl_init(k_init, cfg)
    fcfg = FleetCfg(ticks_per_slot=5, arrivals_per_user_s=0.5)
    path = str(tmp_path / "fleet.jsonl")
    with MetricWriter(path) as w:
        res = simulate_fleet(ts, cfg, fcfg, num_cells=2, seed=3, writer=w,
                             tags={"scenario": "paper-default",
                                   "method": "rcars"})
    assert validate_jsonl(path) == 1 + env.T + 1
    recs = [json.loads(l) for l in open(path)]
    frames = [r for r in recs if r["kind"] == "fleet_frame"]
    assert [f["frame"] for f in frames] == list(range(env.T))
    assert all(f["method"] == "rcars" for f in frames)
    summary = [r for r in recs if r["kind"] == "fleet_summary"]
    assert len(summary) == 1
    assert summary[0]["metrics"]["requests"] == res["requests"]
    assert summary[0]["scenario"] == "paper-default"
