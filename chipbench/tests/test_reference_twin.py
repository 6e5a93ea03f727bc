"""The twin's plain reference, worked by hand: the queue recursion of one
tick, and the channel draw that the reference floors at the smallest
positive fade."""
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness, reference, reference_twin
from chipbench.drivers import twin
from chipbench.tests.conftest import ROOT, tiny_config

F = {"max_arrivals": 8, "queue_cap": 64.0, "slo": 40.0}


def _tick(work, n_raw, serv, trans, cached, dt_tick=1.0, tau=20.0):
    arr = lambda x: jnp.asarray([x], jnp.float32)
    out = reference_twin.tick(arr(work), arr(n_raw), arr(serv), arr(trans),
                              jnp.asarray([cached]), F, dt_tick, tau)
    new, counts, lat, wait, served = (np.asarray(x) if not isinstance(x, dict)
                                      else {k: float(v) for k, v in x.items()}
                                      for x in out)
    return new[0], counts, lat[0], wait[0], served[0]


def test_same_tick_arrivals_wait_in_line(jax_cpu):
    """Three arrivals on a backlog W with service s wait W, W+s, W+2s; the
    backlog grows by their work and drains by one tick."""
    W, s, tr = 5.0, 2.0, 3.0
    new, counts, lat, wait, served = _tick(W, 3, s, tr, True)
    assert served.tolist() == [True] * 3 + [False] * 5
    np.testing.assert_allclose(wait[:3], [W, W + s, W + 2 * s])
    np.testing.assert_allclose(lat[:3], [tr + W + s, tr + W + 2 * s, tr + W + 3 * s])
    assert new == pytest.approx(W + 3 * s - 1.0)
    assert counts["arrivals"] == 3 and counts["admitted"] == 3
    assert counts["dropped"] == 0 and counts["truncated"] == 0
    assert counts["lat_sum"] == pytest.approx(float(lat[:3].sum()))
    assert counts["wait_sum"] == pytest.approx(3 * W + 3 * s)


def test_queue_cap_drops_and_max_arrivals_truncates(jax_cpu):
    s = 2.0
    new, counts, _, _, served = _tick(62 * s, 3, s, 1.0, True)
    assert counts["admitted"] == 2 and counts["dropped"] == 1
    assert served.sum() == 2
    assert new == pytest.approx(64 * s - 1.0)
    _, counts, _, _, served = _tick(0.0, 11, s, 1.0, True)
    assert counts["arrivals"] == 8 and counts["truncated"] == 3
    assert counts["admitted"] == 8 and served.sum() == 8


def test_cloud_path_has_no_queue(jax_cpu):
    """An uncached model serves every arrival at once, with no wait, no
    drop and no work added; an evicted model's backlog keeps draining."""
    W, s, tr = 200.0, 30.0, 4.0
    new, counts, lat, wait, served = _tick(W, 3, s, tr, False)
    assert served.sum() == 3 and counts["dropped"] == 0
    np.testing.assert_allclose(wait[:3], 0.0)
    np.testing.assert_allclose(lat[:3], tr + s)
    assert counts["slo_viol"] == 0 and counts["deadline_miss"] == 3
    assert new == pytest.approx(W - 1.0)


def _cfg():
    with open(os.path.join(ROOT, "chipbench", "configs", "t2drl-paper.json")) as f:
        return reference.shapes(tiny_config(json.load(f)))


def test_ordinary_draws_unchanged(jax_cpu):
    """Every nonzero fade is drawn as before, bit for bit."""
    c = _cfg()
    bs = jnp.array([c["area"] / 2, c["area"] / 2])
    for i in range(20):
        kp, kh = jax.random.split(jax.random.PRNGKey(i))
        pos = jax.random.uniform(kp, (64, 2), minval=0.0, maxval=c["area"])
        dis_km = jnp.maximum(jnp.linalg.norm(pos - bs, axis=-1), 1.0) / 1000.0
        g = 10.0 ** ((-128.1 - 37.6 * jnp.log10(dis_km)) / 10.0)
        plain = g * jax.random.exponential(kh, (64,))
        assert np.all(np.asarray(plain) > 0)
        np.testing.assert_array_equal(np.asarray(reference._gain(kh, pos, c)),
                                      np.asarray(plain))


def test_zero_fade_stays_finite(jax_cpu, monkeypatch):
    """With every uniform behind the fade forced to 0, the reference's
    channel, delays, reward and a whole twin horizon stay finite, and the
    fade is the smallest positive one, -log1p(-2^-23)."""
    c = _cfg()
    monkeypatch.setattr(jax.random, "exponential",
                        lambda key, shape, dtype=jnp.float32: jnp.zeros(shape, dtype))
    reference_twin._jitted.cache_clear()   # no program traced before the patch
    env = reference.env_reset(jax.random.PRNGKey(3), c)
    pos = env["pos"]
    bs = jnp.array([c["area"] / 2, c["area"] / 2])
    dis_km = jnp.maximum(jnp.linalg.norm(pos - bs, axis=-1), 1.0) / 1000.0
    g = 10.0 ** ((-128.1 - 37.6 * jnp.log10(dis_km)) / 10.0)
    np.testing.assert_allclose(np.asarray(env["h"]),
                               np.asarray(g) * -math.log1p(-2.0 ** -23), rtol=1e-6)
    assert np.all(np.asarray(env["h"]) > 0)
    st = reference.init_cell(jax.random.PRNGKey(4), c, jnp.float32)
    U = c["U"]
    b = jnp.full(U, 1.0 / U)
    env = {**env, "rho": jnp.ones(c["M"])}
    _, r, m = reference.env_step(env, c, st["models"], b, b)
    for k in ("d_up", "d_dw", "d_gt", "d_tl"):
        assert np.all(np.isfinite(np.asarray(m[k]))), k
    assert np.isfinite(float(r))
    f = {**F, "ticks_per_slot": 2, "arrivals_per_user_s": 0.5,
         "hist_bins": 16, "hist_max": 240.0}
    rho = np.ones((c["J"], c["M"]), np.float32)
    out = reference_twin.fleet(c, f, st, rho, 7, 2)
    assert out["arrivals"] > 0
    assert np.isfinite(out["lat_sum"]) and np.isfinite(out["wait_sum"])
    assert np.all(np.isfinite(out["backlog_curve"]))
    reference_twin._jitted.cache_clear()   # none traced with the patch


def test_non_finite_call_is_not_correct():
    """A call whose latency sum is NaN reads NaN, which no limit passes."""
    ref = {"arrivals": 10, "truncated": 0, "admitted": 10, "dropped": 0,
           "slo_viol": 1, "deadline_miss": 2, "lat_sum": 300.0,
           "wait_sum": 40.0, "hist": np.ones(4), "backlog_curve": np.ones((2, 3))}
    bad = {**ref, "lat_sum": float("nan")}
    numbers = twin.compare([ref, bad, ref], [ref, ref, ref], 0.0, 1.0)
    assert math.isnan(numbers["latency_gap"])
    limits = {"limits": {k: {"limit": 1.0} for k in numbers}}
    assert not harness.all_within(harness.judge(numbers, limits))
    assert twin.is_failed({"mean_latency_s": float("nan"), "mean_wait_s": 1.0,
                           "p50_s": 1.0, "p95_s": 1.0, "p99_s": 1.0,
                           "requests": 1, "admitted": 1, "dropped": 0,
                           "truncated": 0})
