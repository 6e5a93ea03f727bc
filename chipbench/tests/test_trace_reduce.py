"""The trace reduction, on made-up events with known answers and on a
trace recorded on one TPU v5e chip."""
import os

import pytest

from chipbench import trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_busy_idle_and_gaps_by_host_span():
    host = [(0, 100, "bench.window"), (0, 40, "bench.dispatch"),
            (40, 90, "bench.block"), (90, 100, "bench.host")]
    f1 = "%fusion.1 = f32[8]{0:T(128)} fusion(f32[8]{0} %p), kind=kLoop"
    f2 = "%fusion.2 = f32[8]{0:T(128)} fusion(f32[8]{0} %q), kind=kLoop"
    d3 = "%dot.3 = f32[8,8]{1,0} dot(f32[8,4]{1,0} %a, f32[4,8]{1,0} %b)"
    loop = "%while.4 = (s32[]{:T(128)}, f32[8]{0}) while((s32[], f32[8]) %t)"
    ops = [(10, 30, f1), (20, 35, f2),        # overlap: union 10-35
           (50, 80, d3), (95, 120, f1),       # clipped to 95-100
           (50, 80, loop)]                    # encloses dot.3
    r = trace_reduce.reduce_events(host, {"/device:TPU:0": ops})
    ns = 1e-9
    assert r["window_s"] == pytest.approx(100 * ns)
    assert r["busy_s"] == pytest.approx((25 + 30 + 5) * ns)
    assert r["idle_share"] == pytest.approx(0.4)
    # control flow stays in the busy union, out of the top operations
    assert dict(r["top_ops"]) == pytest.approx(
        {"fusion.1 (fusion)": 25 * ns, "dot.3 (dot)": 30 * ns,
         "fusion.2 (fusion)": 15 * ns})
    # each gap goes whole to the span it overlaps most: 0-10 to dispatch;
    # 35-50 (5 under dispatch, 10 under block) and 80-95 (10 under block,
    # 5 under host) to block
    assert dict(r["idle_gaps"]) == pytest.approx(
        {"bench.dispatch": 10 * ns, "bench.block": 30 * ns})
    assert r["span_device_s"]["bench.block"] == pytest.approx(30 * ns)


def test_devices_are_averaged():
    host = [(0, 10, "bench.window")]
    r = trace_reduce.reduce_events(host, {"/device:TPU:0": [(0, 10, "a")],
                                          "/device:TPU:1": [(0, 5, "a")]})
    assert r["devices"] == 2 and r["busy_s"] == pytest.approx(7.5e-9)


def test_nothing_to_read():
    assert trace_reduce.reduce_events([], {"/device:TPU:0": [(0, 1, "a")]}) is None
    assert trace_reduce.reduce_events([(0, 10, "bench.window")], {}) is None
    assert trace_reduce.reduce_events([(0, 10, "bench.window")],
                                      {"/device:TPU:0": [(20, 30, "a")]}) is None


def test_recorded_chip_trace():
    """A short window of ``t2drl-paper.decide`` traced on one TPU v5e."""
    path = os.path.join(DATA, "decide.xplane.pb")
    r = trace_reduce.reduce(path)
    assert r is not None and r["devices"] == 1
    assert 0.0 < r["busy_s"] < r["window_s"]
    assert 0.0 < r["idle_share"] < 1.0
    assert r["top_ops"] and all(s > 0 for _, s in r["top_ops"])
    names = {n for n, _ in r["idle_gaps"]}
    assert names & {"bench.dispatch", "bench.block"}
