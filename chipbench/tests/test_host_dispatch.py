"""The runtime's host phases and the idle gaps divided by the innermost
host span, on made-up events with known answers and on traces recorded
on one TPU v5e chip; and the readers of the host-dispatch metrics."""
import os
import shutil

import pytest

from chipbench import harness, host_dispatch, trace_reduce
from chipbench.tests.conftest import ROOT

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RECORDED = os.path.join(DATA, "decide.xplane.pb")
READERS = ("decide_put_ms", "decide_launch_ms", "decide_fetch_ms",
           "decide_transfers")
ns = 1e-9

# Two decisions in a 200 ns window.  The idle gap 60-140 holds decision
# 0's copy-back (60-90), its block span's own time (90-100), and decision
# 1's dispatch span's own time (100-105, 120-125), argument transfer
# (105-120) and launch (125-140).
TWO_DECISIONS = (
    [(0, 200, "bench.window"),
     (0, 50, "bench.dispatch"), (5, 20, "DevicePut"), (20, 30, "DevicePut"),
     (32, 45, "CommonPjRtLoadedExecutable::Execute"),
     (50, 100, "bench.block"), (52, 55, "ArrayImpl.copy_to_host_async"),
     (55, 90, "np.asarray(jax.Array)"),
     (100, 150, "bench.dispatch"), (105, 120, "DevicePut"),
     (125, 140, "CommonPjRtLoadedExecutable::Execute"),
     (150, 200, "bench.block"), (150, 190, "np.asarray(jax.Array)")],
    {"/device:TPU:0": [(45, 60, "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)"),
                       (140, 150, "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)")]})


def _bench_only(host):
    return [h for h in host if h[2].startswith("bench.")]


def test_gap_is_split_across_phases_and_span_self_time():
    host, devices = TWO_DECISIONS
    r = host_dispatch.reduce_events(host, devices)
    assert dict(r["idle_gaps"]) == pytest.approx(
        {"host.put": 40 * ns, "host.launch": 28 * ns, "host.fetch": 70 * ns,
         "bench.dispatch": 17 * ns, "bench.block": 20 * ns})
    assert sum(s for _, s in r["idle_gaps"]) == pytest.approx(
        r["window_s"] - r["busy_s"])
    # host time per phase, whether the device is busy or not
    assert r["phase_s"] == pytest.approx(
        {"host.put": 40 * ns, "host.launch": 28 * ns, "host.fetch": 78 * ns})
    assert r["phase_events"] == {"host.put": 3, "host.launch": 2, "host.fetch": 3}
    assert r["transfers"] == 5


@pytest.mark.parametrize("events", ["two_decisions", "bench_spans_only"])
def test_other_keys_are_trace_reduces(events):
    if events == "two_decisions":
        host, devices = TWO_DECISIONS
    else:
        host, devices = _bench_only(TWO_DECISIONS[0]), TWO_DECISIONS[1]
    r = host_dispatch.reduce_events(host, devices)
    base = trace_reduce.reduce_events(_bench_only(host), devices)
    assert {k: v for k, v in r.items() if k in base and k != "idle_gaps"} == \
        {k: v for k, v in base.items() if k != "idle_gaps"}
    assert sum(s for _, s in r["idle_gaps"]) == pytest.approx(
        base["window_s"] - base["busy_s"])


def test_gaps_by_innermost_bench_span():
    """The events of ``test_busy_idle_and_gaps_by_host_span``: with no
    runtime event, each piece of a gap goes to the span covering it."""
    host = [(0, 100, "bench.window"), (0, 40, "bench.dispatch"),
            (40, 90, "bench.block"), (90, 100, "bench.host")]
    f1 = "%fusion.1 = f32[8]{0:T(128)} fusion(f32[8]{0} %p), kind=kLoop"
    f2 = "%fusion.2 = f32[8]{0:T(128)} fusion(f32[8]{0} %q), kind=kLoop"
    d3 = "%dot.3 = f32[8,8]{1,0} dot(f32[8,4]{1,0} %a, f32[4,8]{1,0} %b)"
    loop = "%while.4 = (s32[]{:T(128)}, f32[8]{0}) while((s32[], f32[8]) %t)"
    ops = [(10, 30, f1), (20, 35, f2), (50, 80, d3), (95, 120, f1),
           (50, 80, loop)]
    r = host_dispatch.reduce_events(host, {"/device:TPU:0": ops})
    # 0-10 under dispatch; 35-50: 5 under dispatch, 10 under block;
    # 80-95: 10 under block, 5 under host
    assert dict(r["idle_gaps"]) == pytest.approx(
        {"bench.dispatch": 15 * ns, "bench.block": 20 * ns, "bench.host": 5 * ns})
    assert r["phase_s"] == {"host.put": 0.0, "host.launch": 0.0, "host.fetch": 0.0}


def test_innermost_phase_takes_the_time():
    host = [(0, 100, "bench.window"),
            (0, 50, "CommonPjRtLoadedExecutable::Execute"),
            (10, 20, "DevicePut"), (60, 80, "np.asarray(jax.Array)"),
            (70, 90, "ArrayImpl.copy_to_host_async")]
    p = host_dispatch.phases(host)
    assert p["phase_s"] == pytest.approx(
        {"host.put": 10 * ns, "host.launch": 40 * ns, "host.fetch": 30 * ns})
    assert host_dispatch.phases([(0, 1, "DevicePut")]) is None
    # a bench span that starts inside a phase event does not take its time
    gaps = host_dispatch.idle_gaps(host + [(65, 100, "bench.block")],
                                   {"/device:TPU:0": [(95, 100, "x")]})
    assert dict(gaps) == pytest.approx(
        {"host.launch": 40 * ns, "host.put": 10 * ns, "host.fetch": 30 * ns,
         "other": 10 * ns, "bench.block": 5 * ns})


# Recorded on one TPU v5e: (file, decisions, window_s, busy_s, idle_share,
# the first four top operations, host ms per decision by phase, transfers)
RECORDINGS = [
    ("decide.xplane.pb", 14, 0.025, 0.000475288, 0.98098848,
     [["dynamic_slice.29 (dynamic-slice)", 2.0893e-05],
      ["dynamic_slice.30 (dynamic-slice)", 2.0853e-05],
      ["dynamic_slice.28 (dynamic-slice)", 2.0661e-05],
      ["dynamic_slice.27 (dynamic-slice)", 2.0652e-05]],
     {"host.put": 0.515, "host.launch": 0.273, "host.fetch": 0.762}, 96),
    ("decide-17-decisions.xplane.pb", 17, 0.03254099, 0.000625557,
     0.9807763377819789,
     [["dynamic_slice.29 (dynamic-slice)", 2.7299e-05],
      ["dynamic_slice.30 (dynamic-slice)", 2.7259e-05],
      ["dynamic_slice.28 (dynamic-slice)", 2.6998e-05],
      ["dynamic_slice.27 (dynamic-slice)", 2.692e-05]],
     {"host.put": 0.509, "host.launch": 0.288, "host.fetch": 0.789}, 119),
]


@pytest.mark.parametrize("name,units,window,busy,idle,top,per_ms,transfers",
                         RECORDINGS)
def test_recorded_chip_trace(name, units, window, busy, idle, top, per_ms,
                             transfers):
    """Short windows of ``t2drl-paper.decide`` traced on one TPU v5e: the
    pre-existing keys as ``trace_reduce`` reads them, and the runtime's
    host phases per decision."""
    path = os.path.join(DATA, name)
    r, base = host_dispatch.reduce(path), trace_reduce.reduce(path)
    assert r["window_s"] == pytest.approx(window)
    assert r["busy_s"] == pytest.approx(busy)
    assert r["idle_share"] == pytest.approx(idle)
    assert r["devices"] == 1
    assert r["span_device_s"] == pytest.approx(
        {"bench.dispatch": busy, "bench.block": 0.0})
    assert r["top_ops"][:4] == top
    assert {k: v for k, v in r.items() if k in base and k != "idle_gaps"} == \
        {k: v for k, v in base.items() if k != "idle_gaps"}
    assert {k: 1e3 * v / units for k, v in r["phase_s"].items()} == \
        pytest.approx(per_ms, abs=1e-3)
    assert r["transfers"] == transfers
    assert [n for n, _ in r["idle_gaps"][:2]] == ["host.fetch", "host.put"]
    assert sum(s for _, s in r["idle_gaps"]) == pytest.approx(
        r["window_s"] - r["busy_s"])


@pytest.fixture
def traced_run(tmp_path, monkeypatch):
    """The recorded trace where ``chipbench/run.py`` leaves a traced run's
    profile, and that run's record (14 decisions)."""
    monkeypatch.setattr(host_dispatch.tempfile, "gettempdir", lambda: str(tmp_path))
    d = tmp_path / "chipbench-trace-x" / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    shutil.copy(RECORDED, d / "host.xplane.pb")
    host_dispatch._phases_file.cache_clear()
    yield {"trace": trace_reduce.reduce(RECORDED), "run": {"units": 14}}
    host_dispatch._phases_file.cache_clear()


def _read(name, rec):
    path = os.path.join(ROOT, "chipbench", "metrics", name + ".py")
    return harness.load_module(path, "chipbench_metric").read(rec)


def test_readers_per_decision(traced_run):
    got = {n: _read(n, traced_run) for n in READERS}
    assert got == pytest.approx({"decide_put_ms": 0.515, "decide_launch_ms": 0.273,
                                 "decide_fetch_ms": 0.762,
                                 "decide_transfers": 96 / 14}, abs=1e-3)


def test_renamed_runtime_events_read_none(traced_run, monkeypatch):
    monkeypatch.setattr(host_dispatch, "PHASES",
                        {"DevicePut.v2": "host.put", "Execute.v2": "host.launch",
                         "np.asarray.v2": "host.fetch"})
    monkeypatch.setattr(host_dispatch, "TRANSFERS", ("DevicePut.v2",))
    assert {n: _read(n, traced_run) for n in READERS} == dict.fromkeys(READERS)


def test_phase_without_events_reads_zero(traced_run, monkeypatch):
    phases = dict(host_dispatch.PHASES)
    del phases["CommonPjRtLoadedExecutable::Execute"]
    monkeypatch.setattr(host_dispatch, "PHASES",
                        {**phases, "Execute.v2": "host.launch"})
    assert _read("decide_launch_ms", traced_run) == 0.0
    assert _read("decide_put_ms", traced_run) == pytest.approx(0.515, abs=1e-3)


def test_no_trace_or_another_run_reads_none(traced_run, tmp_path):
    other = dict(traced_run, trace=dict(traced_run["trace"], window_s=0.5))
    assert {n: _read(n, other) for n in READERS} == dict.fromkeys(READERS)
    shutil.rmtree(tmp_path / "chipbench-trace-x")
    assert {n: _read(n, traced_run) for n in READERS} == dict.fromkeys(READERS)
