"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's device
numbers.

- The window is the host span ``bench.window``; everything is clipped to
  it.
- A device is a plane named ``/device:TPU:<n>``; its operations are the
  events of its ``XLA Ops`` line.  Busy time is the union of their
  intervals, idle share is one minus busy over the window, both averaged
  over the devices that ran an operation in the window.
- The top operations leave out control flow (``while``, ``conditional``,
  ``call``), whose events enclose the operations they run; an operation
  is named by its HLO instruction and opcode.
- Host spans are the benchmark's own ``bench.*`` annotations.  Each idle
  gap on a device is put to the host span that overlaps it most (``other``
  where none does), so the gaps are named by what the host was doing.
"""
from __future__ import annotations

import bisect
import glob
import os
import re

DEVICE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
WINDOW = "bench.window"
PREFIX = "bench."
TOP = 10
CONTAINERS = ("while", "conditional", "call")
_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")


def op_name(text: str) -> tuple[str, str]:
    """``(instruction, opcode)`` of an ``XLA Ops`` event's HLO text."""
    inst, _, rest = text.partition(" = ")
    m = _OPCODE.search(" " + rest)
    return inst.lstrip("%"), (m.group(1) if m else "")


def find_xplane(trace_dir: str) -> str | None:
    """The newest ``.xplane.pb`` under ``trace_dir``, if any."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def _union(intervals):
    """Merge ``(start, end)`` pairs into sorted disjoint intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _overlap(a, b) -> int:
    """Total overlap of two sorted disjoint interval lists."""
    i = j = total = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _overlap_one(s, e, iv, starts, ends) -> int:
    """Overlap of ``[s, e)`` with sorted disjoint intervals ``iv``."""
    lo, hi = bisect.bisect_right(ends, s), bisect.bisect_left(starts, e)
    return sum(min(e, b) - max(s, a) for a, b in iv[lo:hi])


def read_events(path: str):
    """``(host, devices)``: host ``bench.*`` spans as ``(start, end,
    name)`` and, per device plane, its operations as ``(start, end,
    name)``; times in nanoseconds."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    host, devices = [], {}
    for plane in data.planes:
        if DEVICE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        (e.start_ns, e.start_ns + e.duration_ns, e.name)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.start_ns, e.start_ns + e.duration_ns, e.name)
                            for e in line.events if e.name.startswith(PREFIX))
    return host, devices


def reduce_events(host, devices) -> dict | None:
    """The device numbers of one traced window, or None when the trace
    has no window or no device operation in it."""
    windows = [(s, e) for s, e, n in host if n == WINDOW]
    if not windows:
        return None
    w0, w1 = min(s for s, _ in windows), max(e for _, e in windows)
    spans = {}
    for s, e, n in host:
        if n != WINDOW:
            spans.setdefault(n, []).append((max(s, w0), min(e, w1)))
    spans = {n: _union([iv for iv in v if iv[1] > iv[0]]) for n, v in spans.items()}
    index = {n: ([a for a, _ in iv], [b for _, b in iv]) for n, iv in spans.items()}
    busy, span_dev, ops, gaps = [], {}, {}, {}
    for events in devices.values():
        clipped = [(max(s, w0), min(e, w1), n) for s, e, n in events
                   if e > w0 and s < w1]
        if not clipped:
            continue
        for s, e, n in clipped:
            inst, opcode = op_name(n)
            if opcode not in CONTAINERS:
                key = f"{inst} ({opcode})"
                ops[key] = ops.get(key, 0) + (e - s)
        merged = _union([(s, e) for s, e, _ in clipped])
        busy.append(sum(e - s for s, e in merged))
        for n, iv in spans.items():
            span_dev[n] = span_dev.get(n, 0) + _overlap(merged, iv)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for s, e in zip(edges[::2], edges[1::2]):
            if e <= s:
                continue
            best, name = 0, "other"
            for n, iv in spans.items():
                o = _overlap_one(s, e, iv, *index[n])
                if o > best:
                    best, name = o, n
            gaps[name] = gaps.get(name, 0) + (e - s)
    if not busy:
        return None
    nd = len(busy)
    per = lambda ns: ns / nd / 1e9
    top = lambda d: [[k, per(v)] for k, v in
                     sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]
    window_s = (w1 - w0) / 1e9
    busy_s = per(sum(busy))
    return {"window_s": window_s, "busy_s": busy_s, "devices": nd,
            "idle_share": 1.0 - busy_s / window_s,
            "span_device_s": {k: per(v) for k, v in span_dev.items()},
            "top_ops": top(ops), "idle_gaps": top(gaps)}


def reduce(path: str) -> dict | None:
    """``reduce_events`` of the trace file at ``path``."""
    return reduce_events(*read_events(path))
