"""Device time inside the gateway engine's own spans in a traced window.

The program marks each prefill and each decode step with a profiler span
(``engine.prefill``, ``engine.decode_step``: ``repro.serving.engine``).
Each call ends on the host once its result is back, so the device work it
launched lies inside its span.  For each span name this module counts the
spans that lie wholly inside the benchmark's window (``bench.window``) and
the device busy time inside them: the union of the ``XLA Ops`` intervals
of a ``/device:TPU:<n>`` plane, overlapped with the union of the spans,
averaged over the devices that ran an operation in the window.

A metric reader is given ``trace_reduce``'s record, which keeps only the
benchmark's ``bench.*`` spans; ``this_run`` finds the trace that
``chipbench/run.py`` wrote for that record, while it is still there.  A
trace without these spans (a program that has none) reads as None.
"""
from __future__ import annotations

import functools
import glob
import os
import tempfile

from chipbench import trace_reduce
from chipbench.host_dispatch import TRACE_DIRS

SPANS = ("engine.prefill", "engine.decode_step")


def read_events(path: str):
    """``(host, devices)`` of the trace file at ``path``: the window and the
    engine's spans as ``(start, end, name)``, and per device plane its
    operations as ``(start, end)``; nanoseconds."""
    from jax.profiler import ProfileData
    host, devices = [], {}
    for plane in ProfileData.from_file(path).planes:
        if trace_reduce.DEVICE.match(plane.name):
            for line in plane.lines:
                if line.name == trace_reduce.OPS_LINE:
                    devices[plane.name] = [(e.start_ns, e.start_ns + e.duration_ns)
                                           for e in line.events]
        elif plane.name.startswith("/host:"):
            host.extend((e.start_ns, e.start_ns + e.duration_ns, e.name)
                        for line in plane.lines for e in line.events
                        if e.name in SPANS or e.name == trace_reduce.WINDOW)
    return host, devices


def reduce_events(host, devices) -> dict | None:
    """``{"window_s", name: {"spans", "device_s"}}`` for each of ``SPANS``;
    None without a window or a device operation in it."""
    windows = [(s, e) for s, e, n in host if n == trace_reduce.WINDOW]
    if not windows:
        return None
    w0, w1 = min(s for s, _ in windows), max(e for _, e in windows)
    busy = [trace_reduce._union([(max(s, w0), min(e, w1)) for s, e in ops
                                 if e > w0 and s < w1])
            for ops in devices.values()]
    busy = [b for b in busy if b]
    if not busy:
        return None
    out = {"window_s": (w1 - w0) / 1e9}
    for name in SPANS:
        spans = [(s, e) for s, e, n in host if n == name and s >= w0 and e <= w1]
        merged = trace_reduce._union(spans)
        dev = sum(trace_reduce._overlap(b, merged) for b in busy) / len(busy)
        out[name] = {"spans": len(spans), "device_s": dev / 1e9}
    return out


@functools.lru_cache(maxsize=1)
def _reduce_file(path: str, stamp: tuple) -> dict | None:
    return reduce_events(*read_events(path))


def this_run(rec) -> dict | None:
    """``reduce_events`` of the trace this run wrote: the newest under the
    temporary directory, taken only where its window is the record's own."""
    tr = rec["trace"]
    if tr is None:
        return None
    paths = glob.glob(os.path.join(tempfile.gettempdir(), TRACE_DIRS,
                                   "**", "*.xplane.pb"), recursive=True)
    if not paths:
        return None
    path = max(paths, key=os.path.getmtime)
    st = os.stat(path)
    r = _reduce_file(path, (st.st_mtime_ns, st.st_size))
    if r is None or r["window_s"] != tr["window_s"]:
        return None
    return r


def device_ms_per_span(rec, name: str) -> float | None:
    """Device busy milliseconds per ``name`` span in the traced window."""
    r = this_run(rec)
    if r is None or not r[name]["spans"]:
        return None
    return 1e3 * r[name]["device_s"] / r[name]["spans"]
