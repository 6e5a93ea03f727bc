"""The host side of dispatch in a traced window, read from the JAX
runtime's own host events, on the same clock as the device's operations.

- A phase is a group of runtime events (``PHASES``): moving an argument
  to the device, launching the executable, copying a result back.  Each
  instant of the window goes to the innermost phase event covering it (the
  one that started last), on any ``/host:`` line, so ``phase_s`` is each
  phase's self time and phases never count an instant twice.
- ``transfers`` counts the events that move one array between host and
  device (``TRANSFERS``).
- ``idle_gaps`` divides each idle gap of a device into pieces and names
  each piece by the innermost host span covering it: a runtime phase if
  one does, otherwise the benchmark's innermost ``bench.*`` span (its self
  time: Python, pytree handling, the profiler's tracer), otherwise
  ``other``.  The pieces sum to the window less the busy time.
- Every other key is ``trace_reduce.reduce_events``'s, of the same trace.

A metric reader is given ``trace_reduce``'s record of the run, which
holds none of this; ``this_run`` finds the trace that ``chipbench/run.py``
wrote for that record, while it is still there, and reads its phases.
"""
from __future__ import annotations

import functools
import glob
import os
import tempfile

from chipbench import trace_reduce

PHASES = {"DevicePut": "host.put",
          "CommonPjRtLoadedExecutable::Execute": "host.launch",
          "ArrayImpl.copy_to_host_async": "host.fetch",
          "np.asarray(jax.Array)": "host.fetch"}
TRANSFERS = ("DevicePut", "np.asarray(jax.Array)")
# where chipbench/run.py puts the profiler's output of a traced run
TRACE_DIRS = "chipbench-trace-*"


def read_host(path: str) -> list:
    """The host spans of the trace file at ``path`` that this module reads:
    the ``bench.*`` annotations and the runtime events of ``PHASES``, as
    ``(start, end, name)`` in nanoseconds."""
    from jax.profiler import ProfileData
    return [(e.start_ns, e.start_ns + e.duration_ns, e.name)
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name in PHASES or e.name.startswith(trace_reduce.PREFIX)]


def _owners(spans, w0, w1):
    """``[(start, end, name)]`` covering ``[w0, w1)`` without overlap: each
    piece named by the span of ``spans`` (``(start, end, name, rank)``)
    that covers it with the highest rank, the latest start among those,
    or ``other`` where none covers it."""
    edges = sorted({w0, w1, *(x for s, e, _, _ in spans for x in (s, e)
                              if w0 < x < w1)})
    starts = sorted((max(s, w0), i) for i, (s, e, _, _) in enumerate(spans)
                    if e > w0 and s < w1)
    active, out, k = {}, [], 0
    for a, b in zip(edges, edges[1:]):
        while k < len(starts) and starts[k][0] <= a:
            i = starts[k][1]
            active[i] = spans[i]
            k += 1
        for i in [i for i, sp in active.items() if sp[1] <= a]:
            del active[i]
        name = (max(active.values(), key=lambda sp: (sp[3], sp[0]))[2]
                if active else "other")
        if out and out[-1][2] == name and out[-1][1] == a:
            out[-1] = (out[-1][0], b, name)
        else:
            out.append((a, b, name))
    return out


def _split(idle, owners) -> dict:
    """Length of the sorted disjoint ``idle`` intervals under each owner."""
    out, j = {}, 0
    for s, e in idle:
        while j < len(owners) and owners[j][1] <= s:
            j += 1
        k = j
        while k < len(owners) and owners[k][0] < e:
            a, b, name = owners[k]
            piece = min(e, b) - max(s, a)
            if piece > 0:
                out[name] = out.get(name, 0) + piece
            k += 1
    return out


def _window(host):
    """``(start, end)`` of the ``bench.window`` spans, or None."""
    w = [(s, e) for s, e, n in host if n == trace_reduce.WINDOW]
    return (min(s for s, _ in w), max(e for _, e in w)) if w else None


def _phase_spans(host, w0, w1):
    """The runtime events of ``PHASES`` that overlap the window, as
    ``(start, end, phase, 1)``."""
    return [(s, e, PHASES[n], 1) for s, e, n in host
            if n in PHASES and e > w0 and s < w1]


def phases(host) -> dict | None:
    """``window_s``, ``phase_s``, ``phase_events`` and ``transfers`` of the
    window, from the host events alone; None where there is no window."""
    w = _window(host)
    if w is None:
        return None
    w0, w1 = w
    spans = _phase_spans(host, w0, w1)
    phase_s = dict.fromkeys(PHASES.values(), 0.0)
    for a, b, name in _owners(spans, w0, w1):
        if name in phase_s:
            phase_s[name] += (b - a) / 1e9
    events = dict.fromkeys(PHASES.values(), 0)
    for _, _, name, _ in spans:
        events[name] += 1
    return {"window_s": (w1 - w0) / 1e9, "phase_s": phase_s,
            "phase_events": events,
            "transfers": sum(n in TRANSFERS and e > w0 and s < w1
                             for s, e, n in host)}


def idle_gaps(host, devices) -> list | None:
    """The top ``[name, seconds]`` pieces of the devices' idle gaps, each
    named by the innermost host span covering it, averaged over the devices
    that ran an operation in the window; None where there is no window or
    no such device."""
    w = _window(host)
    if w is None:
        return None
    w0, w1 = w
    spans = _phase_spans(host, w0, w1) + [
        (s, e, n, 0) for s, e, n in host
        if n.startswith(trace_reduce.PREFIX) and n != trace_reduce.WINDOW
        and e > w0 and s < w1]
    owners = _owners(spans, w0, w1)
    gaps, nd = {}, 0
    for ops in devices.values():
        busy = trace_reduce._union([(max(s, w0), min(e, w1)) for s, e, _ in ops
                                    if e > w0 and s < w1])
        if not busy:
            continue
        nd += 1
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        idle = [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
        for name, ns in _split(idle, owners).items():
            gaps[name] = gaps.get(name, 0) + ns
    if not nd:
        return None
    top = sorted(gaps.items(), key=lambda kv: -kv[1])[:trace_reduce.TOP]
    return [[k, v / nd / 1e9] for k, v in top]


def reduce_events(host, devices) -> dict | None:
    """``trace_reduce.reduce_events`` of the ``bench.*`` spans, with
    ``idle_gaps`` divided by the innermost host span and the ``phases``
    keys added; None where that gives None."""
    base = trace_reduce.reduce_events(
        [h for h in host if h[2].startswith(trace_reduce.PREFIX)], devices)
    if base is None:
        return None
    return {**base, **phases(host), "idle_gaps": idle_gaps(host, devices)}


def reduce(path: str) -> dict | None:
    """``reduce_events`` of the trace file at ``path``."""
    _, devices = trace_reduce.read_events(path)
    return reduce_events(read_host(path), devices)


@functools.lru_cache(maxsize=1)
def _phases_file(path: str, stamp: tuple) -> dict | None:
    return phases(read_host(path))


def this_run(rec) -> dict | None:
    """``phases`` of the trace this run wrote: the newest under the
    temporary directory, taken only where its window is the record's own
    to the nanosecond (a record without a device trace has no window to
    compare).  None where the run was not traced or no trace is left."""
    paths = glob.glob(os.path.join(tempfile.gettempdir(), TRACE_DIRS,
                                   "**", "*.xplane.pb"), recursive=True)
    if not paths:
        return None
    path = max(paths, key=os.path.getmtime)
    st = os.stat(path)
    r = _phases_file(path, (st.st_mtime_ns, st.st_size))
    tr = rec["trace"]
    if r is None or (tr is not None and r["window_s"] != tr["window_s"]):
        return None
    return r


def per_unit(rec, key: str, phase: str | None = None) -> float | None:
    """``key`` of this run's ``phases`` (``phase_s[phase]`` where a phase
    is named) per unit completed in the window; None where the trace is
    missing, the window completed nothing, or no event of ``PHASES`` was
    found at all (a renamed runtime event must not read as 0)."""
    r, units = this_run(rec), rec["run"]["units"]
    if r is None or not units or not any(r["phase_events"].values()):
        return None
    value = r[key] if phase is None else r[key][phase]
    return value / units
