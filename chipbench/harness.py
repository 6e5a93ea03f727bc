"""The benchmark's harness: finds a cell by its name in ``BENCHMARK.json``,
loads the cell's configuration, traffic, driver and per-layer metric
readers from files named after them, and runs one measured window.

Layout (a new cell, configuration or metric is a new file, never an edit):

- ``chipbench/configs/<config>.json``: sizes and constants of a
  configuration (the file named by ``BENCHMARK.json``'s ``configs``).
- ``chipbench/traffic/<traffic>.json``: the traffic mix, a data file
  naming its ``driver`` and that driver's parameters.
- ``chipbench/drivers/<driver>.py``: one driver per kind of traffic,
  with a ``Driver`` class (``setup``, ``window``, ``release``, ``check``,
  and optionally ``prepare``).
- ``chipbench/metrics/<metric>.py``: one reader per per-layer metric,
  ``read(record) -> float | None``.
- ``chipbench/limits/<cell>.json``: the limit of each number that
  decides ``correct`` in that cell, with the readings it was set from.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
SRC = os.path.join(ROOT, "src")

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class CellError(RuntimeError):
    """The cell cannot be run as named (unknown name, missing file)."""


class NoChipError(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_module(path: str, name: str):
    """Import the Python file at ``path`` as module ``name``."""
    if not os.path.isfile(path):
        raise CellError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _read_json(path: str):
    if not os.path.isfile(path):
        raise CellError(f"no file {path}")
    with open(path) as f:
        return json.load(f)


def resolve_cell(root: str, name: str) -> dict:
    """Everything a run of cell ``name`` needs, found by name from
    ``<root>/BENCHMARK.json``: its entry, configuration, traffic, limits,
    end-to-end metrics and per-layer metrics (with the reader files)."""
    bench = _read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise CellError(f"unknown workload {name!r}; BENCHMARK.json has "
                        f"{sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if cell["config"] not in configs:
        raise CellError(f"workload {name!r} names unknown config "
                        f"{cell['config']!r}")
    bench_dir = os.path.join(root, "chipbench")
    config = _read_json(os.path.join(root, configs[cell["config"]]["file"]))
    traffic = _read_json(os.path.join(bench_dir, "traffic",
                                      cell["traffic"] + ".json"))
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [dict(m) for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e_names)]
    for m in per_layer:
        m["reader"] = os.path.join(bench_dir, "metrics", m["name"] + ".py")
        if not os.path.isfile(m["reader"]):
            raise CellError(f"no reader {m['reader']}")
    driver = os.path.join(bench_dir, "drivers", traffic["driver"] + ".py")
    if not os.path.isfile(driver):
        raise CellError(f"no driver {driver}")
    return {"name": name, "cell": cell, "config": config, "traffic": traffic,
            "limits": _read_json(os.path.join(bench_dir, "limits",
                                              name + ".json")),
            "end_to_end": e2e, "per_layer": per_layer, "driver": driver,
            "chips": cell["chips"]}


# -- device ---------------------------------------------------------------------------

def device_stamp(jax, need: int, *, require_chip: bool = True) -> dict:
    """Platform, kind and count of JAX's devices.  Raises ``NoChipError``
    when there is no TPU, or fewer than ``need`` chips; it never falls
    back to the CPU (``require_chip=False`` is for the CPU rehearsals)."""
    devices = jax.devices()
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices)}
    if require_chip:
        if dev["platform"] != "tpu":
            raise NoChipError(f"no TPU found (JAX reports {dev['platform']}); "
                              "the benchmark never falls back to another "
                              "backend")
        if dev["count"] < need:
            raise NoChipError(f"the cell needs {need} chips, JAX found "
                              f"{dev['count']}")
    return dev


def memory_peak_bytes(jax, chips: int):
    """Peak bytes in use on the fullest of the first ``chips`` devices, or
    None where the backend keeps no such statistic."""
    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def prepare(drv) -> float:
    """Seconds the driver's optional ``prepare`` took: traffic that the
    benchmark makes with its own reference before set-up.  That time is
    not the program's, so it is left out of ``setup_s``."""
    fn = getattr(drv, "prepare", None)
    if fn is None:
        return 0.0
    t = time.perf_counter()
    fn()
    return time.perf_counter() - t


class CompileClock:
    """Sums JAX's own trace, lowering and backend-compile durations while
    the ``with`` block runs (a persistent-cache hit is timed inside the
    backend-compile event), and counts backend compiles."""

    def __init__(self, jax):
        self._jax = jax

    def __enter__(self):
        self.seconds = 0.0
        self.compiles = 0
        self._jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def _on_event(self, event, duration, **_):
        if event in _COMPILE_EVENTS:
            self.seconds += duration
        if event == _BACKEND_COMPILE:
            self.compiles += 1

    def __exit__(self, *exc):
        self._jax.monitoring.unregister_event_duration_listener(self._on_event)
        return False


def span_factory(jax, traced: bool):
    """``span(name)`` context: a profiler ``TraceAnnotation`` on the device
    trace's clock when traced, nothing otherwise."""
    if traced:
        return jax.profiler.TraceAnnotation
    return lambda name: contextlib.nullcontext()


def use_compile_cache(jax) -> str:
    """The program's persistent compilation cache, in this checkout, with
    every program written to it however fast it compiled."""
    if SRC not in sys.path:
        sys.path.insert(0, SRC)
    from repro.launch.compile_cache import use_compile_cache as program_cache
    path = program_cache(ROOT)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


# -- checks ------------------------------------------------------------------------------

def judge(numbers: dict, limits: dict) -> dict:
    """``{name: {"value", "limit"}}`` for every number that has a limit in
    the cell's limits file; a number is within its limit when ``value <=
    limit`` (NaN never is)."""
    out = {}
    for name, spec in limits["limits"].items():
        if name not in numbers:
            raise CellError(f"the driver gave no number {name!r}")
        out[name] = {"value": float(numbers[name]), "limit": float(spec["limit"])}
    return out


def all_within(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())
