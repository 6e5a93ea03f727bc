"""Run one benchmark cell once, on the chips of this machine.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is found by name in ``BENCHMARK.json``.  Set-up (process start,
weights and state from ``--seed``, compiling or reading the persistent
cache, warm-up) ends where the window begins; the window measures for
``--seconds`` (``--trace 1``: for the traffic's ``trace_seconds`` at most,
under the profiler).  Then the program's state is freed and the reference
decides ``correct``.  The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer metrics), ``device``, with
``--trace 1`` ``breakdown``, and last ``checks``: every number compared
beside its limit, also printed as the last lines of standard error.

Exits non-zero and prints no result when JAX finds no TPU or fewer chips
than the cell asks for, or when the cell cannot be found.
"""
import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

# libtpu would otherwise log to a fixed directory under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from chipbench import harness  # noqa: E402

_PEAKS = os.path.join(harness.HERE, "peaks.json")


def _number(x):
    return x if isinstance(x, (int, float)) and math.isfinite(x) else str(x)


def run(spec: dict, jax, dev: dict, seed: int, seconds: float, trace: bool,
        t0: float, peaks: dict) -> dict:
    """One run of the resolved cell ``spec``; returns the result object."""
    span = harness.span_factory(jax, trace)
    if trace:
        seconds = min(seconds, spec["traffic"].get("trace_seconds", seconds))
    driver = harness.load_module(spec["driver"], "chipbench_driver")
    drv = driver.Driver(spec["config"], spec["traffic"], seed, span)
    prepare_s = harness.prepare(drv)
    with harness.CompileClock(jax) as setup_clock:
        drv.setup()
    setup_s = time.perf_counter() - t0 - prepare_s
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    try:
        with harness.CompileClock(jax) as window_clock:
            with (jax.profiler.trace(trace_dir) if trace
                  else contextlib.nullcontext()):
                with span("bench.window"):
                    res = drv.window(seconds)
        memory = harness.memory_peak_bytes(jax, spec["chips"])
        drv.release()
        numbers = drv.check()
        numbers["window_compiles"] = window_clock.compiles
        checks = harness.judge(numbers, spec["limits"])
        for name, value in numbers.items():
            if name not in checks:
                print(f"info {name} = {value} (not compared)", file=sys.stderr)
        device = {**dev, "memory_peak_bytes": memory}
        out = {"correct": harness.all_within(checks) and res["failed"] == 0,
               "attempted": res["attempted"], "failed": res["failed"]}
        if trace:
            from chipbench import trace_reduce
            path = trace_reduce.find_xplane(trace_dir)
            reduced = trace_reduce.reduce(path) if path else None
            record = {"trace": reduced, "run": {
                **drv.counters(res), "window_s": res["window_s"],
                "compile_s": setup_clock.seconds, "chips": spec["chips"],
                "peak_flops_per_s": peaks[dev["kind"]]["bf16_flops_per_s"]}}
            metrics = {}
            for m in spec["per_layer"]:
                reader = harness.load_module(m["reader"], "chipbench_metric")
                value = reader.read(record)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
            out["metrics"] = metrics
            if reduced is not None:
                device.update(busy_s=reduced["busy_s"],
                              window_s=reduced["window_s"])
                out["breakdown"] = {"device_ops": reduced["top_ops"],
                                    "idle_gaps": reduced["idle_gaps"]}
                print(f"[trace] device time per host span (s): "
                      f"{reduced['span_device_s']}", file=sys.stderr)
        else:
            values = {**drv.end_to_end(res), "setup_s": setup_s}
            out["metrics"] = {m["name"]: {"value": values[m["name"]],
                                          "unit": m["unit"]}
                              for m in spec["end_to_end"]}
        out["device"] = device
        out["checks"] = {k: {"value": _number(v["value"]), "limit": v["limit"]}
                         for k, v in checks.items()}
        print(f"[setup] setup_s {setup_s} compile_s {setup_clock.seconds} "
              f"(traffic made by the benchmark in {prepare_s} s, left out)",
              file=sys.stderr)
        return out
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)


def main(argv=None, *, require_chip: bool = True, t0: float = _T0,
         peaks: dict | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        spec = harness.resolve_cell(_ROOT, args.workload)
    except harness.CellError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    import jax
    try:
        dev = harness.device_stamp(jax, spec["chips"], require_chip=require_chip)
    except harness.NoChipError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    if peaks is None:
        with open(_PEAKS) as f:
            peaks = json.load(f)
        if dev["kind"] not in peaks:
            print(f"chipbench: no peaks for device kind {dev['kind']!r} in "
                  f"{_PEAKS}", file=sys.stderr)
            return 4
    harness.use_compile_cache(jax)
    out = run(spec, jax, dev, args.seed, args.seconds, bool(args.trace), t0,
              peaks)
    for name, c in out["checks"].items():
        print(f"check {name} = {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(f"correct = {str(out['correct']).lower()}", file=sys.stderr,
          flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
