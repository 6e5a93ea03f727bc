"""Readings that the limits of the gateway cell's ``correct`` are set from,
in one process on the cell's chip:

- the program on ``--seeds`` (the lower readings),
- the control, the reference in float8 in the program's place, on
  ``--control-seeds``,
- each planted fault of ``chipbench/faults_gateway.py`` on
  ``--fault-seeds``.

    python chipbench/calibrate_gateway.py --workload <cell> --seeds 1,2 \
        --control-seeds 1,2 --fault-seeds 1 [--faults padded,overflow] \
        [--window 5] [--out calibrate.jsonl]

Each reading is one JSON line on standard output (and appended to
``--out``).  ``--window`` is the length of each run's window in seconds.
Not part of a benchmark run.
"""
import argparse
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from chipbench import harness  # noqa: E402


def _seeds(s):
    return [int(x) for x in s.split(",") if x]


def main(argv=None, *, require_chip: bool = True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--faults", default="")
    ap.add_argument("--window", type=float, default=5.0)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    spec = harness.resolve_cell(_ROOT, args.workload)
    import jax
    dev = harness.device_stamp(jax, spec["chips"], require_chip=require_chip)
    harness.use_compile_cache(jax)
    from chipbench import faults_gateway
    driver = harness.load_module(spec["driver"], "chipbench_driver")
    span = harness.span_factory(jax, False)

    def emit(variant, seed, numbers, seconds):
        line = json.dumps({"cell": args.workload, "variant": variant,
                           "seed": seed, "numbers": numbers,
                           "seconds": seconds, "device": dev})
        print(line, flush=True)
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "a") as f:
                f.write(line + "\n")

    def program(variant, seed):
        t = time.perf_counter()
        d = driver.Driver(spec["config"], spec["traffic"], seed, span)
        d.setup()
        res = d.window(args.window)
        d.release()
        emit(variant, seed, {**d.check(), "failed": res["failed"]},
             time.perf_counter() - t)

    for seed in _seeds(args.seeds):
        program("program", seed)
    for seed in _seeds(args.control_seeds):
        t = time.perf_counter()
        emit("control", seed, driver.control(spec["config"], spec["traffic"], seed),
             time.perf_counter() - t)
    for name in [f for f in args.faults.split(",") if f]:
        with faults_gateway.planted(name):
            for seed in _seeds(args.fault_seeds):
                program("fault:" + name, seed)
    return 0


if __name__ == "__main__":
    sys.exit(main())
