"""Fleet serving CLI — train → checkpoint → restore → replay request traffic.

  python scripts/serve_fleet.py --smoke
  python scripts/serve_fleet.py --scenarios all --methods t2drl,rcars \
      --episodes 60 --num-cells 4

Thin CLI over ``benchmarks.bench_fleet`` (adds repo paths itself, so no
PYTHONPATH needed).  Each method is trained on the paper-default workload,
checkpointed through ``repro.checkpoint.save_train_state``, restored, and
deployed in the request-level queueing twin (``repro.fleet``) under every
requested scenario's traffic trace.  Tail-latency / SLO / backlog metrics
land in experiments/bench/fleet.json (schema in benchmarks/README.md).

``--smoke`` is the CI gate: a tiny t2drl + rcars sweep over two scenarios
end-to-end from restored checkpoints, which FAILS (exit 1) unless the warm
jitted tick scan sustains at least 1e5 simulated requests/min.
"""
from __future__ import annotations

import argparse
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (_ROOT, os.path.join(_ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from repro.core import EnvCfg                      # noqa: E402
from repro.fleet import FleetCfg                   # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from benchmarks import bench_fleet                 # noqa: E402

SMOKE_RATE_FLOOR = 1e5      # simulated requests/min, warm tick scan


def main():
    ap = argparse.ArgumentParser(
        description="Deploy checkpointed policies in the request-level "
                    "fleet twin; JSON metrics to experiments/bench/.")
    ap.add_argument("--scenarios", default="paper-default,flash-crowd",
                    help="comma list of registered scenarios, or 'all'")
    ap.add_argument("--methods", default="t2drl,rcars",
                    help="comma list from t2drl,ddpg,schrs,rcars")
    ap.add_argument("--episodes", type=int, default=25,
                    help="training episodes for the learned methods")
    ap.add_argument("--num-cells", type=int, default=2,
                    help="edge cells in the simulated fleet")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--users", type=int, default=10, help="users per cell U")
    ap.add_argument("--models", type=int, default=10,
                    help="GenAI model types M")
    ap.add_argument("--frames", type=int, default=10,
                    help="frames per episode T")
    ap.add_argument("--slots", type=int, default=10, help="slots per frame K")
    ap.add_argument("--ticks", type=int, default=20,
                    help="queue ticks per slot")
    ap.add_argument("--rate", type=float, default=0.01,
                    help="Poisson arrivals per active user per second")
    ap.add_argument("--slo", type=float, default=40.0,
                    help="end-to-end latency SLO (seconds)")
    ap.add_argument("--queue-cap", type=float, default=64.0,
                    help="per-(cell,model) queue capacity in requests")
    ap.add_argument("--ckpt-dir", default=None,
                    help="checkpoint directory (default "
                         "<bench out>/ckpt)")
    ap.add_argument("--out", default="fleet.json",
                    help="output file name under experiments/bench/ "
                         "(or $REPRO_BENCH_OUT)")
    ap.add_argument("--obs-out", default=None,
                    help="JSONL telemetry log path; streams per-frame "
                         "fleet series (DESIGN.md §15)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI-scale sweep; asserts the sustained twin "
                         f"rate >= {SMOKE_RATE_FLOOR:.0e} requests/min")
    args = ap.parse_args()
    use_compile_cache(_ROOT)

    env = EnvCfg(U=args.users, M=args.models, T=args.frames, K=args.slots)
    fcfg = FleetCfg(ticks_per_slot=args.ticks,
                    arrivals_per_user_s=args.rate, slo=args.slo,
                    queue_cap=args.queue_cap)
    kw = dict(scenarios=args.scenarios.split(","),
              methods=args.methods.split(","), episodes=args.episodes,
              num_cells=args.num_cells, seed=args.seed, env=env, fcfg=fcfg,
              ckpt_dir=args.ckpt_dir, out_name=args.out,
              obs_out=args.obs_out)
    if args.smoke:
        print("--smoke: overriding scenario/method/size/rate flags with "
              "the CI preset")
        kw.update(scenarios=["paper-default", "flash-crowd"],
                  methods=["t2drl", "rcars"], episodes=2, num_cells=2,
                  env=EnvCfg(U=4, M=4, T=3, K=3),
                  fcfg=FleetCfg(ticks_per_slot=10, arrivals_per_user_s=1.0),
                  out_name="fleet_smoke.json")
    out = bench_fleet.run(**kw)
    if args.smoke:
        rate = out.get("sustained_requests_per_min", 0.0)
        if rate < SMOKE_RATE_FLOOR:
            print(f"FAIL: sustained twin rate {rate:.3g} req/min "
                  f"< {SMOKE_RATE_FLOOR:.0e}")
            raise SystemExit(1)
        print(f"smoke OK: {rate:.3g} simulated requests/min "
              f"(floor {SMOKE_RATE_FLOOR:.0e})")


if __name__ == "__main__":
    main()
