"""Benchmark harness entry point — one bench per paper table/figure.

  python -m benchmarks.run             # quick pass (CI scale)
  python -m benchmarks.run --full      # paper-scale episode counts
  python -m benchmarks.run --only runtime,roofline
"""
from __future__ import annotations

import argparse
import os
import time

from repro.launch.compile_cache import use_compile_cache

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default="",
                    help="comma list: convergence,users,cache,runtime,"
                         "roofline,scenarios,fleet,population")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-scale smoke: runtime runs the throughput "
                         "floor + independent fused gates, population "
                         "runs the one-compile 16-member sweep, cache "
                         "runs the DDQN-vs-classical cacher scoreboard")
    args = ap.parse_args()
    use_compile_cache(_ROOT)
    only = set(args.only.split(",")) if args.only else None
    episodes = 500 if args.full else 60

    def want(name):
        return only is None or name in only

    t0 = time.time()
    if want("runtime"):
        from . import bench_runtime
        if args.smoke:
            print("== runtime smoke: throughput floor + fused gates ==",
                  flush=True)
            bench_runtime.run_smoke()
        else:
            print("== Table 3: per-slot running time ==", flush=True)
            bench_runtime.run(users=(10, 12, 14, 16, 18))
            print("\n== vector-env training throughput ==", flush=True)
            bench_runtime.run_throughput((1, 8), episodes=4)
    if want("population"):
        from . import bench_population
        if args.smoke:
            print("\n== population smoke: 16-member sweep, one compile ==",
                  flush=True)
            bench_population.run_smoke()
        else:
            print("\n== population sweep: fused hyperparameter grid ==",
                  flush=True)
            bench_population.run(episodes=episodes if args.full else 40)
    if want("roofline"):
        print("\n== §Roofline: dry-run table ==", flush=True)
        from . import bench_roofline
        bench_roofline.run()
    if want("convergence"):
        print("\n== Fig 6: convergence ==", flush=True)
        from . import bench_convergence
        bench_convergence.run(episodes=episodes,
                              Ls=(1, 5, 10) if not args.full
                              else (1, 5, 10, 20))
    if want("users"):
        print("\n== Fig 7: users sweep ==", flush=True)
        from . import bench_users
        bench_users.run(users=(10, 14, 18) if not args.full
                        else (10, 12, 14, 16, 18), episodes=episodes)
    if want("cache"):
        from . import bench_cache
        if args.smoke:
            print("\n== cache smoke: DDQN vs classical cacher scoreboard ==",
                  flush=True)
            bench_cache.run_smoke()
        else:
            print("\n== Fig 8: cache sweep ==", flush=True)
            bench_cache.run(capacities=(20.0, 26.0, 32.0) if not args.full
                            else (20.0, 23.0, 26.0, 29.0, 32.0),
                            episodes=episodes)
    if want("scenarios"):
        print("\n== scenario registry: workloads x methods ==", flush=True)
        from . import bench_scenarios
        bench_scenarios.run(episodes=episodes, num_envs=2 if not args.full
                            else 4)
    if want("fleet"):
        print("\n== fleet twin: request-level tail latency ==", flush=True)
        from . import bench_fleet
        bench_fleet.run(scenarios=("all",) if args.full
                        else ("paper-default", "flash-crowd"),
                        episodes=episodes,
                        num_cells=4 if args.full else 2)
    print(f"\nall benchmarks done in {time.time() - t0:.0f}s "
          f"(results in experiments/bench/)")


if __name__ == "__main__":
    main()
