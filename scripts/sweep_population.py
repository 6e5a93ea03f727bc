"""Population-based hyperparameter sweep CLI (DESIGN.md §13).

  python scripts/sweep_population.py --smoke
  python scripts/sweep_population.py --episodes 120 --top 10
  python scripts/sweep_population.py --updates-per-slot 1,2

Thin CLI over ``benchmarks.bench_population`` (adds repo paths itself, so
no PYTHONPATH needed).  Trains the stock 16-member hyperparameter grid —
epsilon schedules x actor/critic LR x DDQN LR x reward shaping — as ONE
fused ``run_training`` call per static group (``--updates-per-slot`` with
N distinct values costs N compiles, crossing the grid to 16N members),
greedily evaluates every member, and prints the leaderboard against the
RCARS baseline.  Results land in ``experiments/bench/population.json``.

``--smoke`` is the CI preset: the full 16-member grid on a reduced
environment, asserting the whole sweep ran as one compiled call.
"""
from __future__ import annotations

import argparse
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (_ROOT, os.path.join(_ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def main() -> None:
    ap = argparse.ArgumentParser(
        description="fused population-based hyperparameter sweep")
    ap.add_argument("--episodes", type=int, default=40,
                    help="training episodes per member (default 40)")
    ap.add_argument("--eval-episodes", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--top", type=int, default=8,
                    help="leaderboard rows to print")
    ap.add_argument("--updates-per-slot", default="1",
                    help="comma list of static updates_per_slot values; "
                         "each distinct value is its own compile group "
                         "(grid grows by the same factor)")
    ap.add_argument("--out", default="population.json",
                    help="output JSON name under experiments/bench/")
    ap.add_argument("--smoke", action="store_true",
                    help="CI preset: full 16-member grid, reduced env, "
                         "assert one compiled call")
    args = ap.parse_args()

    from benchmarks import bench_population
    from repro.core import default_grid
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache(_ROOT)

    ups = tuple(int(v) for v in args.updates_per_slot.split(","))
    grid = default_grid(updates_per_slot=ups)
    if args.smoke:
        if len(ups) != 1:
            ap.error("--smoke asserts a single compile group; drop "
                     "--updates-per-slot")
        bench_population.run_smoke()
        return
    bench_population.run(episodes=args.episodes,
                         eval_episodes=args.eval_episodes, grid=grid,
                         seed=args.seed, out_name=args.out, top=args.top)


if __name__ == "__main__":
    main()
