"""Scenario evaluation harness CLI — sweep workloads × methods.

  python scripts/eval_scenarios.py --scenarios all --methods t2drl,rcars \
      --num-envs 4

Thin CLI over ``benchmarks.bench_scenarios`` (adds repo paths itself, so no
PYTHONPATH needed).  Per-scenario reward/quality/latency breakdowns land in
experiments/bench/scenarios.json (schema in benchmarks/README.md).

Presets:

  --preset long-horizon   500-episode shared-learner run on the paper
                          workload (8 cells feeding one learner) — the
                          ROADMAP convergence open item: does T2DRL beat
                          RCARS once trained at the paper's episode count?
                          Uses the DESIGN.md §12 schedule levers (cosine
                          epsilon decay + cosine actor/critic LR warmdown
                          over 400 episodes); override with
                          --eps-schedule/--lr-schedule/--lr-warmdown-episodes.
  --smoke                 tiny CI-scale sweep (seconds, 2 cells): used by
                          the CI docs job and tests/test_scenarios.py.
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
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from benchmarks import bench_scenarios             # noqa: E402

PRESETS = {
    # The ROADMAP convergence run, now with the schedule levers of
    # DESIGN.md §12: cosine epsilon decay (holds exploration longer before
    # annealing over the 300-episode eps horizon) and a cosine actor/critic
    # LR warmdown to 10% over 400 episodes, so late episodes fine-tune
    # instead of thrashing the shared learner.
    "long-horizon": dict(
        scenarios=["paper-default"], methods=["t2drl", "rcars"],
        episodes=500, eval_episodes=10, num_envs=8, policy="shared",
        out_name="scenarios_long_horizon.json",
        cfg_overrides=dict(eps_schedule="cosine", lr_schedule="cosine",
                           lr_warmdown_episodes=400, lr_end_scale=0.1)),
}


def main():
    ap = argparse.ArgumentParser(
        description="Sweep workload scenarios x methods; JSON breakdowns "
                    "to experiments/bench/.")
    ap.add_argument("--scenarios", default="all",
                    help="comma list of registered scenarios, or 'all'")
    ap.add_argument("--methods", default="t2drl,rcars",
                    help="comma list from t2drl,ddpg,schrs,rcars")
    ap.add_argument("--episodes", type=int, default=25,
                    help="training episodes for the learned methods")
    ap.add_argument("--eval-episodes", type=int, default=5)
    ap.add_argument("--num-envs", type=int, default=2,
                    help="parallel edge cells per scenario")
    ap.add_argument("--policy", default="shared",
                    choices=("independent", "shared"))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--users", type=int, default=10, help="users per cell U")
    ap.add_argument("--models", type=int, default=10,
                    help="GenAI model types M")
    ap.add_argument("--frames", type=int, default=10,
                    help="frames per episode T")
    ap.add_argument("--slots", type=int, default=10, help="slots per frame K")
    ap.add_argument("--out", default="scenarios.json",
                    help="output file name under experiments/bench/ "
                         "(or $REPRO_BENCH_OUT)")
    # schedule flags default to None so an explicitly-passed flag can be
    # told apart from "unset" and win over a --preset's cfg_overrides
    ap.add_argument("--eps-schedule", default=None,
                    choices=("linear", "cosine"),
                    help="epsilon/sigma decay shape (T2DRLCfg.eps_schedule)")
    ap.add_argument("--lr-schedule", default=None,
                    choices=("const", "linear", "cosine"),
                    help="actor/critic LR warmdown shape")
    ap.add_argument("--lr-warmdown-episodes", type=int, default=None,
                    help="LR warmdown horizon in episodes")
    ap.add_argument("--lr-end-scale", type=float, default=None,
                    help="final LR as a fraction of the initial rate")
    ap.add_argument("--preset", choices=sorted(PRESETS),
                    help="named run configuration (overrides the non-"
                         "schedule flags it sets; explicit schedule flags "
                         "win over the preset's)")
    ap.add_argument("--obs-out", default=None,
                    help="JSONL telemetry log path; enables in-scan "
                         "learner diagnostics (DESIGN.md §15)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI-scale sweep (overrides sizes/episodes)")
    args = ap.parse_args()
    use_compile_cache(_ROOT)

    flag_overrides = {k: v for k, v in dict(
        eps_schedule=args.eps_schedule, lr_schedule=args.lr_schedule,
        lr_warmdown_episodes=args.lr_warmdown_episodes,
        lr_end_scale=args.lr_end_scale).items() if v is not None}
    kw = dict(scenarios=args.scenarios.split(","),
              methods=args.methods.split(","), episodes=args.episodes,
              eval_episodes=args.eval_episodes, num_envs=args.num_envs,
              policy=args.policy, seed=args.seed, out_name=args.out,
              obs_out=args.obs_out,
              env=EnvCfg(U=args.users, M=args.models, T=args.frames,
                         K=args.slots))
    if args.preset:
        kw.update(PRESETS[args.preset])
    kw["cfg_overrides"] = {**kw.get("cfg_overrides", {}), **flag_overrides}
    if args.smoke:
        kw.update(episodes=2, eval_episodes=2, num_envs=2,
                  env=EnvCfg(U=4, M=4, T=3, K=3),
                  out_name="scenarios_smoke.json")
    bench_scenarios.run(**kw)


if __name__ == "__main__":
    main()
