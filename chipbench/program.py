"""The system under test, as the benchmark sees it: the program's
configuration built from a configuration file, checked against the
widths that file states.  Nothing else of the program is wrapped here;
drivers call its public entry points directly."""
from __future__ import annotations

import sys

from chipbench.harness import SRC

if SRC not in sys.path:
    sys.path.insert(0, SRC)

from repro.core import EnvCfg, T2DRLCfg  # noqa: E402
from repro.diffusion.denoiser import TIME_DIM  # noqa: E402


def _tuples(x):
    return tuple(_tuples(v) for v in x) if isinstance(x, list) else x


def t2drl_cfg(config: dict) -> T2DRLCfg:
    """The program's ``T2DRLCfg`` for ``config``.  Raises ``ValueError``
    when a width or constant the program fixes itself differs from the
    file, so a run never measures another model than the one named."""
    env = EnvCfg(**{k: _tuples(v) for k, v in config["env"].items()})
    cfg = T2DRLCfg(env=env, **config["t2drl"])
    d3, dq = cfg.d3pg_cfg(), cfg.ddqn_cfg()
    d, q = config["d3pg"], config["ddqn"]
    program = {
        "actor_hidden": d3.actor_hidden, "actor_layers": d3.actor_layers,
        "time_dim": TIME_DIM, "critic_hidden": d3.critic_hidden,
        "critic_layers": d3.critic_layers, "batch": d3.batch,
        "replay": d3.buffer, "omega": d3.omega, "tau_target": d3.eps_target,
        "beta_min": d3.beta_min, "beta_max": d3.beta_max,
        "explore_sigma": d3.explore_sigma,
        "ddqn_hidden": dq.hidden, "ddqn_layers": dq.n_hidden,
        "ddqn_batch": dq.batch, "ddqn_replay": dq.buffer,
        "ddqn_discount": dq.rho, "ddqn_tau_target": dq.kappa}
    stated = {**d, **q}
    wrong = {k: (stated[k], v) for k, v in program.items() if stated[k] != v}
    if wrong:
        raise ValueError(f"{config['name']}: the program runs other sizes "
                         f"than the file states (file, program): {wrong}")
    return cfg
