"""Model FLOPs against a count by hand at a tiny size."""
from chipbench import flops


def _c(actor):
    # U=2, M=2: S = 4U+M = 10, A = 2U = 4, J = 3
    return {"actor": actor, "S": 10, "A": 4, "J": 3, "M": 2, "T": 3, "K": 2,
            "L": 2, "time_dim": 16, "actor_hidden": 8, "actor_layers": 2,
            "critic_hidden": 6, "critic_layers": 1, "ddqn_hidden": 5,
            "ddqn_layers": 1, "batch": 4, "ddqn_batch": 2}


def test_counts_by_hand():
    c = _c("d3pg")
    # denoiser [4+10+16=30, 8, 8, 4]: 30*8 + 8*8 + 8*4 = 336 MACs per row
    assert flops.actor(c, 1) == 2 * 2 * 336           # L = 2 passes
    assert flops.decision(c) == 1344
    # critic [14, 6, 1]: 14*6 + 6 = 90 MACs; qnet [3, 5, 4]: 15 + 20 = 35
    assert flops.critic(c, 4) == 2 * 4 * 90
    assert flops.qnet(c, 1) == 70
    actor4, critic4 = 4 * 1344, 720
    # target action + value, critic 3x, actor 3x, critic input-grad 2x
    assert flops.d3pg_update(c) == actor4 + critic4 + 3 * critic4 + 3 * actor4 + 2 * critic4
    assert flops.ddqn_update(c) == 5 * 2 * 70
    assert flops.cell_episode(c) == (6 * (1344 + flops.d3pg_update(c))
                                     + 3 * 70 + 2 * 700)


def test_ddpg_actor_is_one_mlp_pass():
    c = _c("ddpg")
    # [10, 8, 8, 4]: 80 + 64 + 32 = 176 MACs
    assert flops.actor(c, 3) == 2 * 3 * 176


def test_paper_sizes():
    import json
    import os
    from chipbench import reference
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "configs", "t2drl-paper.json")) as f:
        c = reference.shapes(json.load(f))
    # about 19 GFLOP per cell-episode, nearly all in the D3PG update
    assert 15e9 < flops.cell_episode(c) < 25e9
    assert flops.d3pg_update(c) * 100 > 0.95 * flops.cell_episode(c)
