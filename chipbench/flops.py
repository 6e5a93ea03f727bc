"""Model FLOPs of the controller, worked out from a configuration's shapes.

Only matrix products count: 2 FLOPs per multiply-add.  A network that is
trained costs 3x its forward pass (forward, weight gradients, input
gradients); the critic inside the actor's loss costs 2x (forward and the
input gradient the actor needs).  Element-wise work (environment, Adam,
activations, the diffusion update) is not counted, and neither are
operations the compiler recomputes: these are the FLOPs the model needs,
not the FLOPs the device ran.
"""
from __future__ import annotations


def dense(dims, rows: int) -> int:
    """FLOPs of an MLP with layer widths ``dims`` over ``rows`` inputs."""
    return 2 * rows * sum(i * o for i, o in zip(dims[:-1], dims[1:]))


def actor_dims(c: dict):
    hidden = [c["actor_hidden"]] * c["actor_layers"]
    if c["actor"] == "ddpg":
        return [c["S"]] + hidden + [c["A"]]
    return [c["A"] + c["S"] + c["time_dim"]] + hidden + [c["A"]]


def actor(c: dict, rows: int) -> int:
    """One action per row: L denoiser passes, or one MLP pass for DDPG."""
    passes = 1 if c["actor"] == "ddpg" else c["L"]
    return passes * dense(actor_dims(c), rows)


def critic(c: dict, rows: int) -> int:
    return dense([c["S"] + c["A"]] + [c["critic_hidden"]] * c["critic_layers"]
                 + [1], rows)


def qnet(c: dict, rows: int) -> int:
    return dense([c["J"]] + [c["ddqn_hidden"]] * c["ddqn_layers"]
                 + [2 ** c["M"]], rows)


def d3pg_update(c: dict) -> int:
    """One allocator minibatch step: target action and value, critic
    step, actor step through the chain and the updated critic."""
    n = c["batch"]
    target = actor(c, n) + critic(c, n)
    return target + 3 * critic(c, n) + 3 * actor(c, n) + 2 * critic(c, n)


def ddqn_update(c: dict) -> int:
    """One cacher minibatch step: online Q trained on s, online argmax and
    target evaluation on s'."""
    n = c["ddqn_batch"]
    return 3 * qnet(c, n) + 2 * qnet(c, n)


def cell_episode(c: dict) -> int:
    """One training episode of one cell once past warm-up: every slot acts
    and updates the allocator, every frame acts the cacher, and the
    cacher updates on each of the ``T-1`` frame transitions."""
    T, K = c["T"], c["K"]
    return (T * K * (actor(c, 1) + d3pg_update(c)) + T * qnet(c, 1)
            + (T - 1) * ddqn_update(c))


def decision(c: dict) -> int:
    """One greedy per-slot allocation."""
    return actor(c, 1)
