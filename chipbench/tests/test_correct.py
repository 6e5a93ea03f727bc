"""``correct`` comes out false for the control (the reference in
bfloat16 in the program's place) and for each fault a cell can have,
planted in the program underneath an otherwise whole run."""
import json

import pytest

from chipbench import faults, harness
from chipbench.tests.conftest import N_ACTIONS, run_cell

TRAIN = "ddpg-t2drl-paper.train-b8"
DECIDE = "t2drl-paper.decide"
TWIN = "t2drl-paper.twin-c8"


@pytest.mark.parametrize("name", [TRAIN, DECIDE, TWIN])
def test_control_is_not_correct(tiny_root, jax_cpu, name):
    spec = harness.resolve_cell(tiny_root, name)
    driver = harness.load_module(spec["driver"], "chipbench_driver")
    numbers = driver.control(spec["config"], spec["traffic"], 11)
    numbers["window_compiles"] = 0
    checks = harness.judge(numbers, spec["limits"])
    assert not harness.all_within(checks), json.dumps(checks)


# At the tiny size seed 13's twin policy caches all models or none in
# every popularity state, which a roll leaves as it is; seed 5's does not.
@pytest.mark.parametrize("fault,name,seed", [
    ("frozen", TRAIN, 13), ("frozen_ddqn", TRAIN, 13), ("half_batch", TRAIN, 13),
    ("action", TRAIN, 13), ("reward", TRAIN, 13), ("cache", TRAIN, 13),
    ("action", DECIDE, 13), ("action", TWIN, 5), ("twin_cache", TWIN, 5),
    ("frame_cache", TWIN, 5), ("twin_mix", TWIN, 5), ("twin_service", TWIN, 5)])
def test_fault_is_not_correct(tiny_root, jax_cpu, fault, name, seed):
    with faults.planted(fault, N_ACTIONS):
        out = run_cell(tiny_root, name, jax_cpu, seed=seed)
    assert not out["correct"], json.dumps(out["checks"])
