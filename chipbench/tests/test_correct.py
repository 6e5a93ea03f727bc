"""``correct`` comes out false for the control (the reference in
bfloat16 in the program's place) and for each fault a cell can have,
planted in the program underneath an otherwise whole run."""
import json

import pytest

from chipbench import faults, harness
from chipbench.tests.conftest import N_ACTIONS, run_cell

TRAIN = "ddpg-t2drl-paper.train-b8"
DECIDE = "t2drl-paper.decide"


@pytest.mark.parametrize("name", [TRAIN, DECIDE])
def test_control_is_not_correct(tiny_root, jax_cpu, name):
    spec = harness.resolve_cell(tiny_root, name)
    driver = harness.load_module(spec["driver"], "chipbench_driver")
    numbers = driver.control(spec["config"], spec["traffic"], 11)
    numbers["window_compiles"] = 0
    checks = harness.judge(numbers, spec["limits"])
    assert not harness.all_within(checks), json.dumps(checks)


@pytest.mark.parametrize("fault,name", [
    ("frozen", TRAIN), ("frozen_ddqn", TRAIN), ("half_batch", TRAIN), ("action", TRAIN),
    ("reward", TRAIN), ("cache", TRAIN), ("action", DECIDE)])
def test_fault_is_not_correct(tiny_root, jax_cpu, fault, name):
    with faults.planted(fault, N_ACTIONS):
        out = run_cell(tiny_root, name, jax_cpu, seed=13)
    assert not out["correct"], json.dumps(out["checks"])
