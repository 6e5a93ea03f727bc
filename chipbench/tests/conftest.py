"""CPU rehearsals of the benchmark at tiny sizes.  Run by path:

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q chipbench/tests
"""
import copy
import json
import os
import shutil
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TINY_ENV = {"U": 4, "M": 4, "T": 5, "K": 2}
TINY_T2DRL = {"L": 2, "warmup": 5}
TINY_TRAIN = {"cells": 2, "episodes_per_chunk": 3}
N_ACTIONS = 2 ** TINY_ENV["M"]
# Entries of cells that are out of BENCHMARK.json while a fault of the
# program keeps them from proving correct (PERF.md, Open questions); their
# driver, traffic, limits and readers stay under test.
SHELVED = os.path.join(ROOT, "chipbench", "tests", "data", "shelved.json")


def shelved_cells() -> list:
    with open(SHELVED) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


def add_shelved(root: str) -> None:
    """Append the shelved entries to ``<root>/BENCHMARK.json``."""
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    with open(SHELVED) as f:
        for key, entries in json.load(f).items():
            bench[key].extend(entries)
    with open(path, "w") as f:
        json.dump(bench, f)


def tiny_config(config: dict) -> dict:
    """``config`` at a CPU size: fewer users, models, frames, slots and
    reverse steps; the networks keep their widths."""
    c = copy.deepcopy(config)
    c["env"].update(TINY_ENV)
    c["t2drl"].update(TINY_T2DRL)
    return c


def make_root(path) -> str:
    """A copy of the benchmark (``BENCHMARK.json`` with the shelved cells,
    and ``chipbench/``) at ``path`` with every configuration and the
    training traffic shrunk."""
    root = str(path)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    add_shelved(root)
    shutil.copytree(os.path.join(ROOT, "chipbench"), os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    cdir = os.path.join(root, "chipbench", "configs")
    for name in os.listdir(cdir):
        p = os.path.join(cdir, name)
        with open(p) as f:
            c = tiny_config(json.load(f))
        with open(p, "w") as f:
            json.dump(c, f)
    p = os.path.join(root, "chipbench", "traffic", "train-b8.json")
    with open(p) as f:
        t = json.load(f)
    t.update(TINY_TRAIN)
    with open(p, "w") as f:
        json.dump(t, f)
    return root


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench"))


@pytest.fixture(scope="session")
def jax_cpu():
    import jax
    jax.config.update("jax_enable_compilation_cache", False)
    return jax


def run_cell(root, name, jax, *, seed=5, seconds=0.5, trace=False):
    """One run of cell ``name`` of ``root`` on the CPU, past the look for
    a chip; returns the result object."""
    import time
    from chipbench import harness
    from chipbench import run
    spec = harness.resolve_cell(root, name)
    dev = harness.device_stamp(jax, spec["chips"], require_chip=False)
    return run.run(spec, jax, dev, seed, seconds, trace, time.perf_counter(),
                   {dev["kind"]: {"bf16_flops_per_s": 1e12}})
