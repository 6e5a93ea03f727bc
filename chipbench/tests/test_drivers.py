"""Every driver through the whole run at tiny sizes on the CPU: set-up,
window, the reference's check, the end-to-end and per-layer metrics."""
import json
import os

import pytest

from chipbench.tests.conftest import ROOT, run_cell, shelved_cells


def _cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]] + shelved_cells()


@pytest.mark.parametrize("name", _cells())
def test_cell_runs_and_is_correct(tiny_root, jax_cpu, name):
    from chipbench import harness
    spec = harness.resolve_cell(tiny_root, name)
    out = run_cell(tiny_root, name, jax_cpu)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {m["name"] for m in spec["end_to_end"]}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert set(out["checks"]) == set(spec["limits"]["limits"])
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("name", _cells())
def test_traced_run_reads_per_layer_metrics(tiny_root, jax_cpu, name):
    """On the CPU there is no device plane: the readers of the device
    trace return nothing and are left out; the others are read."""
    from chipbench import harness
    spec = harness.resolve_cell(tiny_root, name)
    out = run_cell(tiny_root, name, jax_cpu, trace=True)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {m["name"] for m in spec["per_layer"]
                                   if m["source"] != "device_trace"}
    assert "compile_s" in out["metrics"]
