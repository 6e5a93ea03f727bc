"""Cells, configurations and metrics are found by name from files; the
harness refuses to run without a TPU."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench import harness
from chipbench.tests.conftest import ROOT, add_shelved, shelved_cells


def _bench(root):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def test_every_cell_resolves():
    for w in _bench(ROOT)["workloads"]:
        spec = harness.resolve_cell(ROOT, w["name"])
        assert spec["config"]["name"] == w["config"]
        assert {m["name"] for m in spec["end_to_end"]} >= {"setup_s"}
        assert len(spec["end_to_end"]) >= 2 and spec["per_layer"]
        assert "window_compiles" in spec["limits"]["limits"]


def test_shelved_cells_resolve(tmp_path):
    """A shelved cell comes back by its entries in BENCHMARK.json alone:
    its configuration, traffic, driver, limits and readers are all here."""
    root = str(tmp_path)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    add_shelved(root)
    shutil.copytree(os.path.join(ROOT, "chipbench"), os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    for name in shelved_cells():
        spec = harness.resolve_cell(root, name)
        assert len(spec["end_to_end"]) >= 2 and spec["per_layer"]
        assert "window_compiles" in spec["limits"]["limits"]


def test_unknown_cell_is_refused():
    with pytest.raises(harness.CellError):
        harness.resolve_cell(ROOT, "no-such-cell")


def test_added_files_make_a_new_cell(tmp_path):
    """A configuration, a traffic mix, a per-layer metric and a cell added
    as files alone are found by name."""
    root = str(tmp_path)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    add_shelved(root)
    shutil.copytree(os.path.join(ROOT, "chipbench"), os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bdir = os.path.join(root, "chipbench")
    with open(os.path.join(bdir, "configs", "t2drl-paper.json")) as f:
        cfg = json.load(f)
    cfg["name"] = "t2drl-u18"
    cfg["env"]["U"] = 18
    with open(os.path.join(bdir, "configs", "t2drl-u18.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(bdir, "traffic", "decide.json")) as f:
        traffic = json.load(f)
    traffic["pool_states"] = 200
    traffic["pool_keys"] = 199
    with open(os.path.join(bdir, "traffic", "decide-long.json"), "w") as f:
        json.dump(traffic, f)
    with open(os.path.join(bdir, "metrics", "decide_calls.py"), "w") as f:
        f.write("def read(rec):\n    return rec['run']['units']\n")
    shutil.copy(os.path.join(bdir, "limits", "t2drl-paper.decide.json"),
                os.path.join(bdir, "limits", "t2drl-u18.decide-long.json"))
    bench = _bench(root)
    bench["configs"].append({"name": "t2drl-u18", "source": "https://arxiv.org/abs/2411.01458",
                             "file": "chipbench/configs/t2drl-u18.json",
                             "reduced": [], "why": "18 users"})
    bench["workloads"].append({"name": "t2drl-u18.decide-long", "config": "t2drl-u18",
                               "traffic": "decide-long", "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "decide_ms":
            m["workloads"].append("t2drl-u18.decide-long")
    bench["per_layer"].append({"name": "decide_calls", "unit": "calls", "better": "higher",
                               "source": "program_counter", "layer": "greedy decision",
                               "moves": "decide_ms"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    spec = harness.resolve_cell(root, "t2drl-u18.decide-long")
    assert spec["config"]["env"]["U"] == 18
    assert spec["traffic"]["pool_states"] == 200
    assert spec["driver"].endswith(os.path.join("drivers", "decide.py"))
    assert [m["name"] for m in spec["end_to_end"]] == ["setup_s", "decide_ms"]
    assert "decide_calls" in {m["name"] for m in spec["per_layer"]}
    reader = harness.load_module(
        [m for m in spec["per_layer"] if m["name"] == "decide_calls"][0]["reader"], "r")
    assert reader.read({"run": {"units": 7}}) == 7
    # a metric without a workloads key goes to every cell reporting what it moves
    assert "decide_calls" not in {m["name"] for m in
                                  harness.resolve_cell(root, "ddpg-t2drl-paper.train-b8")["per_layer"]}


def test_no_tpu_no_result(capsys):
    from chipbench import run
    rc = run.main(["--workload", "t2drl-paper.decide", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    assert rc != 0
    assert capsys.readouterr().out == ""


def test_command_without_program_fails(tmp_path):
    """In a directory holding only BENCHMARK.json and the benchmark's
    files, the command exits non-zero and prints no result."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run([sys.executable, "chipbench/run.py", "--workload",
                        "t2drl-paper.decide", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout == ""
