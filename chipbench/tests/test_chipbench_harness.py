"""The harness finds every configuration, traffic mix, unit and metric of
``BENCHMARK.json`` by name, and a measured run with no TPU fails."""

import importlib
import json
import os
import subprocess
import sys

import pytest

from chipbench.tests.helpers import ROOT

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("workload", CELLS)
def test_cell_is_found_by_name(workload):
    from chipbench import run
    spec = run.load_cell(ROOT, workload)
    assert spec["config"]["name"] == spec["cell"]["config"]
    unit = importlib.import_module(
        f"chipbench.units.{spec['traffic']['unit']}")
    assert set(unit.work(spec["config"])) >= {"pde_fwd"}
    names = [m["name"] for m in spec["end_to_end"]]
    assert "setup_s" in names and len(names) >= 2
    assert spec["per_layer"]
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert callable(run.reader(m["name"]))
    # every limit of the configuration is a number compared in the cell
    assert spec["config"]["limits"]


def test_every_metric_and_config_has_its_file():
    for c in BENCH["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
    from chipbench import run
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert callable(run.reader(m["name"]))


def test_new_files_and_entries_are_enough(tmp_path, monkeypatch):
    """A cell, a configuration, a traffic mix and a metric added as new
    files and entries are found without an edit to any file."""
    from chipbench import metrics, run
    root = tmp_path / "checkout"
    for sub in ("configs", "traffic"):
        (root / "chipbench" / sub).mkdir(parents=True)
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append({"name": "mmd_small", "config": "tiny",
                               "traffic": "tiny_loop", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "unit_count.small", "unit": "1",
                               "better": "higher", "source": "host_clock",
                               "layer": "harness", "moves": "train_step_s",
                               "workloads": ["mmd_small"]})
    bench["end_to_end"][1]["workloads"].append("mmd_small")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cfg = json.loads(open(os.path.join(
        ROOT, "chipbench", "configs", "sigmmd_gbm_d3_L128.json")).read())
    cfg["name"] = "tiny"
    (root / "chipbench" / "configs" / "tiny.json").write_text(json.dumps(cfg))
    (root / "chipbench" / "traffic" / "tiny_loop.json").write_text(
        json.dumps({"unit": "mmd_sgd_step", "loop": "closed",
                    "pool_batches": 2, "check_steps": 3}))
    extra = tmp_path / "metrics"
    extra.mkdir()
    (extra / "unit_count.py").write_text(
        "def read(ctx, variant=None):\n    return float(ctx.units)\n")
    monkeypatch.setattr(metrics, "__path__", [*metrics.__path__, str(extra)])
    spec = run.load_cell(str(root), "mmd_small")
    assert spec["traffic"]["pool_batches"] == 2
    assert [m["name"] for m in spec["end_to_end"]] == ["setup_s",
                                                       "train_step_s"]
    assert [m["name"] for m in spec["per_layer"]] == ["unit_count.small"]

    class Ctx:
        units = 7
    assert run.reader("unit_count.small")(Ctx) == 7.0


def test_measured_run_without_a_tpu_fails_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", CELLS[0], "--seed", str(2**31 + 1), "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert "metrics" not in proc.stdout and "{" not in proc.stdout


def test_run_in_a_bare_directory_fails(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files
    gives no result: the run exits non-zero and prints nothing on
    stdout (here for want of a TPU; on a chip, of the program)."""
    import shutil
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout == ""
