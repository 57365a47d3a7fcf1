"""The harness end to end at a tiny size on the CPU, finding cells,
configurations and metrics by name, and refusing to run off a TPU."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench.tests.tiny import REPO, TINY_LIMITS, result_of, run_cell, tiny_copy

BM = json.loads((REPO / "BENCHMARK.json").read_text())
CELLS = [(w["name"], w["chips"]) for w in BM["workloads"]]


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return tiny_copy(tmp_path_factory.mktemp("tiny"))


@pytest.mark.parametrize("workload,chips", CELLS)
def test_every_cell_runs_end_to_end(tiny, workload, chips):
    res = result_of(run_cell(tiny, workload, devices=chips, seed=2**31 + 11))
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    wanted = {m["name"] for m in BM["end_to_end"]
              if workload in m.get("workloads", [workload])}
    assert set(res["metrics"]) == wanted
    assert all(m["value"] > 0 for k, m in res["metrics"].items() if k != "peak_hbm_gib")
    assert res["device"] == {"platform": "cpu", "kind": "cpu", "count": chips,
                             "memory_peak_bytes": 0}
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == set(TINY_LIMITS)


def test_a_new_config_cell_and_metric_need_no_edit(tmp_path):
    """A later PR adds a configuration, a traffic mix, a cell and a metric as
    new files and new entries, and edits no file that is there."""
    root = tiny_copy(tmp_path / "b")
    bench = root / "bench"
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    cfg = json.loads((bench / "configs" / "qwen2.5-3b.json").read_text())
    cfg.update(name="qwen2.5-3b-deep", num_hidden_layers=3)
    cfg["program"]["replace"]["num_layers"] = 3
    (bench / "configs" / "qwen2.5-3b-deep.json").write_text(json.dumps(cfg))
    mix = json.loads((bench / "traffic" / "themis.1chip.json").read_text())
    mix.update(batch_per_chip=1, chunks=2)
    (bench / "traffic" / "themis.1chip.b1.json").write_text(json.dumps(mix))
    cell = "qwen2.5-3b-deep.themis.1chip.b1"
    (bench / "limits" / f"{cell}.json").write_text(
        (bench / "limits" / "qwen2.5-3b.themis.1chip.json").read_text())
    (bench / "metrics" / "window_steps.py").write_text(
        "def read(rec):\n    return float(rec['steps'])\n")
    bm = json.loads((root / "BENCHMARK.json").read_text())
    bm["configs"].append({"name": cfg["name"], "source": cfg["source"],
                          "file": "bench/configs/qwen2.5-3b-deep.json",
                          "reduced": ["num_hidden_layers"], "why": "test"})
    bm["workloads"].append({"name": cell, "config": cfg["name"],
                            "traffic": "themis.1chip.b1", "chips": 1, "why": "test"})
    bm["end_to_end"].append({"name": "window_steps", "unit": "steps", "better": "higher",
                             "bound": 0.05, "source": "host_clock", "workloads": [cell]})
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    res = result_of(run_cell(root, cell, devices=1))
    assert res["correct"] is True
    assert res["metrics"]["window_steps"]["value"] == res["attempted"]
    assert all(before[p] == p.read_bytes() for p in before)
    # the new metric is reported only where it is listed
    old = result_of(run_cell(root, "qwen2.5-3b.themis.1chip", devices=1))
    assert "window_steps" not in old["metrics"]


def _bare_env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return env


def test_off_tpu_the_run_fails_and_prints_no_result():
    cmd = BM["command"] + ["--workload", "qwen2.5-3b.themis.1chip", "--seed", "1",
                           "--seconds", "1", "--trace", "0"]
    p = subprocess.run(cmd, cwd=REPO, env=_bare_env(), capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert p.stdout.strip() == ""


def test_without_the_program_the_run_fails_and_prints_no_result(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    cmd = [sys.executable] + BM["command"][1:] + [
        "--workload", "qwen2.5-3b.themis.1chip", "--seed", "1", "--seconds", "1",
        "--trace", "0"]
    p = subprocess.run(cmd, cwd=tmp_path, env=_bare_env(), capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
