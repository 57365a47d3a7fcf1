"""The harness end to end at a tiny size on the CPU, finding cells,
configurations, metrics, references and FLOP counts by name, checking the
program against each configuration file, and refusing to run off a TPU."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import harness
from bench.tests.tiny import REPO, TINY_LIMITS, TINY_TRAFFIC, result_of, run_cell, tiny_copy

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


OTHER_REFERENCE = '''"""The plain reference of another architecture: for the test, the Qwen2
reference under a module and a name of its own."""
import sys

from bench import reference as qwen2

VARIANTS, ROWS_PER_BLOCK, init_params = qwen2.VARIANTS, qwen2.ROWS_PER_BLOCK, qwen2.init_params


class Reference(qwen2.Reference):
    def run(self, seed, batches):
        print("[reference_other] run", file=sys.stderr)
        return super().run(seed, batches)
'''
OTHER_FLOPS = 7.0e12


def test_a_new_architecture_enters_with_new_files_only(tmp_path):
    """A configuration file names a reference module and a FLOP module that
    are new files; the run compares with that reference and reports ``mfu``
    from that count, and no file that was there changes."""
    root = tiny_copy(tmp_path / "b")
    bench = root / "bench"
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    (bench / "reference_other.py").write_text(OTHER_REFERENCE)
    (bench / "flops_other.py").write_text(
        f"def train_step_flops(config, batch, seq):\n    return {OTHER_FLOPS!r}\n")
    cfg = json.loads((bench / "configs" / "qwen2.5-3b.json").read_text())
    cfg.update(name="other-arch", reference="bench/reference_other.py",
               flops="bench/flops_other.py")
    (bench / "configs" / "other-arch.json").write_text(json.dumps(cfg))
    cell = "other-arch.themis.1chip"
    (bench / "limits" / f"{cell}.json").write_text(
        (bench / "limits" / "qwen2.5-3b.themis.1chip.json").read_text())
    bm = json.loads((root / "BENCHMARK.json").read_text())
    bm["configs"].append({"name": cfg["name"], "source": cfg["source"],
                          "file": "bench/configs/other-arch.json", "reduced": [],
                          "why": "test"})
    bm["workloads"].append({"name": cell, "config": cfg["name"], "traffic": "themis.1chip",
                            "chips": 1, "why": "test"})
    # mfu is a per-layer metric, read from traced runs, which need a TPU: the
    # copy reports it untraced in the new cell
    mfu = next(m for m in bm["per_layer"] if m["name"] == "mfu")
    bm["end_to_end"].append({**mfu, "bound": 0.05, "workloads": [cell]})
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    proc = run_cell(root, cell, devices=1)
    res = result_of(proc)
    assert res["correct"] is True, res["checks"]
    assert "[reference_other] run" in proc.stderr
    tokens_per_step = TINY_TRAFFIC["batch_per_chip"] * TINY_TRAFFIC["seq"]
    # mfu / tokens_per_s = 100 F / (tokens per step x chips x peak); the copy's peak is 1e12
    assert res["metrics"]["mfu"]["value"] / res["metrics"]["tokens_per_s"]["value"] == \
        pytest.approx(100 * OTHER_FLOPS / (tokens_per_step * 1e12), rel=1e-9)
    assert all(before[p] == p.read_bytes() for p in before)


def _config(name):
    return json.loads((REPO / "bench" / "configs" / f"{name}.json").read_text())


# What the program had to hold for each file before the configuration files
# named their architecture's settings (``program.expect``).
WANT = {
    "qwen2.5-3b": {
        "d_model": 2048, "d_ff": 11008, "num_heads": 16, "num_kv_heads": 2,
        "resolved_head_dim": 128, "num_layers": 3, "vocab_size": 151936, "norm_eps": 1e-06,
        "rope_theta": 1000000.0, "tie_embeddings": True, "dtype": "bfloat16",
        "param_dtype": "float32", "family": "dense", "qkv_bias": True, "gated_mlp": True},
    "qwen2.5-14b": {
        "d_model": 5120, "d_ff": 13824, "num_heads": 40, "num_kv_heads": 8,
        "resolved_head_dim": 128, "num_layers": 1, "vocab_size": 19008, "norm_eps": 1e-05,
        "rope_theta": 1000000.0, "tie_embeddings": False, "dtype": "bfloat16",
        "param_dtype": "float32", "family": "dense", "qkv_bias": True, "gated_mlp": True},
}


@pytest.mark.parametrize("name", sorted(WANT))
def test_real_configs_check_what_they_checked_before(name):
    c = _config(name)
    assert harness.program_want(c) == WANT[name]
    cfg = harness.program_config(c)
    assert all(getattr(cfg, k) == v for k, v in WANT[name].items())


@pytest.mark.parametrize("change", [
    {"qkv_bias": False}, {"family": "moe"}, {"gated_mlp": False},
    {"resolved_head_dim": 64}, {"no_such_attribute": 1}])
def test_program_expect_that_disagrees_with_the_program_raises(change):
    c = _config("qwen2.5-3b")
    c["program"]["expect"].update(change)
    with pytest.raises((ValueError, AttributeError)):
        harness.program_config(c)


@pytest.mark.parametrize("key", ["hidden_size", "vocab_size", "rms_norm_eps",
                                 "tie_word_embeddings"])
def test_a_decoder_size_the_file_lacks_raises(key):
    c = _config("qwen2.5-14b")
    del c[key]
    with pytest.raises(KeyError, match=key):
        harness.program_want(c)


@pytest.mark.parametrize("key", ["reference", "flops"])
def test_reference_and_flops_are_required_and_named_by_path(key):
    c = _config("qwen2.5-3b")
    assert harness.config_module(c, key).__name__ == f"bench.{key}"
    for bad in ("/bench/reference.py", "bench/../bench/flops.py", "bench/flops.json"):
        with pytest.raises(ValueError):
            harness.config_module({**c, key: bad}, key)
    del c[key]
    with pytest.raises(KeyError):
        harness.config_module(c, key)


def test_the_harness_names_no_architecture():
    src = (REPO / "bench" / "harness.py").read_text()
    for word in ("qkv_bias", "gated_mlp", "family", "Qwen", "bench.reference",
                 "bench.flops", "import flops", "import reference"):
        assert word not in src, word
