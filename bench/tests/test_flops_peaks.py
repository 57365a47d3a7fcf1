"""The model FLOP count and the table of peaks."""
import json

import pytest

from bench import flops, harness, peaks
from bench.harness import ROOT


def _config(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())


def test_qwen25_3b_three_layers_by_hand():
    c = _config("qwen2.5-3b")
    d, f, layers, vocab = 2048, 11008, 3, 151936
    per_layer = d * d + 2 * d * 256 + d * d + 3 * d * f  # q, k, v (2 kv heads of 128), o, MLP
    assert per_layer == 77_070_336
    n = layers * per_layer + vocab * d  # the tied embedding counts once, as the head
    assert n == 542_375_936
    tokens = 4 * 1024
    attention = 12 * layers * d * 1024 * tokens
    assert flops.train_step_flops(c, 4, 1024) == 6 * n * tokens + attention
    assert flops.train_step_flops(c, 4, 1024) == pytest.approx(1.364e13, rel=1e-3)


def test_qwen25_14b_counts_its_untied_head_and_not_its_input_embedding():
    c = _config("qwen2.5-14b")
    d, f, vocab = 5120, 13824, 19008
    layer = 2 * d * d + 2 * d * 1024 + 3 * d * f
    assert flops.matmul_params(c) == layer + d * vocab
    # tying the head would not change the count: the input embedding is a gather
    assert flops.matmul_params({**c, "tie_word_embeddings": True}) == flops.matmul_params(c)


def test_v5e_peak_and_unknown_kind():
    assert peaks.peak("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert peaks.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peak("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks.peak("cpu")


# Model FLOPs per step of each cell as the benchmark counted them before the
# configuration files named their FLOP module; mfu rests on them.
CELL_FLOPS = {
    "qwen2.5-3b.themis.1chip": 13638668648448,
    "qwen2.5-14b.themis.1chip": 9414031441920,
    "qwen2.5-3b.themis.2x2": 54554674593792,
    "qwen2.5-3b.gspmd.1chip": 13638668648448,
}


@pytest.mark.parametrize("workload", sorted(CELL_FLOPS))
def test_each_cell_counts_by_the_module_its_file_names(workload):
    found = harness.resolve(workload)
    c, t = found["config"], found["traffic"]
    count = harness.config_module(c, "flops").train_step_flops(
        c, t["batch_per_chip"] * found["cell"]["chips"], t["seq"])
    assert count == CELL_FLOPS[workload]
