"""The control of each configuration's reference module (its second
variant; for ``bench/reference.py`` the reference computed with float8
products) put in the program's place fails the comparison, and the
reference passes against itself.  Tiny size, on the CPU; the chip readings at the cells' own sizes
come from bench/calibrate.py and are in PERF.md."""
import json

import pytest

from bench import check
from bench.data import Tokens
from bench.harness import config_module
from bench.tests.tiny import REPO, TINY, TINY_LIMITS, TINY_TRAFFIC

CONFIGS = [c["name"] for c in json.loads((REPO / "BENCHMARK.json").read_text())["configs"]]


def _tiny(name):
    c = json.loads((REPO / "bench" / "configs" / f"{name}.json").read_text())
    return {**c, **TINY}


def _readings(c, train, seed, variant):
    ref = config_module(c, "reference").Reference(c, train, variant)
    data = Tokens(c["vocab_size"], 4, TINY_TRAFFIC["seq"], seed)
    out = ref.run(seed, [data.batch_at(s) for s in range(3)])
    return {"losses": out["losses"],
            "grad": {k: float(v) for k, v in check.by_path(out["grad"]).items()},
            "change": {k: float(v) for k, v in check.by_path(out["change"]).items()}}


@pytest.mark.parametrize("name", CONFIGS)
@pytest.mark.parametrize("seed", [5, 2**31 + 9, 2**32 + 1])
def test_control_fails_and_reference_agrees_with_itself(name, seed):
    c = _tiny(name)
    train = json.loads((REPO / "bench" / "traffic" / "themis.1chip.json").read_text())["train"]
    sound, control, *_ = config_module(c, "reference").VARIANTS
    truth = _readings(c, train, seed, sound)
    same = check.numbers(truth, truth)
    assert same == {"loss_gap": 0.0, "grad_gap": 0.0, "change_gap": 0.0}
    ctrl = check.numbers(_readings(c, train, seed, control), truth)
    ctrl["nonfinite_losses"] = 0
    ok, checks = check.judge(ctrl, TINY_LIMITS)
    print(name, seed, ctrl)
    assert not ok, checks
