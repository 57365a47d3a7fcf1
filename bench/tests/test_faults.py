"""A run whose timed path is broken comes out as not correct, once for each
fault a cell can have: a step that returns its state unchanged, half of
the batch left out (the mean taken over the rest), and on the 2x2 mesh the
exchange between chips left out.  Tiny size, on the CPU."""
import json

import pytest

from bench.tests.tiny import REPO, result_of, run_cell, tiny_copy

BM = json.loads((REPO / "BENCHMARK.json").read_text())
CASES = [(w["name"], w["chips"], fault) for w in BM["workloads"]
         for fault in ("unchanged", "half_batch") + (("no_exchange",) if w["chips"] > 1 else ())]


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return tiny_copy(tmp_path_factory.mktemp("tiny"))


@pytest.mark.parametrize("workload,chips,fault", CASES)
def test_a_broken_step_is_not_correct(tiny, workload, chips, fault):
    res = result_of(run_cell(tiny, workload, devices=chips, fault=fault, seed=2**31 + 3))
    print(workload, fault, {k: c["value"] for k, c in res["checks"].items()})
    assert res["correct"] is False
    assert res["attempted"] > 0
