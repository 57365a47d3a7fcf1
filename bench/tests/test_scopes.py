"""The reduction from a trace and the compiled step's HLO to device time by
step phase and collective time by mesh axis (``bench/scopes.py``)."""
import gzip
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness, scopes, trace

DATA = Path(__file__).parent / "data"
MESH = ((2, 2), ("data", "model"))

HLO = """HloModule jit_step, is_scheduled=true

%fused_computation (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %neg.1 = f32[8]{0} negate(f32[8]{0} %param_0), metadata={op_name="jit(step)/optimizer/neg"}
}

%wide.body (wide.param: (u32[], f32[8])) -> (u32[], f32[8]) {
  %wide.param = (u32[], f32[8]{0:T(1024)}) parameter(0)
  %get-tuple-element.1 = f32[8]{0:T(1024)} get-tuple-element(%wide.param), index=1
  %dynamic-update-slice.42 = f32[8]{0:T(1024)} dynamic-update-slice(%get-tuple-element.1, %get-tuple-element.1)
  ROOT %tuple.3 = (u32[], f32[8]{0:T(1024)}) tuple(%get-tuple-element.1, %dynamic-update-slice.42)
}

ENTRY %main.10 (p0: f32[8]) -> f32[4] {
  %p0 = f32[8]{0} parameter(0), metadata={op_name="params"}
  %fusion.1 = f32[8]{0} fusion(%p0), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/transpose(jvp(forward))/mul"}
  %tuple.1 = (u32[], f32[8]{0}) tuple(%p0, %fusion.1)
  %while.5 = (u32[], f32[8]{0}) while(%tuple.1), condition=%wide.cond, body=%wide.body
  %get-tuple-element.9 = f32[8]{0} get-tuple-element(%while.5), index=1
  %bitcast.2 = f32[2,4]{1,0} bitcast(%get-tuple-element.9), metadata={op_name="jit(step)/themis_flatten/reshape"}
  %all-reduce-start.3 = f32[2,4]{1,0:T(8,128)} all-reduce-start(f32[2,4]{1,0:T(8,128)} %bitcast.2), channel_id=1, replica_groups=[2,2]<=[2,2]T(1,0), use_global_device_ids=true, to_apply=%add
  %all-reduce-done.3 = f32[2,4]{1,0:T(8,128)} all-reduce-done(f32[2,4]{1,0:T(8,128)} %all-reduce-start.3)
  %dynamic-slice.4 = f32[1,4]{1,0} dynamic-slice(%all-reduce-done.3), metadata={op_name="jit(step)/themis_rs/rs_data/reduce_scatter"}
  %all-gather.7 = f32[2,4]{1,0} all-gather(%dynamic-slice.4), replica_groups={{0,1},{2,3}}, dimensions={0}, metadata={op_name="jit(step)/themis_ag/ag_model/all_gather"}
  %broadcast.8 = f32[2,4]{1,0} broadcast(%all-gather.7), metadata={op_name="jit(step)/broadcast.31"}
  ROOT %fusion.9 = f32[4]{0} fusion(%broadcast.8), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/themis_unravel/slice;jit(step)/shard_map"}
}
"""


@pytest.mark.parametrize("op_name,phase", [
    ("jit(step)/shard_map/jvp(forward)/while/body/dot_general", "forward"),
    ("jit(step)/forward/iota", "forward"),
    ("jit(step)/shard_map/transpose(jvp(forward))/while/body/closed_call/checkpoint/mul",
     "backward"),
    ("jit(step)/optimizer/sub", "optimizer"),
    ("jit(step)/optimizer/mul;jit(step)/shard_map", "optimizer"),
    ("jit(step)/shard_map/themis_flatten/concatenate", "themis_flatten"),
    ("jit(step)/shard_map/themis_rs/rs_model/reduce_scatter", "themis_rs"),
    ("jit(step)/shard_map/themis_ag/ag_data/all_gather", "themis_ag"),
    ("jit(step)/themis_unravel/split", "themis_unravel"),
    ("jit(step)/jvp(forwarded)/mul", "unscoped"),
    ("jit(step)/closed_call/while/body/closed_call/add", "unscoped"),
    ("jit(step)/shard_map/psum", "unscoped"),
    ("", "unscoped"),
])
def test_phase_of_hand_written_op_names(op_name, phase):
    assert scopes.phase_of(op_name) == phase


@pytest.mark.parametrize("line,axes", [
    ("replica_groups={{0,1},{2,3}}, to_apply=%add", ("model",)),
    ("replica_groups={{0,2},{1,3}}, to_apply=%add", ("data",)),
    ("replica_groups={{0,1,2,3}}, to_apply=%add", ("data", "model")),
    ("replica_groups={}, to_apply=%add", ("data", "model")),
    ("replica_groups=[2,2]<=[4], to_apply=%add", ("model",)),
    ("replica_groups=[2,2]<=[2,2]T(1,0), to_apply=%add", ("data",)),
    ("replica_groups=[1,4]<=[4], to_apply=%add", ("data", "model")),
    ("source_target_pairs={{0,1},{1,0},{2,3},{3,2}}", ("model",)),
    ("source_target_pairs={{0,0},{1,2},{2,1},{3,3}}", ("data", "model")),
])
def test_replica_groups_in_both_forms_give_the_axes_they_span(line, axes):
    assert scopes.axes_spanned(scopes.replica_groups(line), *MESH) == axes


def test_iota_groups_by_hand():
    assert scopes.replica_groups("replica_groups=[2,4]<=[2,4]T(1,0)") == \
        [[0, 4, 1, 5], [2, 6, 3, 7]]
    assert scopes.replica_groups("channel_id=3") is None


def test_hlo_map_fills_in_what_the_compiler_left_unnamed():
    m = scopes.hlo_map(HLO, *MESH)
    # named ops keep their names
    assert m["fusion.1"][0] == "jit(step)/transpose(jvp(forward))/mul"
    # a loop the compiler made takes its nearest named user's scope, and the
    # instructions of its body the loop's
    assert m["while.5"][0] == "jit(step)/themis_flatten/reshape"
    assert m["dynamic-update-slice.42"][0] == "jit(step)/themis_flatten/reshape"
    # an unnamed collective takes its user's; its done its start's axes
    assert m["all-reduce-start.3"] == ["jit(step)/themis_rs/rs_data/reduce_scatter", ["data"]]
    assert m["all-reduce-done.3"][1] == ["data"]
    assert m["all-gather.7"][1] == ["model"]
    # an op_name the compiler made up after an instruction counts as none
    assert m["broadcast.8"][0] == "jit(step)/themis_unravel/slice;jit(step)/shard_map"
    assert m["dynamic-slice.4"][1] == []


def _record(*devices, window=(0, 100)):
    return {"window": list(window), "host": [],
            "devices": [{"name": f"/device:TPU:{i}", "ops": [list(o) for o in ops]}
                        for i, ops in enumerate(devices)]}


OPS = [("%fusion.1 = f32[8] fusion(...)", 0, 30),              # backward
       ("%while.5 = (u32[], f32[8]) while(...)", 30, 30),       # flatten, 20 of its own
       ("%dynamic-update-slice.42 = f32[8] dyn...", 35, 10),    # flatten, in the loop
       ("%all-reduce-start.3 = f32[2,4] all-reduce-start", 60, 2),
       ("%all-reduce-done.3 = f32[2,4] all-reduce-done", 62, 8),  # rs on data, 60-70
       ("%all-gather.7 = f32[2,4] all-gather(...)", 70, 10),    # ag on model, 70-80
       ("%copy.99 = f32[8] copy(...)", 80, 10),                 # not in the map
       ("%fusion.9 = f32[4] fusion(...)", 90, 20)]              # unravel, cut at 100


def test_phases_and_unscoped_sum_to_the_busy_own_time_by_hand():
    m = scopes.hlo_map(HLO, *MESH)
    r = scopes.reduce(_record(OPS), m)
    ns = {k: round(v * 1e9) for k, v in r["phases"].items()}
    assert ns == {"themis_flatten": 30, "themis_rs": 10, "themis_ag": 10,
                  "themis_unravel": 10, "optimizer": 0, "backward": 30, "forward": 0,
                  "unscoped": 10}
    assert {k: round(v * 1e9) for k, v in r["axes"].items()} == {"data": 10, "model": 10}
    busy = trace.reduce(_record(OPS))["busy_s"]
    assert sum(r["phases"].values()) == pytest.approx(busy)


def test_an_op_over_both_axes_counts_in_each_and_devices_are_averaged():
    hlo = HLO.replace("replica_groups={{0,1},{2,3}}", "replica_groups=[1,4]<=[4]")
    m = scopes.hlo_map(hlo, *MESH)
    other = [("%all-gather.7 = f32[2,4] all-gather(...)", 0, 50)]
    r = scopes.reduce(_record(OPS, other), m)
    assert r["axes"]["data"] == pytest.approx((20e-9 + 50e-9) / 2)
    assert r["axes"]["model"] == pytest.approx((10e-9 + 50e-9) / 2)
    assert r["phases"]["themis_ag"] == pytest.approx((10e-9 + 50e-9) / 2)


def test_a_program_without_scopes_reads_all_unscoped():
    """The parent program names no phase: its ops all read ``unscoped``, and
    the axes, which come from the replica groups, still read."""
    unnamed = scopes.hlo_map(HLO.replace("op_name=", "source_file="), *MESH)
    r = scopes.per_step_ms(_record(OPS), unnamed, steps=1)
    assert {k for k, v in r["phases_ms"].items() if v} == {"unscoped"}
    assert r["phases_ms"]["unscoped"] == pytest.approx(100e-9 * 1e3)
    assert r["axes_ms"] == pytest.approx({"data": 10e-6, "model": 10e-6})
    assert r["data_ms"] == {}


def test_per_step_ms_by_hand():
    rec = _record(OPS)
    rec["data"] = [["data.produce", 5, 10], ["data.produce", 95, 10],
                   ["data.produce", 200, 5]]
    r = scopes.per_step_ms(rec, scopes.hlo_map(HLO, *MESH), steps=2)
    assert r["phases_ms"]["themis_flatten"] == pytest.approx(15e-6)
    assert r["phases_ms"]["backward"] == pytest.approx(15e-6)
    assert r["axes_ms"] == pytest.approx({"data": 5e-6, "model": 5e-6})
    # a span counts inside the window only: 10 + 5 ns over 2 steps
    assert r["data_ms"] == {"data.produce": pytest.approx(7.5e-6)}


def test_program_spans_are_read_from_a_profile(tmp_path):
    """``load_spans`` finds the ``data.produce`` spans the program's
    ``Prefetcher`` writes, and nothing of the harness's ``bench.*``."""
    import jax
    from jax.profiler import TraceAnnotation

    from repro.data import Prefetcher, SyntheticLM
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    jax.profiler.start_trace(str(tmp_path))
    try:
        with TraceAnnotation("bench.window"):
            pf = Prefetcher(SyntheticLM(64, global_batch=2, seq_len=8, seed=1), mesh)
            for _ in range(3):
                next(pf)
            pf.close()
    finally:
        jax.profiler.stop_trace()
    spans = scopes.load_spans(str(tmp_path))
    assert len(spans) >= 3 and {s[0] for s in spans} == {"data.produce"}
    t0 = min(s[1] for s in spans)
    assert scopes.span_seconds(spans, [t0, t0 + 10**12])["data.produce"] > 0


def test_off_tpu_record_scopes_fails_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run([sys.executable, "bench/record_scopes.py", "--workload",
                        "qwen2.5-3b.themis.1chip", "--seed", "1", "--seconds", "1"],
                       cwd=harness.ROOT, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert p.stdout.strip() == ""


@pytest.mark.parametrize("name,devices", [("trace_3b_themis_1chip_scoped", 1),
                                          ("trace_3b_themis_2x2_scoped", 4)])
def test_recorded_scoped_trace(name, devices):
    """A short traced window of a Themis cell on v5e chips, with the
    compiled step's scope and axes of every op it holds: the phases and the
    unscoped rest add up to the busy time, the rest is small, and on the
    2x2 mesh both axes carry collectives that cover ``collective_s``."""
    with gzip.open(DATA / f"{name}.json.gz", "rt") as f:
        rec = json.load(f)
    r, tr = scopes.reduce(rec, rec["hlo"]), trace.reduce(rec)
    assert len(tr["devices"]) == devices
    assert {trace.op_name(o[0]) for d in rec["devices"] for o in d["ops"]} <= set(rec["hlo"])
    own = sum(r["phases"].values())
    assert own == pytest.approx(tr["busy_s"], rel=0.02)
    assert r["phases"]["unscoped"] < 0.05 * own
    for phase in ("forward", "backward", "optimizer", "themis_flatten", "themis_rs",
                  "themis_unravel"):
        assert r["phases"][phase] > 0, phase
    assert set(scopes.span_seconds(rec["data"], rec["window"])) == {"data.produce"}
    if devices == 1:
        assert r["axes"] == {} and r["phases"]["themis_ag"] == 0
        return
    assert r["phases"]["themis_ag"] > 0
    assert set(r["axes"]) == {"data", "model"}
    assert sum(r["axes"].values()) >= 0.99 * tr["collective_s"]
    assert r["axes"]["data"] == pytest.approx(r["axes"]["model"], rel=0.1)


@pytest.mark.parametrize("op_name,held", [
    ("jit(step)/shard_map/themis_rs/rs_data/reduce_scatter",
     {"step", "shard_map", "themis_rs", "rs_data"}),
    ("jit(step)/transpose(jvp(forward))/moe/router/dot_general",
     {"step", "forward", "moe", "router"}),
    ("jit(step)/optimizer/mul;jit(step)/shard_map", {"step", "optimizer"}),
    ("params", set()),
    ("", set()),
])
def test_scopes_of_hand_written_op_names(op_name, held):
    assert scopes.scopes_of(op_name) == held


def test_by_scope_by_hand():
    """Every op's own time goes to each scope it holds: a nested scope counts
    inside its parent, and a scope counts forward and backward together."""
    r = scopes.reduce(_record(OPS), scopes.hlo_map(HLO, *MESH))
    ns = {k: round(v * 1e9) for k, v in r["by_scope"].items()}
    assert ns == {"step": 90, "forward": 30, "themis_flatten": 30, "themis_rs": 10,
                  "rs_data": 10, "themis_ag": 10, "ag_model": 10, "themis_unravel": 10}
    ops_map = {"fusion.1": ["jit(step)/jvp(forward)/moe/router/dot_general", []],
               "fusion.2": ["jit(step)/transpose(jvp(forward))/moe/dot_general", []],
               "fusion.3": ["jit(step)/jvp(forward)/dot_general", []]}
    ops = [("%fusion.1 = f32[8] fusion(...)", 0, 10),
           ("%fusion.2 = f32[8] fusion(...)", 10, 30),
           ("%fusion.3 = f32[8] fusion(...)", 40, 20)]
    r = scopes.per_step_ms(_record(ops), ops_map, steps=2)
    assert r["by_scope_ms"] == pytest.approx(
        {"step": 30e-6, "forward": 30e-6, "moe": 20e-6, "router": 5e-6})
    assert r["phases_ms"]["forward"] == pytest.approx(15e-6)
    assert r["phases_ms"]["backward"] == pytest.approx(15e-6)


NEW_READERS = {
    "forward_ms": ("phases_ms", "forward"), "backward_ms": ("phases_ms", "backward"),
    "optimizer_ms": ("phases_ms", "optimizer"),
    "themis_flatten_ms": ("phases_ms", "themis_flatten"),
    "themis_rs_ms": ("phases_ms", "themis_rs"), "themis_ag_ms": ("phases_ms", "themis_ag"),
    "themis_unravel_ms": ("phases_ms", "themis_unravel"),
    "collective_data_ms": ("axes_ms", "data"), "collective_model_ms": ("axes_ms", "model"),
    "input_produce_ms": ("data_ms", "data.produce"),
}


def _recorded(name):
    with gzip.open(DATA / f"{name}.json.gz", "rt") as f:
        return json.load(f)


@pytest.mark.parametrize("name,devices", [("trace_3b_themis_1chip_scoped", 1),
                                          ("trace_3b_themis_2x2_scoped", 4)])
def test_the_record_of_a_recorded_trace_and_its_readers(name, devices):
    """What the harness adds to a traced run's record: the phases and the
    unscoped rest add up to the busy time within 0.1%, ``by_scope`` holds
    each phase's scope at least at the phase's time, and each new reader
    returns its number where the cell has it and nothing where it does not
    (the all-gather phase on one chip, in the step these traces were
    recorded from; the mesh axes on one chip)."""
    rec = _recorded(name)
    steps = 3
    record = {"steps": steps, **harness.trace_readings(rec, rec["hlo"], steps)}
    sc = record["scopes"]
    busy_ms = record["trace"]["busy_s"] / steps * 1e3
    assert sum(sc["phases_ms"].values()) == pytest.approx(busy_ms, rel=1e-3)
    for phase in scopes.PHASES:
        assert sc["by_scope_ms"].get(phase, 0.0) >= sc["phases_ms"][phase] * (1 - 1e-9)
    assert sc["by_scope_ms"]["forward"] == pytest.approx(
        sc["phases_ms"]["forward"] + sc["phases_ms"]["backward"], rel=1e-3)
    absent = {"themis_ag_ms", "collective_data_ms", "collective_model_ms"} \
        if devices == 1 else set()
    for metric, (kind, key) in NEW_READERS.items():
        got = harness.read_metric(metric, record)
        if metric in absent:
            assert got is None, metric
        else:
            assert got == pytest.approx(sc[kind][key]) and got > 0, metric


def test_readers_of_a_step_without_scopes_and_of_an_untraced_run_read_nothing():
    """A step whose op names hold no phase (a program before the scopes, or a
    gspmd step for the Themis phases) gives no phase reading, and an
    untraced run, whose record has no scopes, none at all."""
    rec = _recorded("trace_3b_themis_1chip_scoped")
    unnamed = {k: ["", axes] for k, (_, axes) in rec["hlo"].items()}
    record = {"steps": 1, **harness.trace_readings(rec, unnamed, 1)}
    for metric, (kind, _) in NEW_READERS.items():
        got = harness.read_metric(metric, record)
        assert (got is None) == (kind != "data_ms"), metric
        assert harness.read_metric(metric, {"steps": 1, "trace": None, "scopes": None}) is None
