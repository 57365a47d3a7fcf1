"""The reduction from a trace to the per-layer numbers."""
import gzip
import json
from pathlib import Path

import pytest

from bench import trace

DATA = Path(__file__).parent / "data"


def _record(ops, host=(), window=(0, 100)):
    return {"window": list(window), "host": [list(h) for h in host],
            "devices": [{"name": "/device:TPU:0", "ops": [list(o) for o in ops]}]}


def test_busy_collective_exposed_and_idle_by_hand():
    ops = [("fusion.1", 0, 30),                 # compute 0-30
           ("all-reduce-start.3", 20, 2),       # collective 20 .. done end 60
           ("fusion.2", 35, 10),                # compute 35-45, inside the collective
           ("all-reduce-done.3", 55, 5),
           ("all-gather.7", 70, 10),            # synchronous collective 70-80
           ("fusion.3", 90, 20)]                # compute 90-110, cut at the window's end
    host = [("bench.wait", 60, 30), ("bench.next_batch", 80, 5)]
    r = trace.reduce(_record(ops, host))
    assert r["window_s"] == pytest.approx(100e-9)
    # busy: 0-60, 70-80, 90-100
    assert r["busy_s"] == pytest.approx(80e-9)
    # collective: 20-60 and 70-80
    assert r["collective_s"] == pytest.approx(50e-9)
    # exposed: 30-35, 45-60 and 70-80
    assert r["exposed_collective_s"] == pytest.approx(30e-9)
    # idle: 60-70 under bench.wait, 80-90 mostly under bench.wait (80-85 also next_batch)
    gaps = sorted((round(s * 1e9), name) for name, s in r["idle_gaps"])
    assert gaps == [(10, "bench.wait"), (10, "bench.wait")]
    assert r["host_s"]["bench.wait"] == pytest.approx(30e-9)
    assert dict(r["device_ops"])["fusion.3"] == pytest.approx(10e-9)


def test_devices_are_averaged():
    rec = _record([("fusion.1", 0, 50)])
    rec["devices"].append({"name": "/device:TPU:1", "ops": [["fusion.1", 0, 100]]})
    r = trace.reduce(rec)
    assert r["busy_s"] == pytest.approx(75e-9)
    assert [d["busy_s"] for d in r["devices"]] == pytest.approx([50e-9, 100e-9])
    assert r["collective_s"] == 0


def test_collective_names():
    for name in ("all-reduce.12", "all-gather-start.1", "reduce-scatter-done.4",
                 "collective-permute.2", "all-to-all"):
        assert trace.COLLECTIVE.match(name), name
    for name in ("fusion.3", "all-reduce-fusion.1", "copy.7", "convolution.2"):
        assert not trace.COLLECTIVE.match(name), name


def test_nested_ops_count_their_own_time_once():
    ops = [("%while.1 = (f32[8]) while(...)", 0, 100),
           ("%fusion.2 = f32[8] fusion(...)", 10, 30),
           ("%fusion.2 = f32[8] fusion(...)", 50, 30)]
    r = trace.reduce(_record(ops))
    own = dict(r["device_ops"])
    assert own["%fusion.2 = f32[8] fusion(...)"] == pytest.approx(60e-9)
    assert own["%while.1 = (f32[8]) while(...)"] == pytest.approx(40e-9)
    assert r["busy_s"] == pytest.approx(100e-9)


def test_recorded_trace_of_the_themis_step_on_one_chip():
    """0.7 s of cell qwen2.5-3b.themis.1chip on a v5e chip (PR 12): one
    device, no collective, busy all through; the flat-vector writes of the
    Themis path take the most time of their own."""
    with gzip.open(DATA / "trace_3b_themis_1chip.json.gz", "rt") as f:
        rec = json.load(f)
    r = trace.reduce(rec)
    assert r["window_s"] == pytest.approx(0.7)
    assert len(r["devices"]) == 1
    assert r["collective_s"] == 0 and r["exposed_collective_s"] == 0
    assert 0.99 * r["window_s"] < r["busy_s"] <= r["window_s"]
    own = sum(s for _, s in trace.self_times(
        [[x, max(t, rec["window"][0]), min(t + d, rec["window"][1]) - max(t, rec["window"][0])]
         for x, t, d in rec["devices"][0]["ops"]
         if t < rec["window"][1] and t + d > rec["window"][0]]))
    assert own <= r["window_s"] * 1.0001
    top, _ = r["device_ops"][0]
    assert trace.op_name(top).startswith("dynamic-update-slice")
    assert max(s for _, s in r["idle_gaps"]) < 1e-3
    assert set(r["host_s"]) == {"bench.next_batch", "bench.dispatch", "bench.wait"}


def test_recorded_trace_of_the_themis_step_on_the_2x2_mesh():
    """0.6 s of cell qwen2.5-3b.themis.2x2 on four v5e chips (PR 12): the
    chunked reduce-scatter and all-gather run as synchronous all-reduces,
    most of them with nothing else on the device."""
    with gzip.open(DATA / "trace_3b_themis_2x2.json.gz", "rt") as f:
        rec = json.load(f)
    r = trace.reduce(rec)
    assert len(r["devices"]) == 4
    names = {trace.op_name(o[0]).split(".")[0] for o in rec["devices"][0]["ops"]
             if trace.COLLECTIVE.match(trace.op_name(o[0]))}
    assert names == {"all-reduce", "collective-permute-start", "collective-permute-done"}
    for d in r["devices"]:
        assert 0 < d["exposed_collective_s"] < d["collective_s"] < d["busy_s"] <= r["window_s"]
    assert r["collective_s"] == pytest.approx(0.1916, rel=1e-3)
    assert r["exposed_collective_s"] == pytest.approx(0.1567, rel=1e-3)
