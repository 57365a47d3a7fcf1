"""Scheduler -> JAX bridge + HLO collective audit."""
import pytest

from repro.comms.schedule_bridge import (
    collective_stats,
    predicted_axis_loads,
    themis_axis_orders,
    themis_axis_orders_stream,
    topology_from_axes,
)

AXES = {"model": 16, "data": 16, "pod": 2}


def test_topology_from_axes_order_innermost_first():
    topo, names = topology_from_axes(AXES)
    assert names == ["model", "data", "pod"]
    assert [d.npus for d in topo.dims] == [16, 16, 2]
    # ICI faster than DCN
    assert topo.dims[0].aggr_bw_bytes > topo.dims[2].aggr_bw_bytes


def test_baseline_orders_static():
    orders = themis_axis_orders(AXES, 1e9, 8, "baseline")
    assert all(o == ("model", "data", "pod") for o in orders)


def test_themis_orders_balance_loads():
    n = 64
    base = themis_axis_orders(AXES, 12e9, n, "baseline")
    them = themis_axis_orders(AXES, 12e9, n, "themis")
    lb = predicted_axis_loads(AXES, 12e9, base)
    lt = predicted_axis_loads(AXES, 12e9, them)

    def imbalance(loads):
        v = list(loads.values())
        return max(v) / max(min(v), 1e-12)

    assert imbalance(lt) < imbalance(lb)
    assert imbalance(lt) < 2.0
    assert len(set(them)) > 1  # chunks got distinct orders


def test_single_axis_degenerates():
    orders = themis_axis_orders({"data": 8}, 1e9, 4, "themis")
    assert all(o == ("data",) for o in orders)


def test_stream_orders_see_residual_loads():
    """Bucket k's orders are scheduled against buckets 0..k-1's residual
    loads: back-to-back buckets produce valid per-bucket permutations and
    the later bucket's leading-axis mix differs from an isolated schedule
    of the same bytes (the residual-load signature)."""
    n = 16
    per_bucket = themis_axis_orders_stream(AXES, [4e9, 4e9], n, "themis")
    assert len(per_bucket) == 2
    for orders in per_bucket:
        assert len(orders) == n
        for o in orders:
            assert sorted(o) == sorted(AXES)  # permutation of all axes
    fresh = themis_axis_orders(AXES, 4e9, n, "themis")

    def lead_counts(orders):
        out = {}
        for o in orders:
            out[o[0]] = out.get(o[0], 0) + 1
        return out

    assert lead_counts(per_bucket[1]) != lead_counts(fresh)


def test_stream_unsorted_issue_times_schedule_in_issue_order():
    """Out-of-order issue_times must not corrupt the running clock: the
    t=0 bucket is scheduled first (fresh tracker) even when listed last."""
    n = 8
    got = themis_axis_orders_stream(AXES, [4e9, 4e9], n, "themis",
                                    issue_times=[10.0, 0.0])
    want_first = themis_axis_orders(AXES, 4e9, n, "themis")
    assert got[1] == want_first  # t=0 bucket saw an empty fabric
    assert len(got[0]) == n


def test_stream_baseline_static():
    per_bucket = themis_axis_orders_stream(AXES, [1e9, 1e9], 4, "baseline")
    for orders in per_bucket:
        assert all(o == ("model", "data", "pod") for o in orders)


SAMPLE_HLO = """
  %ag = bf16[16,512]{1,0} all-gather(%p0), replica_groups={{0,1,2,3}}, dimensions={0}
  %ar.1 = f32[1024]{0} all-reduce(%x), replica_groups={{0,1},{2,3}}, to_apply=%add
  %rs = f32[256]{0} reduce-scatter(%y), replica_groups={{0,1,2,3}}, dimensions={0}
  %arst = (f32[8]{0}, f32[8]{0}) all-reduce-start(%z), replica_groups={}
"""

# as the TPU compiler prints them: tiled layouts, async start/done pairs,
# and collective names among the operands of other ops
SAMPLE_TPU_HLO = """
  %all-reduce.32 = f32[1024]{0:T(1024)} all-reduce(%slice.154), channel_id=36, replica_groups={{0,1},{2,3}}, use_global_device_ids=true
  %copy-start.15 = (f32[1024]{0:T(1024)S(1)}, f32[1024]{0:T(1024)}, u32[]{:S(2)}) copy-start(%all-reduce.32)
  %collective-permute-start.1 = (s32[2,1024]{1,0:T(2,128)}, s32[2,1024]{1,0:T(2,128)}, u32[]{:S(2)}, u32[]{:S(2)}) collective-permute-start(%f.9), channel_id=3, source_target_pairs={{0,0},{1,2},{2,1},{3,3}}
  %collective-permute-done.1 = s32[2,1024]{1,0:T(2,128)} collective-permute-done(%collective-permute-start.1)
  %fusion.315 = (f32[]{:T(128)}, f32[2,1024]{1,0:T(2,128)S(1)}) fusion(%gather.11, %collective-permute-done.1), kind=kLoop
  %all-gather-start = (bf16[256]{0:T(256)}, bf16[1024]{0:T(1024)}) all-gather-start(%p), replica_groups={{0,1,2,3}}, dimensions={0}
"""


def test_collective_stats_parses_hlo():
    s = collective_stats(SAMPLE_HLO)
    assert s["op_counts"]["all-gather"] == 1
    assert s["op_counts"]["all-reduce"] == 2  # ar.1 + all-reduce-start
    assert s["bytes_by_kind"]["all-gather"] == 16 * 512 * 2
    assert s["bytes_by_kind"]["reduce-scatter"] == 256 * 4
    assert s["bytes_by_group_size"][4] == 16 * 512 * 2 + 256 * 4
    assert s["total_bytes"] > 0


def test_collective_stats_parses_tpu_hlo():
    s = collective_stats(SAMPLE_TPU_HLO)
    assert s["op_counts"] == {"all-reduce": 1, "collective-permute": 1,
                              "all-gather": 1}
    assert s["bytes_by_kind"]["all-reduce"] == 1024 * 4
    assert s["bytes_by_group_size"][2] == 1024 * 4


def test_collective_stats_counts_ops_that_take_a_done():
    """A reduce-scatter hop as the TPU compiler makes it: an all-reduce of
    the output of an async ``dynamic-slice-done``.  It is an all-reduce,
    not the "-done" half of an async pair."""
    line = ("  %all-reduce.32 = f32[16951296]{0:T(1024)} all-reduce("
            "%dynamic-slice-done.2), channel_id=68, replica_groups={{0,1},{2,3}}")
    s = collective_stats(line + "\n" + line.replace(".32", ".33"))
    assert s["op_counts"] == {"all-reduce": 2}
    assert s["bytes_by_group_size"][2] == 2 * 16951296 * 4
