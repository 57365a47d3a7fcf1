"""Bridge: Themis scheduler -> static JAX collective program; HLO audits.

JAX programs are compiled once and replayed, and the paper itself computes
schedules once and reuses them (Sec. 4.6.2) — so Themis's greedy pass runs
at *trace time*: we model the mesh axes as a Themis topology (ICI axes
innermost, DCN 'pod' axis outermost), run Algorithm 1 over the gradient
buffer, and emit the per-chunk axis orders that ``chunked_all_reduce``
bakes into the compiled program.

Also provides the HLO collective audit used by the dry-run/roofline: total
bytes moved by each collective category, and the per-axis load balance
(the paper's Dim-Load metric recovered statically from the compiled HLO).
"""
from __future__ import annotations

import math
import re
from collections import defaultdict

from repro.core.latency_model import LatencyModel
from repro.core.requests import CollectiveRequest
from repro.core.scheduler import ThemisScheduler, baseline_order
from repro.topology import Phase, make_tpu_pod_topology
from repro.topology.topology import NetworkDim, Topology, GBPS, TopoKind


def topology_from_axes(axis_sizes: dict[str, int]) -> tuple[Topology, list[str]]:
    """Mesh axes -> Themis topology (dims innermost-first: model, data, pod).

    ICI axes: ring, 2 x 400 Gb/s links (~100 GB/s aggregate); pod axis: DCN
    NIC, 200 Gb/s.  Returns (topology, axis name per dim index).
    """
    order = [a for a in ("model", "data", "pod") if axis_sizes.get(a, 1) > 1]
    dims = []
    for a in order:
        if a == "pod":
            dims.append(NetworkDim(axis_sizes[a], TopoKind.SWITCH, 200.0, 1, 2e-5))
        else:
            dims.append(NetworkDim(axis_sizes[a], TopoKind.RING, 400.0, 2, 1e-6))
    return Topology("mesh", tuple(dims)), order


def themis_axis_orders(
    axis_sizes: dict[str, int],
    nbytes: float,
    n_chunks: int,
    policy: str = "themis",
) -> list[tuple[str, ...]]:
    """Per-chunk RS axis orders for a gradient All-Reduce of ``nbytes``."""
    topo, names = topology_from_axes(axis_sizes)
    if topo.num_dims == 0:
        return [()] * n_chunks
    if policy in ("baseline", "hier_baseline"):
        rs = [d for ph, d in baseline_order(topo.num_dims, "RS")]
        return [tuple(names[d] for d in rs)] * n_chunks
    sched = ThemisScheduler(LatencyModel.for_topology(topo),
                            policy if policy != "themis_scf" else "themis")
    chunks = sched.schedule_collective("AR", nbytes, n_chunks)
    orders = []
    for c in chunks:
        rs = [d for ph, d in c.schedule if ph == Phase.RS]
        orders.append(tuple(names[d] for d in rs))
    return orders


def themis_axis_orders_stream(
    axis_sizes: dict[str, int],
    bucket_bytes: list[float],
    n_chunks: int,
    policy: str = "themis",
    issue_times: list[float] | None = None,
) -> list[list[tuple[str, ...]]]:
    """Per-chunk RS axis orders for a *stream* of gradient-bucket ARs.

    Unlike :func:`themis_axis_orders` (one fused collective, tracker reset),
    this runs ONE incremental scheduler across the whole bucket stream
    (``schedule_request``): bucket k's chunk orders account for the residual
    dim loads of buckets 0..k-1 still in flight — the trace-time analogue of
    overlapping backprop collectives.  ``issue_times`` defaults to
    back-to-back issue (all 0.0, i.e. maximum residual contention).
    Returns one order list per bucket, each with ``n_chunks`` entries.
    """
    topo, names = topology_from_axes(axis_sizes)
    if topo.num_dims == 0:
        return [[()] * n_chunks for _ in bucket_bytes]
    if policy in ("baseline", "hier_baseline"):
        rs = [d for ph, d in baseline_order(topo.num_dims, "RS")]
        return [[tuple(names[d] for d in rs)] * n_chunks for _ in bucket_bytes]
    if issue_times is None:
        issue_times = [0.0] * len(bucket_bytes)
    sched = ThemisScheduler(
        LatencyModel.for_topology(topo),
        policy if policy != "themis_scf" else "themis")
    out: list[list[tuple[str, ...]] | None] = [None] * len(bucket_bytes)
    # schedule in issue order (the tracker clock only moves forward) while
    # returning orders indexed like the input buckets
    for i in sorted(range(len(bucket_bytes)), key=lambda i: (issue_times[i], i)):
        chunks = sched.schedule_request(
            CollectiveRequest("AR", bucket_bytes[i], issue_time=issue_times[i]),
            n_chunks)
        out[i] = [
            tuple(names[d] for ph, d in c.schedule if ph == Phase.RS)
            for c in chunks
        ]
    return out


def predicted_axis_loads(
    axis_sizes: dict[str, int], nbytes: float, orders: list[tuple[str, ...]]
) -> dict[str, float]:
    """Dim-Load-Tracker view of a chunk-order assignment (seconds/axis)."""
    topo, names = topology_from_axes(axis_sizes)
    lm = LatencyModel.for_topology(topo)
    idx = {n: i for i, n in enumerate(names)}
    loads = {n: 0.0 for n in names}
    per_chunk = nbytes / max(len(orders), 1)
    for order in orders:
        sched = [(Phase.RS, idx[a]) for a in order] + [
            (Phase.AG, idx[a]) for a in reversed(order)
        ]
        for d, secs in lm.calc_loads(per_chunk, sched).items():
            loads[names[d]] += secs
    return loads


# -- HLO audit ----------------------------------------------------------------
_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}
_COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute",
)
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
# result shape (a tuple, or dtype[dims] with a layout such as the TPU's
# {0:T(1024)}), then the collective's opcode
_OP_RE = re.compile(
    r"=\s*(?:\(.*?\)|\w+\[[\d,]*\](?:\{[^}]*\})?)\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?\("
)
_GROUPS_RE = re.compile(r"replica_groups=\{?\{([\d,]+)\}")


def _line_output_bytes(line: str) -> int:
    """Bytes of the op's result shape(s) — the data a collective moves."""
    lhs = line.split("=", 1)[0] + "=" + line.split("=", 1)[1]
    head = line.strip()
    # shapes appear right after '=' and before the op name
    m = _OP_RE.search(head)
    if not m:
        return 0
    pre = head[: m.start(1)]
    total = 0
    for dt, dims in _SHAPE_RE.findall(pre):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_stats(hlo_text: str) -> dict:
    """Sum collective bytes by category and by replica-group size."""
    by_kind: dict[str, float] = defaultdict(float)
    by_group: dict[int, float] = defaultdict(float)
    counts: dict[str, int] = defaultdict(int)
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        # an async pair counts once, at its start; an operand's name (such
        # as %dynamic-slice-done.3) does not make an op a "-done"
        if not m or m.group(2) == "-done":
            continue
        kind = m.group(1)
        nbytes = _line_output_bytes(line)
        by_kind[kind] += nbytes
        counts[kind] += 1
        g = _GROUPS_RE.search(line)
        if g:
            size = len(g.group(1).split(","))
            by_group[size] += nbytes
    return {
        "bytes_by_kind": dict(by_kind),
        "bytes_by_group_size": dict(by_group),
        "op_counts": dict(counts),
        "total_bytes": float(sum(by_kind.values())),
    }
