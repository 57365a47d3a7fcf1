"""From a profiler trace to the per-layer numbers of a run.

``load`` reads the ``.xplane.pb`` that ``jax.profiler.trace`` writes and
keeps a neutral record: the traced window (the harness's ``bench.window``
span), the harness's host spans (``bench.*``), and for each device the
events of its "XLA Ops" line as ``[text, start_ns, duration_ns]``, where
the text is the start of the HLO instruction (``%name = type ...``).  On a
TPU that line nests: a ``while`` or ``conditional`` spans the operations
of its body.  ``reduce`` turns the record into busy, collective,
exposed-collective and idle time per device, the operations that took the
most time of their own (their span less that of the operations inside
it), and the longest idle gaps with the host span each fell in.  Both work
on any record of this form, so the tests check the reduction on a small
recorded trace.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

# HLO instruction names of the collectives, as the device's op line names them
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)"
    r"(-start|-done)?(\.\d+)?$")
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
WINDOW = "bench.window"
TEXT = 120  # characters of an op's HLO text kept in the record


def op_name(text: str) -> str:
    """The HLO instruction name of an op's text (``%name = ...``)."""
    return text.split(" = ", 1)[0].lstrip("%")


def load(log_dir: str) -> dict:
    """The neutral record of the one trace under ``log_dir``."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if len(files) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}, found {files}")
    pd = ProfileData.from_file(files[0])
    host, devices = [], []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            if OPS_LINE not in lines:
                raise RuntimeError(f"{plane.name} has no {OPS_LINE!r} line: {sorted(lines)}")
            devices.append({"name": plane.name, "ops": [
                [e.name[:TEXT], int(e.start_ns), int(e.duration_ns)]
                for e in lines[OPS_LINE].events]})
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host += [[e.name, int(e.start_ns), int(e.duration_ns)]
                         for e in ln.events if e.name.startswith("bench.")]
    wins = [h for h in host if h[0] == WINDOW]
    if len(wins) != 1 or not devices:
        raise RuntimeError(f"trace holds {len(wins)} {WINDOW} spans and "
                           f"{len(devices)} device planes")
    _, w0, wd = wins[0]
    devices.sort(key=lambda d: int(d["name"].rsplit(":", 1)[1]))
    return {"window": [w0, w0 + wd], "host": [h for h in host if h[0] != WINDOW],
            "devices": devices}


# -- interval arithmetic ----------------------------------------------------------
def _union(iv: list) -> list:
    out = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _length(iv: list) -> int:
    return sum(b - a for a, b in iv)


def _minus(iv: list, cut: list) -> list:
    """Parts of the disjoint sorted intervals ``iv`` outside ``cut`` (same form)."""
    out, j = [], 0
    for a, b in iv:
        while j < len(cut) and cut[j][1] <= a:
            j += 1
        k = j
        while a < b and k < len(cut) and cut[k][0] < b:
            if cut[k][0] > a:
                out.append([a, cut[k][0]])
            a = max(a, cut[k][1])
            k += 1
        if a < b:
            out.append([a, b])
    return out


def _clip(iv: list, w0: int, w1: int) -> list:
    return [[max(a, w0), min(b, w1)] for a, b in iv if b > w0 and a < w1]


def collective_intervals(ops: list) -> list:
    """Intervals of collective work: a synchronous collective op, or an
    asynchronous one from its ``-start`` to the matching ``-done``."""
    out, pending = [], defaultdict(list)
    for text, t, d in sorted(ops, key=lambda o: o[1]):
        m = COLLECTIVE.match(op_name(text))
        if not m:
            continue
        kind, phase = m.group(1), m.group(2)
        if phase == "-start":
            pending[kind].append(t)
        elif phase == "-done":
            start = pending[kind].pop(0) if pending[kind] else t
            out.append([start, t + d])
        else:
            out.append([t, t + d])
    return out


def self_times(ops: list) -> list:
    """``[text, seconds]`` of each op, less the time of the ops nested in it."""
    out, stack = [], []
    for text, t, d in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][1] <= t:
            stack.pop()
        if stack:
            out[stack[-1][2]][1] -= min(t + d, stack[-1][1]) - t
        out.append([text, d])
        stack.append((t, t + d, len(out) - 1))
    return [[text, s * 1e-9] for text, s in out]


def _host_span_at(host: list, a: int, b: int) -> str:
    """The host span that overlaps [a, b) the most."""
    best, name = 0, "host.other"
    for n, t, d in host:
        ov = min(b, t + d) - max(a, t)
        if ov > best:
            best, name = ov, n
    return name


def reduce(tr: dict, top: int = 10) -> dict:
    """Per-device busy, collective and exposed-collective seconds in the
    window, their means over the devices, and the breakdown."""
    w0, w1 = tr["window"]
    window_s = (w1 - w0) * 1e-9
    host = [h for h in tr["host"] if h[1] < w1 and h[1] + h[2] > w0]
    per_dev, op_time, gaps = [], defaultdict(float), []
    for dev in tr["devices"]:
        ops = [o for o in dev["ops"] if o[1] < w1 and o[1] + o[2] > w0]
        coll = _union(_clip(collective_intervals(ops), w0, w1))
        compute = _union(_clip([[t, t + d] for n, t, d in ops
                                if not COLLECTIVE.match(op_name(n))], w0, w1))
        busy = _union(coll + compute)
        per_dev.append({"busy_s": _length(busy) * 1e-9,
                        "collective_s": _length(coll) * 1e-9,
                        "exposed_collective_s": _length(_minus(coll, compute)) * 1e-9})
        clipped = [[x, max(t, w0), min(t + d, w1) - max(t, w0)] for x, t, d in ops]
        for text, s in self_times(clipped):
            op_time[text] += s
        for a, b in _minus([[w0, w1]], busy):
            gaps.append([_host_span_at(host, a, b), (b - a) * 1e-9])
    n = len(per_dev)
    mean = {k: sum(d[k] for d in per_dev) / n for k in per_dev[0]}
    host_s = defaultdict(float)
    for name, t, d in host:
        host_s[name] += (min(t + d, w1) - max(t, w0)) * 1e-9
    return {
        "window_s": window_s,
        "devices": per_dev,
        **mean,
        "host_s": dict(host_s),
        "device_ops": sorted(([k, v / n] for k, v in op_time.items()),
                             key=lambda x: -x[1])[:top],
        "idle_gaps": sorted(gaps, key=lambda x: -x[1])[:top],
    }
