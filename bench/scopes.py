"""Device time by step phase and collective time by mesh axis.

The program names its phases with ``jax.named_scope`` (``train/step.py``)
and each hop of a chunk's collective with the axis it runs on
(``comms/hierarchical.py``).  The names reach the ``op_name`` metadata of
the compiled step's HLO.  ``hlo_map`` reads that text once and maps each
HLO instruction name to its ``op_name`` and, for a collective, the mesh
axes its replica groups (or source-target pairs) span.  ``reduce`` then
puts each device op of a trace record (``bench/trace.py``) down to those
names:

- phase own time: each op's own time in the window goes to the first phase
  its ``op_name`` holds as a path component, in the order of ``PHASES``;
  ``backward`` is ``transpose(jvp(forward))``, ``forward`` is
  ``jvp(forward)`` or ``forward``; anything else is ``unscoped``;
- per-axis collective time: the union of the collective intervals (an
  asynchronous op from its start to its done) of the collectives that
  span each axis; one over two axes counts in both;
- scope own time: each op's own time goes to every scope its ``op_name``
  holds (``scopes_of``), so a nested scope counts inside its parent, and a
  scope under a transformation (``jvp(moe)``, ``transpose(jvp(moe))``)
  counts as the scope itself, forward and backward together.  A later
  configuration's metric reads its own scope here with no edit to this
  file.

All are means over the devices, in seconds.  The reduction keeps its own
reading of the HLO text, so its numbers do not move when the program's
HLO audit (``comms/schedule_bridge``) changes.  ``load_spans`` reads the
program's own host spans (``data.produce``) from the same trace.

In a ``--trace 1`` run the harness puts ``per_step_ms`` of its window into
the record its metric readers get, as ``record["scopes"]``;
``bench/record_scopes.py`` prints the same record.
"""
from __future__ import annotations

import glob
import os
import re

import numpy as np

from bench import trace

PHASES = ("themis_flatten", "themis_rs", "themis_ag", "themis_unravel", "optimizer")
BACKWARD = "transpose(jvp(forward))"
FORWARD = ("jvp(forward)", "forward")

INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.-]+) = ")
OP_NAME = re.compile(r'op_name="([^"]*)"')
GROUPS_LIST = re.compile(r"replica_groups=\{((?:\{[\d,]*\},?)*)\}")
GROUPS_IOTA = re.compile(r"replica_groups=\[([\d,]+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?")
PAIRS = re.compile(r"source_target_pairs=\{((?:\{\d+,\d+\},?)*)\}")
DONE_OF = re.compile(r"-done\([^%]*%([\w.-]+)")
MADE = re.compile(r"(?:^|/)[a-z][\w-]*\.\d+$")  # an op_name the compiler made
COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.-]+)\s")
WRAPPED = re.compile(r"^[\w-]+\((.*)\)$")  # a transformation's name: jvp(forward)
CALLEES = re.compile(
    r"(?:body|condition|to_apply|calls|true_computation|false_computation)=(%?[\w.-]+)"
    r"|branch_computations=\{([^}]*)\}")


def phase_of(op_name: str) -> str:
    """The step phase an ``op_name`` belongs to (the compiler joins the names
    of fused ops with ``;``)."""
    parts = re.split(r"[/;]", op_name)
    for p in PHASES:
        if p in parts:
            return p
    if BACKWARD in parts:
        return "backward"
    if any(p in parts for p in FORWARD):
        return "forward"
    return "unscoped"


def scopes_of(op_name: str) -> set[str]:
    """The scopes an ``op_name`` holds: every path component but the last,
    which names the operation, with transformations unwrapped
    (``transpose(jvp(forward))`` is ``forward``)."""
    out = set()
    for path in op_name.split(";"):
        for part in path.split("/")[:-1]:
            while m := WRAPPED.match(part):
                part = m.group(1)
            out.add(part)
    return out


def replica_groups(line: str) -> list[list[int]] | None:
    """The device groups of a collective's HLO line, in either form:
    ``{{0,1},{2,3}}`` or ``[2,2]<=[4]`` / ``[2,2]<=[2,2]T(1,0)``.  Source-target
    pairs count as groups of two.  None where the line states none."""
    m = GROUPS_LIST.search(line)
    if m:
        return [[int(x) for x in g.split(",") if x]
                for g in re.findall(r"\{([\d,]*)\}", m.group(1))]
    m = GROUPS_IOTA.search(line)
    if m:
        shape = [int(x) for x in m.group(1).split(",")]
        dims = [int(x) for x in m.group(2).split(",")]
        ids = np.arange(int(np.prod(dims))).reshape(dims)
        if m.group(3):
            ids = ids.transpose([int(x) for x in m.group(3).split(",")])
        return ids.reshape(shape).tolist()
    m = PAIRS.search(line)
    if m:
        return [[int(a), int(b)] for a, b in re.findall(r"\{(\d+),(\d+)\}", m.group(1))]
    return None


def axes_spanned(groups: list[list[int]], shape: tuple[int, ...],
                 names: tuple[str, ...]) -> tuple[str, ...]:
    """The mesh axes along which the members of some group differ.  Device
    ids are positions in the mesh's device array; an empty group list means
    every device."""
    if not groups or not any(groups):
        return tuple(n for n, s in zip(names, shape) if s > 1)
    span = set()
    for g in groups:
        coords = np.array(np.unravel_index(g, shape)).T
        span |= {names[i] for i in np.flatnonzero((coords != coords[0]).any(0))}
    return tuple(n for n in names if n in span)


def _instructions(hlo_text: str):
    """``(computation, name, op_name, operands, line)`` of each instruction,
    and ``{computation: instruction}`` of the computations that a ``while``,
    ``conditional`` or ``call`` runs (fused computations are not listed)."""
    instrs, callers, comp = [], {}, None
    for line in hlo_text.splitlines():
        if line and not line[0].isspace():
            h = COMPUTATION.match(line)
            comp = h.group(1) if h else comp
            continue
        m = INSTR.match(line)
        if not m:
            continue
        name, rest = m.group(1), line[m.end():]
        head = rest.split(", metadata=")[0].split(", backend_config=")[0]
        op = OP_NAME.search(line)
        instrs.append((comp, name, op.group(1) if op else "",
                       re.findall(r"%([\w.-]+)", head), line))
        if " fusion(" not in head:
            for one, many in CALLEES.findall(head):
                for c in re.findall(r"[\w.-]+", f"{one},{many}".replace("%", "")):
                    callers[c] = name
    return instrs, callers


def hlo_map(hlo_text: str, mesh_shape: tuple[int, ...],
            axis_names: tuple[str, ...]) -> dict[str, list]:
    """``{instruction: [op_name, [axes]]}`` of the compiled step's HLO text;
    the axes are empty except on collectives.  A ``-done`` takes the axes of
    its ``-start``.

    The compiler drops the metadata of some instructions it makes, such as
    the loops that lay the flat gradient out as chunks and the all-reduces
    it makes of the chunked collectives, or names them after another
    instruction (``.../broadcast.32``).  Such an instruction takes the
    ``op_name`` of its nearest user whose ``op_name`` names a phase (the
    value it makes is what the program named), else that of the ``while``
    or ``call`` that runs its computation, else that of its nearest operand
    that names a phase."""
    instrs, callers = _instructions(hlo_text)
    own = {name: "" if MADE.search(op) else op for _, name, op, _, _ in instrs}
    comp_of = {name: comp for comp, name, _, _, _ in instrs}
    operands = {name: [a for a in args if a in own] for _, name, _, args, _ in instrs}
    users = {}
    for name, args in operands.items():
        for a in args:
            users.setdefault(a, []).append(name)

    def nearest(name, edges):
        seen, todo = {name}, [name]
        for n in todo:
            for nxt in edges.get(n, []):
                if nxt not in seen and comp_of[nxt] == comp_of[name]:
                    if phase_of(own[nxt]) != "unscoped":
                        return own[nxt]
                    seen.add(nxt)
                    todo.append(nxt)
        return ""

    resolved = {}

    def op_name(name):
        if name not in resolved:
            resolved[name] = ""  # guards a cycle of callers
            caller = callers.get(comp_of[name])
            resolved[name] = (own[name] or nearest(name, users)
                              or (op_name(caller) if caller else "")
                              or nearest(name, operands))
        return resolved[name]

    out, done_of = {}, {}
    for _, name, _, _, line in instrs:
        axes = []
        if trace.COLLECTIVE.match(name):
            d = DONE_OF.search(line)
            if "-done" in name and d:
                done_of[name] = d.group(1)
            else:
                groups = replica_groups(line)
                if groups is not None:
                    axes = list(axes_spanned(groups, mesh_shape, axis_names))
        out[name] = [op_name(name), axes]
    for done, start in done_of.items():
        if start in out:
            out[done][1] = out[start][1]
    return out


def reduce(tr: dict, ops_map: dict[str, list]) -> dict:
    """Phase own seconds, per-axis collective seconds and scope own seconds
    in the window of the trace record ``tr``, means over its devices.  Ops
    missing from ``ops_map`` count as ``unscoped``, span no axis and hold
    no scope."""
    w0, w1 = tr["window"]
    per_dev = []
    for dev in tr["devices"]:
        ops = [o for o in dev["ops"] if o[1] < w1 and o[1] + o[2] > w0]
        phases = dict.fromkeys(PHASES + ("backward", "forward", "unscoped"), 0.0)
        by_scope = {}
        clipped = [[x, max(t, w0), min(t + d, w1) - max(t, w0)] for x, t, d in ops]
        for text, s in trace.self_times(clipped):
            op = ops_map.get(trace.op_name(text))
            phases[phase_of(op[0]) if op else "unscoped"] += s
            for scope in scopes_of(op[0]) if op else ():
                by_scope[scope] = by_scope.get(scope, 0.0) + s
        axes = {}
        for text, t, d in ops:
            for ax in (ops_map.get(trace.op_name(text)) or ["", []])[1]:
                axes.setdefault(ax, []).append([text, t, d])
        per_dev.append((phases, {
            ax: trace._length(trace._union(trace._clip(
                trace.collective_intervals(a), w0, w1))) * 1e-9
            for ax, a in axes.items()}, by_scope))
    n = len(per_dev)

    def mean(dicts):
        names = dict.fromkeys(k for d in dicts for k in d)
        return {k: sum(d.get(k, 0.0) for d in dicts) / n for k in names}

    phases, axes, by_scope = zip(*per_dev)
    return {"phases": mean(phases), "axes": mean(axes), "by_scope": mean(by_scope)}


def load_spans(log_dir: str, prefix: str = "data.") -> list[list]:
    """``[name, start_ns, duration_ns]`` of the host spans the program names
    ``prefix...`` (``data.produce``, ``data/pipeline.py``) in the one trace
    under ``log_dir``.  ``trace.load`` keeps only the harness's ``bench.*``
    spans; these are read apart, so its readings do not move."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if len(files) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}, found {files}")
    return [[e.name, int(e.start_ns), int(e.duration_ns)]
            for plane in ProfileData.from_file(files[0]).planes
            if plane.name.startswith("/host:")
            for ln in plane.lines for e in ln.events if e.name.startswith(prefix)]


def span_seconds(spans: list[list], window: list[int]) -> dict[str, float]:
    """Seconds of each span name inside the window."""
    w0, w1 = window
    out = {}
    for name, t, d in spans:
        if t < w1 and t + d > w0:
            out[name] = out.get(name, 0.0) + (min(t + d, w1) - max(t, w0)) * 1e-9
    return out


def per_step_ms(tr: dict, ops_map: dict[str, list], steps: int) -> dict:
    """Device ms per step of each phase, of each mesh axis's collectives and
    of each scope, and host ms per step of each of the program's ``data.*``
    spans, in the window of the trace record ``tr`` (its ``"data"`` key
    holds the spans, from ``load_spans``)."""
    r = reduce(tr, ops_map)
    r["data"] = span_seconds(tr.get("data", []), tr["window"])
    return {f"{k}_ms": {name: v / steps * 1e3 for name, v in r[k].items()}
            for k in ("phases", "axes", "by_scope", "data")}
