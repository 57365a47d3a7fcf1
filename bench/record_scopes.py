#!/usr/bin/env python3
"""Device time by step phase and collective time by mesh axis of a cell.

    python3 bench/record_scopes.py --workload <cell> --seed <n> --seconds <s> \
        [--keep-ms <ms> --out <file.json.gz>]

Runs the cell's first steps and a traced window as a ``--trace 1`` run
does, and prints one JSON line: the window's steps, the device's busy and
collective ms per step (``bench/trace.py``), and per step the own ms of
each named phase, the collective ms over each mesh axis and the host ms of
each ``data.*`` span (``bench/scopes.py``).  With ``--out`` it also writes
the neutral record of ``bench/trace.py``, cut to the first ``--keep-ms``
of the window, with the program's ``data.*`` spans under ``"data"`` and
the compiled step's ``[op_name, axes]`` of each instruction the kept ops
name under ``"hlo"``.  The tests of the scope reduction read such a file.
"""
from __future__ import annotations

import argparse
import gzip
import json
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness as H  # noqa: E402
from bench.record_trace import cut  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10)
    ap.add_argument("--keep-ms", type=float, default=1000)
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    found = H.resolve(args.workload)
    try:
        devs, _ = H.require_chips(found["cell"]["chips"])
    except H.NoChip as e:
        H.log(f"[fail] {e}")
        return 2
    import jax

    from repro.launch.cache import enable_compile_cache

    from bench import scopes, trace

    enable_compile_cache()
    # The cache key leaves out metadata by default, so a step compiled
    # before by a program without the named scopes would be read back
    # without them, and every op would read unscoped.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    cell = H.Cell(found["config"], found["traffic"], devs)
    params, opt, pf, _ = H.first_steps(cell, args.seed)
    tmp = tempfile.mkdtemp(prefix="bench_scopes_")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tmp, profiler_options=opts)
        try:
            params, opt, losses, window_s = H.window(cell.compiled, params, opt, pf,
                                                     args.seconds)
        finally:
            jax.profiler.stop_trace()
        record = trace.load(tmp)
        record["data"] = scopes.load_spans(tmp)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    pf.close()

    steps = len(losses)
    ops_map = scopes.hlo_map(cell.compiled.as_text(), cell.mesh.devices.shape,
                             cell.mesh.axis_names)
    red = trace.reduce(record)
    out = {"workload": args.workload, "seed": args.seed, "steps": steps,
           "window_s": window_s, "busy_ms": red["busy_s"] / steps * 1e3,
           "collective_ms": red["collective_s"] / steps * 1e3,
           **scopes.per_step_ms(record, ops_map, steps)}
    if args.out:
        small = cut(record, args.keep_ms)
        small["data"] = [e for e in record["data"]
                         if e[1] < small["window"][1] and e[1] + e[2] > small["window"][0]]
        kept = {trace.op_name(o[0]) for d in small["devices"] for o in d["ops"]}
        small["hlo"] = {k: v for k, v in ops_map.items() if k in kept}
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        with gzip.open(args.out, "wt") as f:
            json.dump(small, f)
        H.log(f"[out] {args.out}: {sum(len(d['ops']) for d in small['devices'])} ops")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
