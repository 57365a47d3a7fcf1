#!/usr/bin/env python3
"""Device time by step phase and collective time by mesh axis of a cell.

    python3 bench/record_scopes.py --workload <cell> --seed <n> --seconds <s> \
        [--keep-ms <ms> --out <file.json.gz>]

Makes one ``--trace 1`` run of the cell (``bench.harness.measure``) and
prints one JSON line from the record its metrics were read from: the
window's steps, the device's busy and collective ms per step
(``bench/trace.py``), and per step the own ms of each named phase and of
each scope, the collective ms over each mesh axis and the host ms of each
``data.*`` span (``bench/scopes.py``), with ``correct``.  With ``--out`` it
also writes the neutral record of ``bench/trace.py``, cut to the first
``--keep-ms`` of the window, with the program's ``data.*`` spans under
``"data"`` and the compiled step's ``[op_name, axes]`` of each instruction
the kept ops name under ``"hlo"``.  The tests of the scope reduction read
such a file.
"""
from __future__ import annotations

import argparse
import gzip
import json
import sys
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

    try:
        result, rec = H.measure(args.workload, args.seed, args.seconds, True)
    except H.NoChip as e:
        H.log(f"[fail] {e}")
        return 2
    from bench import trace

    steps, red = rec["steps"], rec["trace"]
    out = {"workload": args.workload, "seed": args.seed, "steps": steps,
           "window_s": rec["window_s"], "busy_ms": red["busy_s"] / steps * 1e3,
           "collective_ms": red["collective_s"] / steps * 1e3, **rec["scopes"],
           "correct": result["correct"]}
    if args.out:
        record = rec["trace_record"]
        small = cut(record, args.keep_ms)
        small["data"] = [e for e in record["data"]
                         if e[1] < small["window"][1] and e[1] + e[2] > small["window"][0]]
        kept = {trace.op_name(o[0]) for d in small["devices"] for o in d["ops"]}
        small["hlo"] = {k: v for k, v in rec["ops_map"].items() if k in kept}
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        with gzip.open(args.out, "wt") as f:
            json.dump(small, f)
        H.log(f"[out] {args.out}: {sum(len(d['ops']) for d in small['devices'])} ops")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
