#!/usr/bin/env python3
"""Record a short traced window of a cell and keep its first milliseconds.

    python3 bench/record_trace.py --workload <cell> --seed <n> --seconds <s> \
        --keep-ms <ms> --out <file.json.gz>

Runs the cell's first steps and a traced window as a ``--trace 1`` run
does, and writes the neutral record of ``bench/trace.py``, cut to the
first ``--keep-ms`` of the window, to ``--out``.  The tests of the trace
reduction read such a file.
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


def cut(record: dict, keep_ms: float) -> dict:
    w0, w1 = record["window"]
    w1 = min(w1, w0 + int(keep_ms * 1e6))

    def inside(evs):
        return [e for e in evs if e[1] < w1 and e[1] + e[2] > w0]

    return {"window": [w0, w1], "host": inside(record["host"]),
            "devices": [{"name": d["name"], "ops": inside(d["ops"])}
                        for d in record["devices"]]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=3)
    ap.add_argument("--keep-ms", type=float, default=1000)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    found = H.resolve(args.workload)
    devs, _ = H.require_chips(found["cell"]["chips"])
    from bench import trace

    H.enable_cache()
    cell = H.Cell(found["config"], found["traffic"], devs)
    params, opt, pf, _ = H.first_steps(cell, args.seed)
    with H.profiled(True) as prof:
        params, opt, losses, window_s = H.window(cell.compiled, params, opt, pf,
                                                 args.seconds)
    pf.close()
    red = trace.reduce(prof["record"])
    H.log(f"[reduce] {len(losses)} steps in {window_s} s: "
          f"{ {k: v for k, v in red.items() if k != 'devices'} }")
    small = cut(prof["record"], args.keep_ms)
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    with gzip.open(args.out, "wt") as f:
        json.dump(small, f)
    H.log(f"[out] {args.out}: {sum(len(d['ops']) for d in small['devices'])} ops")
    return 0


if __name__ == "__main__":
    sys.exit(main())
