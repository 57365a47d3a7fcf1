#!/usr/bin/env python3
"""Readings that a cell's correctness limits are set from (run on the chip).

    python3 bench/calibrate.py --workload <cell> --seeds <n> --first-seed <s> \
        [--faults <k>] [--out <file.json>]

For each of ``n`` seeds from ``s`` on, the program's first steps against
the plain reference (the sound runs: the lower readings).  The reference
is the module that the cell's configuration file names; its ``VARIANTS``
are the sound reference, then the control, then the faults planted in
the reference.  For ``bench/reference.py`` the control is the reference
computed with float8 products, and the planted fault half of the batch
left out.  For the first ``k`` seeds, the control and those faults put in
the program's place, read against the same reference, and on a cell of
several chips the exchange between chips left out (planted in the
program: each device keeps its own shard of its own gradient).  A step that returns its state unchanged reads
1 on ``grad_gap`` and ``change_gap`` by construction and needs no run.

The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness as H  # noqa: E402


def local_reduce_scatter(chunks, orders):
    """The reduce-scatter with its exchange left out."""
    import jax

    out = []
    for i, order in enumerate(orders):
        y = chunks[i]
        for ax in order:
            n = y.shape[0] // jax.lax.axis_size(ax)
            y = jax.lax.dynamic_slice(y, (jax.lax.axis_index(ax) * n,), (n,))
        out.append(y)
    return out


def sound_readings(cell, seed):
    params, opt, pf, raw = H.first_steps(cell, seed)
    pf.close()
    del params, opt
    return H.program_readings(cell, raw)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, required=True)
    ap.add_argument("--faults", type=int, default=3)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    found = H.resolve(args.workload)
    config, traffic, chips = found["config"], found["traffic"], found["cell"]["chips"]
    devs, device = H.require_chips(chips)
    from bench import check

    reference = H.config_module(config, "reference")
    sound, control, *planted = reference.VARIANTS
    H.enable_cache()
    cell = H.Cell(config, traffic, devs)
    refs = {}
    kinds = ["sound", "control", *planted, "no_exchange"]
    out = {"workload": args.workload, "device": device, "leaves": [],
           **{kind: [] for kind in kinds}}

    def ref_of(seed, variant=sound):
        if variant not in refs:
            refs[variant] = reference.Reference(config, traffic["train"], variant)
        return H.reference_readings(refs[variant], config, traffic, chips, seed)

    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    truth = {}
    for seed in seeds:
        t0 = time.perf_counter()
        prog = sound_readings(cell, seed)
        t1 = time.perf_counter()
        truth[seed] = ref_of(seed)
        nums = check.numbers(prog, truth[seed])
        out["sound"].append({"seed": seed, **nums})
        out["leaves"].append({"seed": seed, "program": prog, "reference": truth[seed]})
        H.log(f"[sound] seed {seed} {nums} program {t1 - t0:.1f} s "
              f"reference {time.perf_counter() - t1:.1f} s")
    for seed in seeds[: args.faults]:
        for variant in (control, *planted):
            got = ref_of(seed, variant)
            nums = check.numbers(got, truth[seed])
            if variant == control:
                out["leaves"].append({"seed": seed, "control": got})
            out["control" if variant == control else variant].append({"seed": seed, **nums})
            H.log(f"[{variant}] seed {seed} {nums}")
    if chips > 1 and args.faults:
        import repro.train.step as S

        S.chunked_reduce_scatter = local_reduce_scatter
        broken = H.Cell(config, traffic, devs)
        for seed in seeds[: args.faults]:
            nums = check.numbers(sound_readings(broken, seed), truth[seed])
            out["no_exchange"].append({"seed": seed, **nums})
            H.log(f"[no_exchange] seed {seed} {nums}")
    for kind in kinds:
        rows_ = out[kind]
        if rows_:
            pick = max if kind == "sound" else min
            out[f"{kind}_{pick.__name__}"] = {
                k: pick(r[k] for r in rows_) for k in ("loss_gap", "grad_gap", "change_gap")}
            H.log(f"[{kind}] {pick.__name__} {out[f'{kind}_{pick.__name__}']}")
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
