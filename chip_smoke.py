#!/usr/bin/env python3
"""Smoke run of the Themis ZeRO-2 train step on a TPU.

    python chip_smoke.py             # one chip: scheduler + trainer phases
    python chip_smoke.py --chips 4   # the 2x2 mesh phase only

Runs in one process and starts no other.  The trainer phase drives
``repro.launch.train.main`` at the full width of qwen2.5-3b (d_model 2048,
16 q-heads / 2 kv-heads, d_ff 11008, vocab 151936; random weights from a
seed) with the depth cut to fit one 16 GiB v5e chip, once with
``--dp-sync themis`` and once with ``--dp-sync gspmd``.  On one device both
do the same math, so their losses must agree step for step.

When JAX finds no TPU, or any phase fails, the script exits non-zero and
prints no result line.  Otherwise its last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "qwen2.5-3b"
SEQ = 1024
BATCH_PER_CHIP = 4
STEPS = 5
# Depth cut (widths untouched).  Compiled for a described v5e chip, three
# layers need 14.05 GiB for the Themis step (its state: params, fp32
# master, m and v; the step's gradients and gathered params) against the
# compiler's 15.75 GiB; four do not fit.  The 2x2 mesh keeps the same depth.
LAYERS = 3
MESH_STEPS = 3
# Relative loss agreement.  Both modes run the same bf16 forward on the same
# fp32 weights and data; they differ only in how XLA fuses the two programs
# and in the order of fp32 reductions (gradient norm, ZeRO chunking, and
# on the mesh the reduce-scatter/all-gather or tensor-parallel partial
# sums).  Such differences stay orders of magnitude below 1e-3 of a loss
# near ln(vocab) ~ 11.9 over these few steps; a wrong gradient sync or
# optimizer update moves the loss by more.
LOSS_RTOL = 1e-3


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def device_phase(want_chips: int) -> dict:
    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    print(f"[device] {json.dumps(dev)}", flush=True)
    check(dev["platform"] == "tpu", f"no TPU: JAX runs on {dev['platform']}")
    check(dev["count"] >= want_chips,
          f"{want_chips} chips wanted, {dev['count']} found")
    return dev


class CacheCounter:
    """Counts JAX's persistent compilation cache hits and misses."""

    def __init__(self):
        import jax

        from repro.launch.cache import enable_compile_cache

        self.dir = enable_compile_cache()
        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **kwargs) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def report(self) -> None:
        print(f"[cache] dir={self.dir} hits={self.hits} misses={self.misses}",
              flush=True)


def scheduler_phase() -> None:
    """Algorithm 1 on the host: mesh axis orders, and one 64 MB All-Reduce
    on the paper's Table-2 2D-SW_SW fabric under Themis and the baseline."""
    from repro.comms.schedule_bridge import themis_axis_orders
    from repro.core.requests import CollectiveRequest
    from repro.core.simulator import simulate_requests
    from repro.topology import make_table2_topologies

    orders = themis_axis_orders({"data": 2, "model": 2}, 64e6, 16, "themis")
    print(f"[sched] 2x2 themis orders: {['->'.join(o) for o in orders]}")
    check(len(orders) == 16 and all(sorted(o) == ["data", "model"]
                                    for o in orders),
          f"bad axis orders {orders}")

    topo = make_table2_topologies()["2D-SW_SW"]
    span = {}
    for policy in ("themis", "baseline"):
        res, _ = simulate_requests(topo, [CollectiveRequest("AR", 64e6)],
                                   policy=policy)
        span[policy] = res.makespan
        print(f"[sched] 2D-SW_SW 64 MB AR {policy}: makespan "
              f"{res.makespan * 1e6} us (simulated)", flush=True)
    check(math.isfinite(span["themis"]) and span["themis"] > 0,
          f"bad makespan {span}")
    check(span["themis"] <= span["baseline"],
          f"themis makespan {span['themis']} > baseline {span['baseline']}")


def check_losses_agree(runs: dict[str, list[float]], steps: int) -> None:
    (ref_name, ref), *others = runs.items()
    for name, losses in runs.items():
        check(len(losses) == steps, f"{name}: {len(losses)} of {steps} steps")
        check(all(math.isfinite(x) for x in losses),
              f"{name}: non-finite loss {losses}")
    for name, losses in others:
        for i, (a, b) in enumerate(zip(losses, ref)):
            rel = abs(a - b) / abs(b)
            print(f"[agree] step {i}: {name} {a} {ref_name} {b} rel {rel}")
            check(rel <= LOSS_RTOL,
                  f"step {i}: {name} loss {a} vs {ref_name} {b} (rel {rel})")


def trainer_phase() -> None:
    """The one-chip path through the training driver's entry point."""
    from repro.launch import train

    runs = {}
    for mode in ("themis", "gspmd"):
        argv = ["--arch", ARCH, "--layers", str(LAYERS), "--mesh", "1x1",
                "--batch", str(BATCH_PER_CHIP), "--seq", str(SEQ),
                "--steps", str(STEPS), "--dp-sync", mode,
                "--log-every", str(STEPS)]
        print(f"[train] {mode}: {' '.join(argv)}", flush=True)
        runs[mode] = train.main(argv)
        print(f"[train] {mode} losses {runs[mode]}", flush=True)
    check_losses_agree(runs, STEPS)


def mesh_phase() -> None:
    """themis, hier_baseline and gspmd on a 2x2 ("data", "model") mesh from
    the same init and batch: orders, collectives as compiled, and losses."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding

    from repro.comms.schedule_bridge import collective_stats
    from repro.configs import ParallelConfig, TrainConfig, get_arch
    from repro.launch.mesh import make_mesh
    from repro.models import build_model
    from repro.sharding.specs import batch_pspec
    from repro.train.step import (
        gspmd_init_state,
        make_gspmd_train_step,
        make_themis_train_step,
    )

    mesh = make_mesh((2, 2), ("data", "model"), devices=jax.devices()[:4])
    cfg = get_arch(ARCH).replace(num_layers=LAYERS)
    api = build_model(cfg)
    tcfg = TrainConfig(total_steps=MESH_STEPS, warmup_steps=1)
    batch, seq = 4 * BATCH_PER_CHIP, SEQ
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab_size, (batch, seq + 1), dtype=np.int32)
    sharding = NamedSharding(mesh, batch_pspec((batch, seq), mesh, batch))
    host_batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}

    runs = {}
    for mode in ("themis", "hier_baseline", "gspmd"):
        parallel = ParallelConfig(data=2, model=2, dp_sync=mode)
        if mode == "gspmd":
            jit_step, *_ = make_gspmd_train_step(api, mesh, parallel, tcfg)
            params, opt = gspmd_init_state(api, mesh, parallel, seed=0)
        else:
            jit_step, init_state, orders = make_themis_train_step(
                api, mesh, parallel, tcfg)
            params, opt = init_state(0)
            print(f"[mesh] {mode} per-chunk RS orders: "
                  f"{['->'.join(o) for o in orders]}")
        b = {k: jax.device_put(v, sharding) for k, v in host_batch.items()}
        t0 = time.perf_counter()
        step = jit_step.lower(params, opt, b).compile()
        print(f"[mesh] {mode} compiled in {time.perf_counter() - t0} s; "
              f"collective ops {collective_stats(step.as_text())['op_counts']}",
              flush=True)
        losses = []
        for i in range(MESH_STEPS):
            params, opt, metrics = step(params, opt, b)
            losses.append(float(metrics["loss"]))
            if i == 0:
                jax.block_until_ready((params, opt))
                t0 = time.perf_counter()
        jax.block_until_ready((params, opt))
        print(f"[mesh] {mode} losses {losses}; steady-state step time "
              f"{(time.perf_counter() - t0) / (MESH_STEPS - 1) * 1e3} ms", flush=True)
        runs[mode] = losses
        del params, opt
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in mesh.devices.flat]
    print(f"[mesh] peak_bytes_in_use per device, all three modes {peaks}")
    check_losses_agree(runs, MESH_STEPS)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the 2x2 mesh phase")
    args = ap.parse_args(argv)
    try:
        dev = device_phase(args.chips)
        cache = CacheCounter()
        if args.chips == 4:
            mesh_phase()
        else:
            scheduler_phase()
            trainer_phase()
        cache.report()
    except SmokeFailure as e:
        print(f"[fail] {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
