"""End-to-end training driver (fault-tolerant).

    PYTHONPATH=src python -m repro.launch.train --arch llama3-8b \
        --steps 200 --batch 8 --seq 256 --mesh 1x1 --reduced \
        --dp-sync gspmd --ckpt-dir runs/ckpt

``--layers N`` cuts the depth of the chosen architecture and keeps its
widths (a full-width model on one chip).  Compiled programs go to the
persistent cache of ``repro.launch.cache``.

Features: synthetic data pipeline with host prefetch, AdamW + cosine LR,
grad clipping, gradient accumulation, periodic atomic checkpoints with
async writer, resume-from-latest (exact data-cursor resume), Themis or
baseline hierarchical gradient sync (``--dp-sync``), optional int8
compression.  Survives SIGTERM/crash: rerun the same command and it
continues from the newest valid checkpoint (elastic: the mesh may differ).
"""
from __future__ import annotations

import argparse
import time


def main(argv: list[str] | None = None) -> list[float]:
    """Train; ``argv`` defaults to ``sys.argv[1:]``.  Returns the losses."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3-8b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--layers", type=int, default=0,
                    help="override num_layers (depth only; widths stay)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--mesh", default="1x1", help="DATAxMODEL[xPOD]")
    ap.add_argument("--dp-sync", default="gspmd",
                    choices=["gspmd", "themis", "hier_baseline"])
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--compression", default="none", choices=["none", "int8"])
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from repro.ckpt import AsyncCheckpointer, latest_step, restore
    from repro.configs import ParallelConfig, TrainConfig, get_arch
    from repro.data import Prefetcher, SyntheticLM
    from repro.launch.cache import enable_compile_cache
    from repro.launch.mesh import make_mesh
    from repro.models import build_model
    from repro.train.step import (
        gspmd_init_state,
        make_gspmd_train_step,
        make_themis_train_step,
    )

    dims = [int(x) for x in args.mesh.split("x")]
    while len(dims) < 3:
        dims.append(1)
    data, model, pods = dims
    names = ("pod", "data", "model") if pods > 1 else ("data", "model")
    shape = (pods, data, model) if pods > 1 else (data, model)
    enable_compile_cache()
    mesh = make_mesh(shape, names)

    cfg = get_arch(args.arch, reduced=args.reduced)
    if args.layers:
        cfg = cfg.replace(num_layers=args.layers)
    api = build_model(cfg)
    parallel = ParallelConfig(data=data, model=model, pods=pods,
                              dp_sync=args.dp_sync,
                              compression=args.compression)
    tcfg = TrainConfig(learning_rate=args.lr, total_steps=args.steps,
                       warmup_steps=max(args.steps // 20, 1),
                       microbatch=args.microbatch,
                       checkpoint_every=args.ckpt_every,
                       checkpoint_dir=args.ckpt_dir)

    if args.dp_sync == "gspmd":
        jit_step, p_shard, o_shard, _ = make_gspmd_train_step(
            api, mesh, parallel, tcfg)
        params, opt = gspmd_init_state(api, mesh, parallel)
    else:
        jit_step, init_state, orders = make_themis_train_step(
            api, mesh, parallel, tcfg)
        params, opt = init_state()
        uniq = sorted(set(orders))
        print(f"[train] themis chunk orders ({len(orders)} chunks): "
              + ", ".join("->".join(o) or "local" for o in uniq))

    start_step = 0
    ckpt = None
    if args.ckpt_dir:
        ckpt = AsyncCheckpointer(args.ckpt_dir, keep=3)
        last = latest_step(args.ckpt_dir)
        if last is not None:
            (params, opt), extra = restore(
                args.ckpt_dir, (params, opt))
            start_step = extra.get("next_step", last)
            print(f"[train] resumed from step {last} "
                  f"(data cursor -> {start_step})")

    ds = SyntheticLM(cfg.vocab_size, args.batch, args.seq, seed=tcfg.seed)
    pf = Prefetcher(ds, mesh, start_step=start_step)

    t_last = time.time()
    losses = []
    for step, batch in pf:
        if step >= args.steps:
            break
        params, opt, metrics = jit_step(params, opt, batch)
        losses.append(float(metrics["loss"]))
        if len(losses) == 1:  # the steady-state window starts after compile
            jax.block_until_ready((params, opt))
            t_steady = time.perf_counter()
        if (step + 1) % args.log_every == 0:
            dt = (time.time() - t_last) / args.log_every
            t_last = time.time()
            print(f"[train] step {step+1:5d} loss={np.mean(losses[-args.log_every:]):.4f} "
                  f"gnorm={float(metrics['gnorm']):.3f} "
                  f"lr={float(metrics['lr']):.2e} {dt*1e3:.0f} ms/step")
        if ckpt and (step + 1) % tcfg.checkpoint_every == 0:
            ckpt.save_async(step + 1, (params, opt),
                            extra={"next_step": step + 1, "seed": tcfg.seed})
    jax.block_until_ready((params, opt))
    if len(losses) > 1:
        dt = (time.perf_counter() - t_steady) / (len(losses) - 1)
        print(f"[train] steady-state step time {dt * 1e3} ms "
              f"(mean of {len(losses) - 1} steps after the first)")
    pf.close()
    if ckpt:
        ckpt.wait()
    stats = jax.local_devices()[0].memory_stats()
    if stats and "peak_bytes_in_use" in stats:
        # the device keeps one peak for the whole process, not one per call
        print(f"[train] peak_bytes_in_use {stats['peak_bytes_in_use']} "
              f"on {jax.local_devices()[0]} (since the process started)")
    print(f"[train] done: {len(losses)} steps, "
          f"loss {losses[0]:.4f} -> {np.mean(losses[-10:]):.4f}")
    return losses


if __name__ == "__main__":
    main()
