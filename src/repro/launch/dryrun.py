"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this builds the production mesh (16x16 single-pod or 2x16x16
multi-pod of host-platform placeholder devices), constructs the step
function (train_step for train shapes, serve prefill/decode for inference
shapes), lowers it against ShapeDtypeStruct inputs (zero allocation),
compiles it, and records:

  * memory_analysis()  — proves the cell fits (bytes per device),
  * cost_analysis()    — HLO FLOPs / bytes,
  * HLO collective stats (bytes by kind / replica-group size),
  * the analytic roofline terms (launch/roofline.py).

Usage:
  python -m repro.launch.dryrun --arch llama3-8b --shape train_4k --multi-pod
  python -m repro.launch.dryrun --all [--out runs/dryrun]
  python -m repro.launch.dryrun --arch qwen2.5-3b --shape train_4k \
      --dp-sync themis          # the paper-technique ZeRO-2 program
"""
import argparse
import json
import os
import time
import traceback


def parallel_for(arch_name: str, cfg, mesh_axes: dict, dp_sync: str = "gspmd"):
    from repro.configs.base import ParallelConfig
    from repro.models import build_model, count_params

    n = count_params(build_model(cfg).param_spec())
    return ParallelConfig(
        data=mesh_axes.get("data", 1),
        model=mesh_axes.get("model", 1),
        pods=mesh_axes.get("pod", 1),
        fsdp=n >= 8e9,
        # SP between blocks for transformer-family residual streams; the
        # recurrent/ssm/moe paths operate on full rows (scan over time /
        # per-row dispatch sort) and use microbatching instead.
        seq_sharding=cfg.family in ("dense", "vlm", "audio"),
        zero=1,
        dp_sync=dp_sync,
    )


def pick_microbatch(cfg, shape, mesh_axes: dict, parallel) -> int:
    """Gradient-accumulation factor so the layer-carry stack fits HBM.

    carry ~= L x tokens_local x d_model x 2B (bf16), /tp when seq-sharded.
    Target <= 2 GiB per device."""
    dp = mesh_axes.get("data", 1) * mesh_axes.get("pod", 1)
    tp = mesh_axes.get("model", 1)
    b_loc = max(shape.global_batch // dp, 1)
    carry = cfg.num_layers * b_loc * shape.seq_len * cfg.d_model * 2
    if parallel.seq_sharding:
        carry /= tp
    target = 2 * 2**30
    n = 1
    while carry / n > target and n < b_loc and shape.global_batch % (2 * n) == 0:
        n *= 2
    return n


def dryrun_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
                dp_sync: str = "gspmd", verbose: bool = True,
                kv_quant: bool = False,
                mesh_split: tuple[int, int] | None = None) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.configs import get_arch
    from repro.configs.base import ALL_SHAPES, applicable_shapes
    from repro.launch.mesh import make_production_mesh
    from repro.launch.roofline import compute_roofline
    from repro.comms.schedule_bridge import collective_stats
    from repro.models import build_model, count_params
    from repro.models.common import mesh_context
    from repro.sharding.specs import (
        batch_pspec, cache_pspec, param_shardings, tree_shardings,
    )
    from jax.sharding import NamedSharding, PartitionSpec as P

    cfg = get_arch(arch)
    if kv_quant:
        cfg = cfg.replace(kv_quant=True)
    shape = {s.name: s for s in ALL_SHAPES}[shape_name]
    if shape not in applicable_shapes(cfg):
        return {"arch": arch, "shape": shape_name, "status": "skipped",
                "reason": "long_500k requires sub-quadratic attention"}

    if mesh_split is not None:
        # Perf-iteration lever: re-balance the logical (data, model) split
        # over the same 256 chips (e.g. 32x8 for serving workloads).
        from repro.launch.mesh import make_mesh

        mesh = make_mesh(mesh_split, ("data", "model"))
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
    axes = dict(mesh.shape)
    api = build_model(cfg)
    n_params = count_params(api.param_spec())
    parallel = parallel_for(arch, cfg, axes, dp_sync)

    t0 = time.time()
    if shape.kind == "train":
        if dp_sync == "gspmd":
            from repro.train.step import make_gspmd_train_step
            from repro.train.optimizer import adamw_init

            tcfg = _tcfg()
            import dataclasses
            tcfg = dataclasses.replace(
                tcfg, microbatch=pick_microbatch(cfg, shape, axes, parallel))
            jit_step, p_shard, o_shard, batch_sh = make_gspmd_train_step(
                api, mesh, parallel, tcfg)
            params_s = api.param_spec()
            opt_s = {"m": jax.eval_shape(adamw_init, params_s)["m"],
                     "v": jax.eval_shape(adamw_init, params_s)["v"],
                     "count": jax.ShapeDtypeStruct((), jnp.int32)}
            batch_s = api.batch_spec(shape)
            lowered = jit_step.lower(params_s, opt_s, batch_s)
        else:
            from repro.train.step import make_themis_train_step

            # Themis manual mode: pure DP over all axes; global batch must
            # cover the device count — use a world-sized batch.
            world = 1
            for v in axes.values():
                world *= v
            from repro.configs.base import ShapeConfig
            shape = ShapeConfig(shape.name, shape.seq_len,
                                max(shape.global_batch, world), shape.kind)
            jit_step, init_state, orders = make_themis_train_step(
                api, mesh, parallel, _tcfg())
            params_s, opt_s = jax.eval_shape(init_state)
            batch_s = api.batch_spec(shape)
            lowered = jit_step.lower(params_s, opt_s, batch_s)
    elif shape.kind == "prefill":
        from repro.train.serve import make_serve_fns

        jit_prefill, _, _ = make_serve_fns(api, mesh, parallel, shape)
        params_s = api.param_spec()
        batch_s = api.batch_spec(shape)
        lowered = jit_prefill.lower(params_s, batch_s)
    else:  # decode
        from repro.train.serve import make_serve_fns

        _, jit_decode, _ = make_serve_fns(api, mesh, parallel, shape)
        params_s = api.param_spec()
        caches_s, token_s, pos_s = api.decode_spec(shape)
        lowered = jit_decode.lower(params_s, caches_s, token_s, pos_s)

    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    try:
        hlo = compiled.as_text()
    except Exception:
        hlo = lowered.as_text()
    stats = collective_stats(hlo)
    rl = compute_roofline(cfg, shape, n_params, parallel, axes,
                          hlo_flops=float(cost.get("flops", 0.0)))

    chips = 1
    for v in axes.values():
        chips *= v
    result = {
        "arch": arch, "shape": shape_name, "kind": shape.kind,
        "mesh": "x".join(f"{k}={v}" for k, v in axes.items()),
        "chips": chips, "dp_sync": dp_sync, "status": "ok",
        "n_params": n_params,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "per_device_total_gib": round(
                (mem.argument_size_in_bytes + mem.temp_size_in_bytes)
                / 2**30, 3),
        },
        "cost": {k: cost.get(k) for k in
                 ("flops", "bytes accessed") if k in cost},
        "collectives_hlo": stats,
        "roofline": {
            "compute_s": rl.compute_s, "memory_s": rl.memory_s,
            "collective_s": rl.collective_s, "per_axis_s": rl.per_axis_s,
            "dominant": rl.dominant, "model_flops": rl.model_flops,
            "analytic_flops": rl.analytic_flops,
            "useful_ratio": rl.useful_ratio,
            "roofline_fraction": rl.roofline_fraction,
            "step_time_s": rl.step_time_s,
        },
    }
    if verbose:
        print(f"[dryrun] {arch} x {shape_name} mesh={result['mesh']} "
              f"dp_sync={dp_sync}: OK "
              f"compile={t_compile:.1f}s "
              f"mem/dev={result['memory']['per_device_total_gib']}GiB "
              f"dominant={rl.dominant} frac={rl.roofline_fraction:.3f}")
        print("  memory_analysis:", mem)
        print("  cost_analysis: flops=%.3e bytes=%.3e" % (
            float(cost.get("flops", 0)), float(cost.get("bytes accessed", 0))))
        print("  hlo collectives:", json.dumps(stats["bytes_by_kind"]))
    return result


def _tcfg():
    from repro.configs.base import TrainConfig

    return TrainConfig()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--dp-sync", default="gspmd",
                    choices=["gspmd", "themis", "hier_baseline"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--kv-quant", action="store_true")
    ap.add_argument("--mesh-split", default="",
                    help="override single-pod logical split, e.g. 32x8")
    ap.add_argument("--tag", default="")
    ap.add_argument("--out", default="runs/dryrun")
    args = ap.parse_args()

    # placeholder host devices for the production meshes; must precede the
    # first use of a jax backend in this process
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    os.makedirs(args.out, exist_ok=True)
    cells = []
    if args.all:
        from repro.configs import list_archs
        from repro.configs.base import ALL_SHAPES

        for a in list_archs():
            for s in ALL_SHAPES:
                for mp in (False, True):
                    cells.append((a, s.name, mp))
    else:
        cells.append((args.arch, args.shape, args.multi_pod))

    split = None
    if args.mesh_split:
        split = tuple(int(x) for x in args.mesh_split.split("x"))
    failures = 0
    for arch, shape, mp in cells:
        tag = f"{arch}_{shape}_{'2x16x16' if mp else '16x16'}_{args.dp_sync}"
        if args.tag:
            tag += "_" + args.tag
        try:
            res = dryrun_cell(arch, shape, multi_pod=mp, dp_sync=args.dp_sync,
                              kv_quant=args.kv_quant, mesh_split=split)
        except Exception as e:
            traceback.print_exc()
            res = {"arch": arch, "shape": shape, "status": "FAIL",
                   "error": f"{type(e).__name__}: {e}"}
            failures += 1
        with open(os.path.join(args.out, tag + ".json"), "w") as f:
            json.dump(res, f, indent=1, default=float)
    if failures:
        raise SystemExit(f"{failures} dry-run cells failed")


if __name__ == "__main__":
    main()
