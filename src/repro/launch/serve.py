"""Serving driver: batched prefill + autoregressive decode.

    PYTHONPATH=src python -m repro.launch.serve --arch llama3-8b --reduced \
        --batch 4 --prompt-len 32 --gen 16 --mesh 2x4 [--kv-quant]

Builds the sharded prefill/decode programs (train/serve.py), runs a batch
of synthetic requests through them, and reports per-token decode latency.
"""
from __future__ import annotations

import argparse
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3-8b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--mesh", default="1x1", help="DATAxMODEL")
    ap.add_argument("--kv-quant", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import ParallelConfig, ShapeConfig, get_arch
    from repro.launch.cache import enable_compile_cache
    from repro.launch.mesh import make_mesh
    from repro.models import build_model
    from repro.train.serve import make_serve_fns

    enable_compile_cache()
    data, model = (int(x) for x in args.mesh.split("x"))
    mesh = make_mesh((data, model), ("data", "model"))
    cfg = get_arch(args.arch, reduced=args.reduced)
    if args.kv_quant:
        cfg = cfg.replace(kv_quant=True)
    api = build_model(cfg)
    total = args.prompt_len + args.gen
    shape = ShapeConfig("serve", total, args.batch, "decode")
    jit_prefill, jit_decode, _ = make_serve_fns(
        api, mesh, ParallelConfig(data=data, model=model), shape)

    print(f"[serve] {args.arch} reduced={args.reduced} mesh={args.mesh} "
          f"kv_quant={args.kv_quant}")
    params = api.init(jax.random.key(0))
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, (args.batch, args.prompt_len)),
        jnp.int32)}
    if cfg.family == "audio":
        batch["frames"] = jnp.asarray(
            rng.standard_normal((args.batch, cfg.num_frames, cfg.d_model)),
            jnp.bfloat16)
    if cfg.family == "vlm":
        batch["patches"] = jnp.asarray(
            rng.standard_normal((args.batch, cfg.num_patches, cfg.d_model)),
            jnp.bfloat16)

    t0 = time.time()
    logits, caches = jit_prefill(params, batch)
    logits.block_until_ready()
    print(f"[serve] prefill {args.batch}x{args.prompt_len}: "
          f"{(time.time()-t0)*1e3:.0f} ms (incl. compile)")

    tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
    out = [tok]
    t0 = time.time()
    for i in range(args.gen):
        pos = jnp.asarray(args.prompt_len + i, jnp.int32)
        logits, caches = jit_decode(params, caches, tok, pos)
        tok = jnp.argmax(logits[:, 0], -1).astype(jnp.int32)
        out.append(tok)
    jax.block_until_ready(tok)
    dt = (time.time() - t0) / args.gen
    print(f"[serve] decode: {dt*1e3:.1f} ms/token "
          f"({args.batch/dt:.1f} tok/s aggregate)")
    print(f"[serve] sample output ids: "
          f"{[int(t[0]) for t in out[:10]]}")


if __name__ == "__main__":
    main()
