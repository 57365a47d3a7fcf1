"""Multi-device checks (run as a subprocess with 8 virtual CPU devices).

Covers: chunked hierarchical AR correctness with mixed per-chunk orders,
int8-on-the-wire RS, manual Themis ZeRO-2 step vs GSPMD reference,
pipeline-parallel loss equality, serve-path sharded prefill/decode.
Exits non-zero on any failure; the pytest wrapper asserts the exit code.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.comms.hierarchical import (  # noqa: E402
    chunked_all_reduce,
    int8_reduce_scatter_axis,
)
from repro.comms.schedule_bridge import themis_axis_orders  # noqa: E402
from repro.configs import ParallelConfig, TrainConfig, get_arch  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.models import build_model  # noqa: E402


def check_chunked_all_reduce():
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    n = 1234
    orders = themis_axis_orders({"pod": 2, "data": 2, "model": 2}, n * 4, 6,
                                "themis")
    # force diverse orders incl. non-baseline
    orders[0] = ("pod", "model", "data")
    x = jnp.asarray(np.random.default_rng(0).standard_normal((8, n)),
                    jnp.float32)

    f = jax.jit(jax.shard_map(
        lambda xl: chunked_all_reduce(xl[0], [tuple(o) for o in orders],
                                      mean=False)[None],
        mesh=mesh, in_specs=P(("pod", "data", "model")),
        out_specs=P(("pod", "data", "model")), check_vma=False))
    out = np.asarray(f(x))
    want = np.asarray(x).sum(0)
    for row in out:
        # fp32 8-way sums: hierarchical reduction order differs from numpy
        np.testing.assert_allclose(row, want, rtol=1e-3, atol=1e-3)
    print("chunked_all_reduce OK")


def check_int8_rs():
    mesh = make_mesh((8,), ("data",))
    n = 64 * 8
    x = jnp.asarray(np.random.default_rng(1).standard_normal((8, n)),
                    jnp.float32)

    f = jax.jit(jax.shard_map(
        lambda xl: int8_reduce_scatter_axis(xl[0], "data")[None],
        mesh=mesh, in_specs=P("data"), out_specs=P("data"), check_vma=False))
    out = np.asarray(f(x)).reshape(-1)
    want = np.asarray(x).sum(0)
    rel = np.abs(out - want) / (np.abs(want) + 1e-3)
    assert rel.mean() < 0.05, f"int8 RS error too large: {rel.mean()}"
    print("int8_reduce_scatter OK (mean rel err %.4f)" % rel.mean())


def check_themis_step_matches_gspmd():
    from repro.train.step import (
        gspmd_init_state,
        make_gspmd_train_step,
        make_themis_train_step,
    )

    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    cfg = get_arch("qwen2.5-3b", reduced=True).replace(remat=False)
    api = build_model(cfg)
    tcfg = TrainConfig(learning_rate=1e-3, warmup_steps=1, total_steps=10,
                       weight_decay=0.0)
    rng = np.random.default_rng(0)
    batch = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 32)),
                              jnp.int32),
        "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 32)),
                              jnp.int32),
    }
    step_t, init_t, orders = make_themis_train_step(
        api, mesh, ParallelConfig(data=2, model=2, pods=2, dp_sync="themis",
                                  chunks_per_collective=4), tcfg)
    pt, ot = init_t(0)
    step_g, *_ = make_gspmd_train_step(
        api, mesh, ParallelConfig(data=2, model=2, pods=2), tcfg)
    pg, og = gspmd_init_state(api, mesh,
                              ParallelConfig(data=2, model=2, pods=2))
    for i in range(2):
        pt, ot, mt = step_t(pt, ot, batch)
        pg, og, mg = step_g(pg, og, batch)
    lt, lg = float(mt["loss"]), float(mg["loss"])
    assert abs(lt - lg) < 0.05, f"themis {lt} vs gspmd {lg}"
    assert len(set(orders)) >= 1
    print(f"themis-vs-gspmd OK (loss {lt:.4f} vs {lg:.4f}; "
          f"{len(set(orders))} distinct orders)")


def check_int8_themis_step_trains():
    from repro.train.step import make_themis_train_step

    mesh = make_mesh((2, 4), ("data", "model"))
    cfg = get_arch("llama3-8b", reduced=True).replace(remat=False)
    api = build_model(cfg)
    tcfg = TrainConfig(learning_rate=1e-2, warmup_steps=1, total_steps=10,
                       weight_decay=0.0)
    step_t, init_t, _ = make_themis_train_step(
        api, mesh, ParallelConfig(data=2, model=4, dp_sync="themis",
                                  chunks_per_collective=2,
                                  compression="int8"), tcfg)
    p, o = init_t(0)
    rng = np.random.default_rng(0)
    batch = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 32)),
                              jnp.int32),
        "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 32)),
                              jnp.int32),
    }
    losses = []
    for i in range(6):
        p, o, m = step_t(p, o, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0], f"int8 training diverged: {losses}"
    print(f"int8 themis step OK ({losses[0]:.3f} -> {losses[-1]:.3f})")


def check_chunk_geometry_2x2():
    """On a 2x2 mesh every device's shard of a chunk is whole f32 tiles,
    and the first moment after one step, read back through the layout
    ``bench/check.py`` reads, is (1 - beta1) times the clipped gradient of
    each leaf."""
    import math
    import sys
    from pathlib import Path

    from repro.comms.hierarchical import TILE
    from repro.models.registry import count_params
    from repro.train.step import make_themis_train_step

    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    from bench import check

    mesh = make_mesh((2, 2), ("data", "model"), devices=jax.devices()[:4])
    # f32 compute: in bf16 the devices' quarter batches round otherwise
    # than the whole batch of the reference
    cfg = get_arch("qwen2.5-3b", reduced=True).replace(remat=False, dtype="float32")
    api = build_model(cfg)
    tcfg = TrainConfig(learning_rate=1e-3, warmup_steps=1, total_steps=10)
    step, init_state, orders = make_themis_train_step(
        api, mesh, ParallelConfig(data=2, model=2, dp_sync="themis",
                                  chunks_per_collective=4), tcfg)
    assert len(set(orders)) == 2, orders  # both axis orders are exercised
    params, opt = init_state(0)
    n = count_params(api.param_spec())
    n_chunks, per_chunk = opt["m"].shape
    assert per_chunk % (4 * TILE) == 0 and n_chunks * per_chunk >= n
    assert n_chunks * per_chunk - n < n_chunks * 4 * TILE

    rng = np.random.default_rng(0)
    tok = rng.integers(0, cfg.vocab_size, (8, 17), dtype=np.int32)
    batch = {"tokens": jnp.asarray(tok[:, :-1]), "labels": jnp.asarray(tok[:, 1:])}
    g = jax.tree.map(lambda x: np.asarray(x, np.float64),
                     jax.grad(api.loss_fn)(params, batch))
    gnorm = math.sqrt(sum(float(np.sum(x * x)) for x in jax.tree.leaves(g)))
    scale = (1 - tcfg.beta1) * min(1.0, tcfg.grad_clip / gnorm)
    like = check.by_path(params)
    _, opt, _ = step(params, opt, batch)
    spec = opt["m"].sharding.spec
    flat = check.themis_flat(np.asarray(opt["m"]), orders, spec[1], dict(mesh.shape))
    assert not flat[n:].any(), "padding is not at the tail alone"
    got = check.split_flat(flat, like)
    want = {k: scale * w.reshape(-1) for k, w in check.by_path(g).items()}
    # a key bias has a gradient of round-off alone (softmax ignores it), so
    # each gap is taken over the larger of its leaf's norm and the median's
    median = float(np.median([check.norm(w) for w in want.values()]))
    for k, w in want.items():
        gap = check.norm(got[k] - w) / max(check.norm(w), median)
        # the same sums in another order: f32 round-off
        assert gap <= 1e-4, f"{k}: first moment off by {gap}"
    print(f"chunk geometry 2x2 OK (per_chunk {per_chunk}, orders {sorted(set(orders))})")


def check_pipeline_parallel():
    from repro.models import transformer as tr
    from repro.train.pipeline import make_pipeline_loss

    cfg = get_arch("llama3-8b", reduced=True).replace(num_layers=4,
                                                      remat=False)
    api = build_model(cfg)
    params = api.init(jax.random.key(0))
    mesh = make_mesh((4,), ("pipe",))
    loss_fn = make_pipeline_loss(cfg, mesh, n_micro=4)
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 16)), jnp.int32)
    labs = jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 16)), jnp.int32)
    lp = float(jax.jit(loss_fn)(params, toks, labs))
    lref = float(tr.loss_fn(params, {"tokens": toks, "labels": labs}, cfg))
    assert abs(lp - lref) < 1e-3, f"pipeline {lp} vs ref {lref}"
    g = jax.jit(jax.grad(lambda p: loss_fn(p, toks, labs)))(params)
    gn = sum(float(jnp.sum(x.astype(jnp.float32) ** 2))
             for x in jax.tree.leaves(g))
    assert np.isfinite(gn) and gn > 0
    print(f"pipeline-parallel OK (loss {lp:.4f} == {lref:.4f})")


def check_sharded_serving():
    from repro.configs import ShapeConfig
    from repro.train.serve import make_serve_fns

    mesh = make_mesh((2, 4), ("data", "model"))
    cfg = get_arch("llama3-8b", reduced=True).replace(remat=False)
    api = build_model(cfg)
    shape = ShapeConfig("serve", 32, 4, "decode")
    jit_prefill, jit_decode, sh = make_serve_fns(
        api, mesh, ParallelConfig(data=2, model=4), shape)
    params = api.init(jax.random.key(0))
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 32)),
                                   jnp.int32)}
    logits, caches = jit_prefill(params, batch)
    tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
    logits2, caches = jit_decode(params, caches, tok,
                                 jnp.asarray(32, jnp.int32))
    assert bool(jnp.isfinite(logits2).all())
    print("sharded serving OK")


if __name__ == "__main__":
    check_chunked_all_reduce()
    check_int8_rs()
    check_themis_step_matches_gspmd()
    check_int8_themis_step_trains()
    check_chunk_geometry_2x2()
    check_pipeline_parallel()
    check_sharded_serving()
    print("ALL MULTIDEVICE CHECKS PASSED")
