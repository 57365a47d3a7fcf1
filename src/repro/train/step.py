"""Train-step builders.

Two data-parallel gradient-sync modes:

* ``gspmd``  — production default for all architectures: single ``jax.jit``
  with GSPMD shardings; XLA inserts all collectives (TP/EP/FSDP included).
* ``themis`` / ``hier_baseline`` — the paper's technique as a first-class
  feature: the entire step runs in a ``shard_map`` manual over every mesh
  axis (pure-DP ZeRO-2).  The gradient leaves, laid end to end in
  ``ravel_pytree`` order, are cut into chunks whose per-device shards are
  whole f32 tiles (``comms.hierarchical.chunk_len``), and
  reduce-scattered with per-chunk axis orders from the Themis scheduler
  (trace-time Algorithm 1); the sharded AdamW update runs on each device's
  scattered shard against fp32 master shards; updated parameters are
  all-gathered chunk-by-chunk in reverse order (bf16 on the wire).
  ``hier_baseline`` pins the static dim1->dimD order for every chunk
  (paper Sec. 2.3) — the reproduction baseline.  Optional int8-on-the-wire
  reduce-scatter with per-device error feedback.

Both modes name their phases with ``jax.named_scope``: ``forward`` (and so
``transpose(jvp(forward))`` for the backward) and ``optimizer``; the Themis
mode adds ``themis_flatten``, ``themis_rs``, ``themis_ag`` and
``themis_unravel``.  The names reach the compiled HLO's ``op_name``
metadata and cost nothing at run time.
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.comms.hierarchical import (
    _quantize,
    chunk_len,
    chunked_all_gather,
    chunked_reduce_scatter,
    chunked_reduce_scatter_int8,
    join_chunks,
    split_chunks,
)
from repro.comms.schedule_bridge import themis_axis_orders
from repro.configs.base import ParallelConfig, TrainConfig
from repro.models.common import mesh_context
from repro.models.registry import ModelApi, count_params
from repro.sharding.specs import batch_pspec, opt_state_pspec, param_shardings
from repro.train.optimizer import adamw_init, adamw_update, clip_by_global_norm, lr_schedule


def _forward(api: ModelApi, params, batch):
    """The loss under the ``forward`` scope: differentiated, its ops are named
    ``jvp(forward)`` and the backward's ``transpose(jvp(forward))``."""
    with jax.named_scope("forward"):
        return api.loss_fn(params, batch)


# --------------------------------------------------------------------------
# GSPMD mode
# --------------------------------------------------------------------------
def _gspmd_shardings(api: ModelApi, mesh: Mesh, parallel: ParallelConfig):
    """(param shardings, AdamW state shardings) of the GSPMD mode."""
    pspec_tree = api.param_spec()
    p_shard = param_shardings(pspec_tree, mesh, parallel)

    def opt_shard_of(leaf_spec, ns):
        return NamedSharding(
            mesh, opt_state_pspec(ns.spec, leaf_spec.shape, mesh, parallel)
        )

    mv = jax.tree.map(opt_shard_of, pspec_tree, p_shard)
    return p_shard, {"m": mv, "v": mv, "count": NamedSharding(mesh, P())}


def make_gspmd_train_step(
    api: ModelApi, mesh: Mesh, parallel: ParallelConfig, tcfg: TrainConfig
):
    """Returns (jit_step, param_shardings, opt_shardings, batch_sharding_fn)."""
    p_shard, o_shard = _gspmd_shardings(api, mesh, parallel)

    n_micro = max(tcfg.microbatch, 1)

    def grads_of(params, batch):
        """Gradient accumulation: scan over n_micro microbatches so live
        activations are O(batch / n_micro) (compute/comm overlap: the DP
        collectives of microbatch i overlap microbatch i+1's backward under
        XLA's async scheduler)."""
        if n_micro == 1:
            return jax.value_and_grad(lambda p: _forward(api, p, batch))(params)
        micro = jax.tree.map(
            lambda x: x.reshape((n_micro, x.shape[0] // n_micro) + x.shape[1:]),
            batch,
        )

        def body(acc, mb):
            loss_i, g_i = jax.value_and_grad(
                lambda p: _forward(api, p, mb)
            )(params)
            acc_loss, acc_g = acc
            return (acc_loss + loss_i,
                    jax.tree.map(jnp.add, acc_g, g_i)), None

        zero = (jnp.zeros((), jnp.float32),
                jax.tree.map(lambda p: jnp.zeros(p.shape, p.dtype), params))
        (loss_sum, g_sum), _ = jax.lax.scan(body, zero, micro)
        inv = 1.0 / n_micro
        return loss_sum * inv, jax.tree.map(lambda g: g * inv, g_sum)

    def step(params, opt_state, batch):
        with mesh_context(mesh, sp=parallel.seq_sharding):
            loss, grads = grads_of(params, batch)
            with jax.named_scope("optimizer"):
                grads, gnorm = clip_by_global_norm(grads, tcfg.grad_clip)
                new_params, new_opt, lr = adamw_update(grads, opt_state, params, tcfg)
            return new_params, new_opt, {"loss": loss, "gnorm": gnorm, "lr": lr}

    jit_step = jax.jit(
        step,
        in_shardings=(p_shard, o_shard, None),
        out_shardings=(p_shard, o_shard, None),
        donate_argnums=(0, 1),
    )

    def batch_shardings(batch_spec_tree):
        return jax.tree.map(
            lambda s: NamedSharding(mesh, batch_pspec(s.shape, mesh, s.shape[0])),
            batch_spec_tree,
        )

    return jit_step, p_shard, o_shard, batch_shardings


def gspmd_init_state(api: ModelApi, mesh: Mesh, parallel: ParallelConfig,
                     seed: int = 0):
    """Initialize params + optimizer state directly into sharded buffers."""
    def init(key):
        params = api.init(key)
        return params, adamw_init(params)

    return jax.jit(init, out_shardings=_gspmd_shardings(api, mesh, parallel))(
        jax.random.key(seed))


# --------------------------------------------------------------------------
# Manual Themis ZeRO-2 mode (pure DP over every mesh axis)
# --------------------------------------------------------------------------
def _local_shard(y: jax.Array, order: tuple[str, ...]) -> jax.Array:
    """This device's nested block of a replicated chunk (zero-comm slicing
    matching the psum_scatter ownership for the given axis order)."""
    for ax in order:
        a = jax.lax.axis_size(ax)
        i = jax.lax.axis_index(ax)
        ln = y.shape[0] // a
        y = jax.lax.dynamic_slice(y, (i * ln,), (ln,))
    return y


def _raveled(tree) -> list[jax.Array]:
    """The leaves of ``tree`` as 1-D f32 arrays, in ``ravel_pytree`` order."""
    return [x.reshape(-1).astype(jnp.float32) for x in jax.tree.leaves(tree)]


def make_themis_train_step(
    api: ModelApi, mesh: Mesh, parallel: ParallelConfig, tcfg: TrainConfig
):
    """ZeRO-2 DP step with Themis-scheduled chunked RS/AG.

    All mesh axes act as DP dims (a D-dim hierarchical collective — the
    paper's exact setting).  Returns (jit_step, init_state_fn, orders);
    opt m/v/master live in the reduce-scattered layout.
    """
    axes = tuple(a for a in ("model", "data", "pod") if mesh.shape.get(a, 1) > 1)
    axis_sizes = {a: mesh.shape[a] for a in axes}
    world = math.prod(axis_sizes.values())

    n_params = count_params(api.param_spec())
    n_chunks = parallel.chunks_per_collective
    policy = "themis" if parallel.dp_sync == "themis" else "baseline"
    orders = [tuple(o) for o in
              themis_axis_orders(axis_sizes, n_params * 4, n_chunks, policy)]

    per_chunk = chunk_len(n_params, n_chunks, world)
    use_int8 = parallel.compression == "int8"

    # one mesh axis -> its name; several -> the tuple; none (a single
    # device) -> None: replicated specs, no collectives, world = 1
    dp_axes = axes[0] if len(axes) == 1 else (axes or None)
    shard_spec = P(None, dp_axes)  # (C, per_chunk) scattered layout

    def step_shard(params, master, m, v, count, err, batch):
        loss, grads = jax.value_and_grad(lambda p: _forward(api, p, batch))(params)
        with jax.named_scope("themis_flatten"):
            chunks = split_chunks(_raveled(grads), n_chunks, per_chunk)
            new_err = err
            if use_int8:
                chunks = chunks + split_chunks([err[0]], n_chunks, per_chunk)
                q, s = _quantize(chunks)
                new_err = join_chunks(chunks - q.astype(jnp.float32) * s,
                                      [n_params])[0][None]
        with jax.named_scope("themis_rs"):
            rs = (chunked_reduce_scatter_int8 if use_int8 else chunked_reduce_scatter)(
                chunks, orders
            )
            g_shard = jnp.stack(rs) / world            # (C, per_chunk / world)

        with jax.named_scope("optimizer"):
            # global-norm clip across the scattered shards
            sq = jnp.sum(jnp.square(g_shard))
            for a in axes:
                sq = jax.lax.psum(sq, a)
            gnorm = jnp.sqrt(sq)
            g_shard = g_shard * jnp.minimum(1.0, tcfg.grad_clip / jnp.maximum(gnorm, 1e-9))

            # ZeRO-2 AdamW on fp32 master shards
            count2 = count + 1
            lr = lr_schedule(tcfg, count2)
            b1, b2 = tcfg.beta1, tcfg.beta2
            c1 = 1.0 - b1 ** count2.astype(jnp.float32)
            c2 = 1.0 - b2 ** count2.astype(jnp.float32)
            m2 = b1 * m + (1 - b1) * g_shard
            v2 = b2 * v + (1 - b2) * jnp.square(g_shard)
            upd = (m2 / c1) / (jnp.sqrt(v2 / c2) + tcfg.eps) + tcfg.weight_decay * master
            master2 = master - lr * upd

        with jax.named_scope("themis_ag"):
            # all-gather updated params (compute dtype on the wire)
            p_dtype = jax.tree.leaves(params)[0].dtype
            gathered = chunked_all_gather(
                [master2[i].astype(p_dtype) for i in range(n_chunks)], orders
            )
        with jax.named_scope("themis_unravel"):
            leaves, treedef = jax.tree.flatten(params)
            parts = join_chunks(gathered, [x.size for x in leaves])
            new_params = treedef.unflatten(
                [x.reshape(p.shape).astype(p.dtype) for x, p in zip(parts, leaves)])
        for a in axes:
            loss = jax.lax.pmean(loss, a)
        return (new_params, master2, m2, v2, count2, new_err,
                {"loss": loss, "gnorm": gnorm, "lr": lr})

    err_spec = P(dp_axes, None) if use_int8 else P()
    shard_step = jax.shard_map(
        step_shard,
        mesh=mesh,
        in_specs=(P(), shard_spec, shard_spec, shard_spec, P(), err_spec,
                  P(dp_axes)),
        out_specs=(P(), shard_spec, shard_spec, shard_spec, P(), err_spec, P()),
        check_vma=False,
    )

    def step(params, opt_state, batch):
        new_p, master2, m2, v2, c2, err2, metrics = shard_step(
            params, opt_state["master"], opt_state["m"], opt_state["v"],
            opt_state["count"], opt_state["err"], batch,
        )
        return new_p, {"master": master2, "m": m2, "v": v2, "count": c2,
                       "err": err2}, metrics

    def build_master(params):
        chunks = split_chunks(_raveled(params), n_chunks, per_chunk)
        return jnp.stack([_local_shard(chunks[i], orders[i])
                          for i in range(n_chunks)])

    def init(key):
        params = api.init(key)
        master = jax.shard_map(build_master, mesh=mesh, in_specs=P(),
                               out_specs=shard_spec,
                               check_vma=False)(params)
        err_shape = (world, n_params) if use_int8 else ()
        opt = {"master": master, "m": jnp.zeros_like(master),
               "v": jnp.zeros_like(master),
               "count": jnp.zeros((), jnp.int32),
               "err": jnp.zeros(err_shape, jnp.float32)}
        return params, opt

    # One program that lays every buffer out on the mesh: params replicated,
    # optimizer state scattered.  The chunked copy of the params is a
    # temporary of this program and is freed when it returns.
    rep = NamedSharding(mesh, P())
    scat = NamedSharding(mesh, shard_spec)
    jit_init = jax.jit(init, out_shardings=(
        rep, {"master": scat, "m": scat, "v": scat, "count": rep,
              "err": NamedSharding(mesh, err_spec)}))

    def init_state(seed: int = 0):
        return jit_init(jax.random.key(seed))

    jit_step = jax.jit(step, donate_argnums=(0, 1))
    return jit_step, init_state, orders


def make_train_step(api, mesh, parallel: ParallelConfig, tcfg: TrainConfig):
    if parallel.dp_sync == "gspmd":
        return make_gspmd_train_step(api, mesh, parallel, tcfg)
    return make_themis_train_step(api, mesh, parallel, tcfg)
