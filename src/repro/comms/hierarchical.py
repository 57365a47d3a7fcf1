"""Chunked hierarchical collectives over mesh axes (inside shard_map).

The TPU-native realization of the paper's multi-rail hierarchical algorithm
(Sec. 2.3): an All-Reduce over D mesh axes is a pipeline of per-axis
Reduce-Scatters followed by All-Gathers in reverse order; the gradient
buffer is split into chunks and **each chunk carries its own axis order** —
the Themis schedule (Sec. 4).  Because a chunk's AG order is the reverse of
its RS order (Algorithm 1 line 8), `psum_scatter`/`all_gather` pairs invert
each other exactly and the element layout round-trips with no index
bookkeeping.

These functions must run inside a ``shard_map`` that is *manual* over every
axis in the chunk orders.  Each hop runs under ``jax.named_scope`` named
``rs_<axis>`` or ``ag_<axis>``, so a profile shows the axis of every hop.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

AxisOrder = tuple[str, ...]


def world_size(axes: tuple[str, ...]) -> int:
    return math.prod(jax.lax.axis_size(a) for a in axes)


def pad_to_chunks(flat: jax.Array, n_chunks: int, axes: tuple[str, ...]):
    """Pad a flat vector so it splits into n_chunks divisible by the world."""
    world = world_size(axes)
    n = flat.shape[0]
    per = -(-n // (n_chunks * world)) * world
    padded = jnp.pad(flat, (0, n_chunks * per - n))
    return padded.reshape(n_chunks, per), n


def chunked_reduce_scatter(
    chunks: jax.Array, orders: list[AxisOrder]
) -> list[jax.Array]:
    """chunks: (C, L) local addends -> list of C shards (L/world each).

    Chunk i is reduce-scattered along ``orders[i]`` axis-by-axis; the final
    shard this device owns is the nested (order-lexicographic) block.
    """
    out = []
    for i, order in enumerate(orders):
        y = chunks[i]
        for ax in order:
            with jax.named_scope(f"rs_{ax}"):
                y = jax.lax.psum_scatter(y, ax, scatter_dimension=0, tiled=True)
        out.append(y)
    return out


def chunked_all_gather(
    shards: list[jax.Array], orders: list[AxisOrder]
) -> jax.Array:
    """Inverse of ``chunked_reduce_scatter`` (AG order = reverse RS order)."""
    out = []
    for y, order in zip(shards, orders):
        for ax in reversed(order):
            with jax.named_scope(f"ag_{ax}"):
                y = jax.lax.all_gather(y, ax, axis=0, tiled=True)
        out.append(y)
    return jnp.stack(out)  # (C, L)


def chunked_all_reduce(
    flat: jax.Array, orders: list[AxisOrder], *, mean: bool = True
) -> jax.Array:
    """Themis/baseline-scheduled hierarchical All-Reduce of a flat buffer."""
    axes = tuple(orders[0])
    chunks, n = pad_to_chunks(flat, len(orders), axes)
    shards = chunked_reduce_scatter(chunks, orders)
    if mean:
        w = world_size(axes)
        shards = [s / w for s in shards]
    gathered = chunked_all_gather(shards, orders)
    return gathered.reshape(-1)[:n]


# -- int8-on-the-wire reduce-scatter (beyond paper: gradient compression) ----
def _quantize(x: jax.Array):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-8) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def int8_reduce_scatter_axis(y: jax.Array, axis: str):
    """Reduce-scatter with int8 payload on the wire.

    psum_scatter would carry fp32; instead: quantize, all_to_all the int8
    shards, de-quantize with gathered scales, and reduce locally.  4x less
    wire traffic per hop at ~0.4% relative quantization error (compensated
    globally by error feedback in the optimizer wrapper).
    """
    a = jax.lax.axis_size(axis)
    q, scale = _quantize(y)
    qs = q.reshape(a, -1)
    recv = jax.lax.all_to_all(qs, axis, split_axis=0, concat_axis=0, tiled=False)
    scales = jax.lax.all_gather(scale, axis)
    deq = recv.astype(jnp.float32) * scales[:, None]
    return deq.sum(0)


def chunked_reduce_scatter_int8(chunks, orders):
    out = []
    for i, order in enumerate(orders):
        y = chunks[i]
        for ax in order:
            with jax.named_scope(f"rs_{ax}"):
                y = int8_reduce_scatter_axis(y, ax)
        out.append(y)
    return out
