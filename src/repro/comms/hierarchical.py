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


# f32 elements in one (8, 128) TPU tile: a flat vector is laid out in
# 1024-element tiles, a 2-D buffer in (8, 128) ones
TILE = 1024


def chunk_len(n: int, n_chunks: int, world: int) -> int:
    """Length of each of ``n_chunks`` chunks that together hold ``n``
    elements: a multiple of ``world * TILE``, so that every device's shard
    of a chunk is whole f32 tiles.  The padding is at most
    ``n_chunks * world * TILE`` elements, all of it at the tail."""
    unit = world * TILE
    return -(-n // (n_chunks * unit)) * unit


def _pieces(sizes: list[int], per_chunk: int):
    """Where the parts of ``sizes``, laid end to end, fall in rows of
    ``per_chunk``: ``(part, start, stop, row, row_start)`` per piece."""
    at = 0
    for i, n in enumerate(sizes):
        a = 0
        while a < n:
            row, col = divmod(at + a, per_chunk)
            b = min(n, a + per_chunk - col)
            yield i, a, b, row, col
            a = b
        at += n


def split_chunks(parts: list[jax.Array], n_chunks: int, per_chunk: int) -> jax.Array:
    """``(n_chunks, per_chunk)``: the 1-D ``parts`` laid end to end, cut into
    rows and zero-padded at the tail.  Each row is concatenated from static
    slices of the parts, so no flat vector is built, and none is reshaped:
    the TPU compiler turns the reshape of a flat vector into rows into a
    row-by-row loop where a row is not whole tiles."""
    dtype = parts[0].dtype
    rows = [[] for _ in range(n_chunks)]
    for i, a, b, row, _ in _pieces([p.shape[0] for p in parts], per_chunk):
        rows[row].append(parts[i][a:b])
    for r in rows:
        fill = per_chunk - sum(x.shape[0] for x in r)
        if fill:
            r.append(jnp.zeros((fill,), dtype))
    return jnp.stack([jnp.concatenate(r) for r in rows])


def join_chunks(chunks: jax.Array, sizes: list[int]) -> list[jax.Array]:
    """Inverse of ``split_chunks``: the 1-D parts of ``sizes``, each
    concatenated from static slices of the rows."""
    per_chunk = chunks.shape[1]
    parts = [[] for _ in sizes]
    for i, a, b, row, col in _pieces(sizes, per_chunk):
        parts[i].append(chunks[row, col:col + b - a])
    return [jnp.concatenate(p) if p else chunks[0, :0] for p in parts]


def pad_to_chunks(flat: jax.Array, n_chunks: int, axes: tuple[str, ...]):
    """Split a flat vector into ``n_chunks`` whose shards are whole tiles."""
    n = flat.shape[0]
    return split_chunks([flat], n_chunks, chunk_len(n, n_chunks, world_size(axes))), n


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
    return join_chunks(chunked_all_gather(shards, orders), [n])[0]


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
