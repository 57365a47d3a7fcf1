"""Pallas TPU RG-LRU linear-recurrence scan kernel.

GPU implementations scan with warp shuffles; on TPU the natural shape is a
*channel-parallel, time-sequential* kernel: grid over (batch, channel
blocks, time blocks), each step loading an (bt x bc) tile of the
coefficient arrays into VMEM and iterating time rows with the running
hidden state h (1, bc) held in VMEM scratch across the time-block grid
dimension.  Channels are fully vectorized on the VPU lanes (block 128+);
time rows are read and written through the refs one aligned 8-row tile
at a time.

Computes h_t = a_t * h_{t-1} + b_t given precomputed per-step (a, b)
(the gate math stays in XLA where it fuses with the surrounding matmuls).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_C = 256
DEFAULT_BLOCK_T = 256
_ROWS = 8  # fp32 sublanes per vreg: the kernel walks time one tile at a time


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _rglru_kernel(a_ref, b_ref, h0_ref, o_ref, h_ref, *, block_t: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        h_ref[...] = h0_ref[0]                         # (1, bc)

    def tile(i, h):
        # one aligned (8, bc) tile of time rows through the refs; the
        # recurrence runs over its rows in registers
        rows = pl.ds(pl.multiple_of(i * _ROWS, _ROWS), _ROWS)
        a = a_ref[0, rows, :]
        b = b_ref[0, rows, :]
        row = jax.lax.broadcasted_iota(jnp.int32, a.shape, 0)
        out = jnp.zeros_like(a)
        for r in range(_ROWS):
            h = a[r:r + 1] * h + b[r:r + 1]
            out = jnp.where(row == r, h, out)
        o_ref[0, rows, :] = out.astype(o_ref.dtype)
        return h

    h_ref[...] = jax.lax.fori_loop(0, block_t // _ROWS, tile, h_ref[...])


def rglru_scan(
    a: jax.Array, b: jax.Array, h0: jax.Array | None = None, *,
    block_c: int = DEFAULT_BLOCK_C, block_t: int = DEFAULT_BLOCK_T,
    interpret: bool = False,
) -> jax.Array:
    """a, b: (B, S, C) fp32; h0: (B, C) or None -> h: (B, S, C)."""
    bsz, s, c = a.shape
    if h0 is None:
        h0 = jnp.zeros((bsz, c), jnp.float32)
    bc = min(block_c, c)
    bt = min(_round_up(block_t, _ROWS), _round_up(s, _ROWS))
    pad_c = (-c) % bc
    pad_t = (-s) % bt
    if pad_c or pad_t:
        a = jnp.pad(a, ((0, 0), (0, pad_t), (0, pad_c)))
        b = jnp.pad(b, ((0, 0), (0, pad_t), (0, pad_c)))
        h0 = jnp.pad(h0, ((0, 0), (0, pad_c)))
    nc = (c + pad_c) // bc
    nt = (s + pad_t) // bt

    kernel = functools.partial(_rglru_kernel, block_t=bt)
    out = pl.pallas_call(
        kernel,
        grid=(bsz, nc, nt),
        in_specs=[
            pl.BlockSpec((1, bt, bc), lambda b_, ci, ti: (b_, ti, ci)),
            pl.BlockSpec((1, bt, bc), lambda b_, ci, ti: (b_, ti, ci)),
            pl.BlockSpec((1, 1, bc), lambda b_, ci, ti: (b_, 0, ci)),
        ],
        out_specs=pl.BlockSpec((1, bt, bc), lambda b_, ci, ti: (b_, ti, ci)),
        out_shape=jax.ShapeDtypeStruct((bsz, s + pad_t, c + pad_c), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, bc), jnp.float32)],
        interpret=interpret,
    )(a, b, h0[:, None, :])
    return out[:, :s, :c]
