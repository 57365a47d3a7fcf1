"""Jit'd public wrappers for the Pallas kernels.

Kernels lower to Mosaic for the TPU.  ``interpret=True`` runs the kernel
body through the Pallas interpreter instead (any backend, for correctness
checks); only the caller decides that.  ``flash_attention`` installs a
``jax.custom_vjp`` whose backward recomputes attention blockwise in XLA
(the standard recompute-based flash backward data-flow), so the kernel can
be used in training code.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import rglru as _rg
from repro.kernels import rmsnorm as _rn
from repro.models.common import flash_attention_xla


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal=True, window=0, q_offset=0,
                    interpret=False):
    if q_offset:
        # Decode-style offsets take the XLA path (kernel assumes offset 0).
        return flash_attention_xla(q, k, v, causal=causal, window=window,
                                   q_offset=q_offset)
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               interpret=interpret)


def _fa_fwd(q, k, v, causal, window, q_offset, interpret):
    out = flash_attention(q, k, v, causal, window, q_offset, interpret)
    return out, (q, k, v)


def _fa_bwd(causal, window, q_offset, interpret, res, g):
    q, k, v = res

    def f(q, k, v):
        return flash_attention_xla(q, k, v, causal=causal, window=window,
                                   q_offset=q_offset)

    _, vjp = jax.vjp(f, q, k, v)
    return vjp(g)


flash_attention.defvjp(_fa_fwd, _fa_bwd)


def rglru_scan(a, b, h0=None, interpret=False):
    return _rg.rglru_scan(a, b, h0, interpret=interpret)


def rmsnorm(x, w, eps=1e-6, interpret=False):
    return _rn.rmsnorm(x, w, eps, interpret=interpret)
