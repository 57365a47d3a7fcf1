"""Pallas kernels vs pure-jnp oracles (interpret mode on CPU).

Shape/dtype sweeps via hypothesis + fixed allclose cases per kernel.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:
    from _hypothesis_shim import given, settings, strategies as st

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention as fa_raw
from repro.kernels.rglru import rglru_scan as rg_raw
from repro.kernels.rmsnorm import rmsnorm as rn_raw

RNG = np.random.default_rng(42)


def t(shape, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(RNG.standard_normal(shape) * scale, dtype)


def tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 2e-5


# -- flash attention ----------------------------------------------------------
@pytest.mark.parametrize("b,s,h,kv,d,tk,win", [
    (2, 128, 4, 2, 64, 128, 0),
    (1, 200, 8, 1, 64, 200, 0),       # MQA + ragged seq
    (2, 96, 4, 4, 32, 96, 32),        # sliding window
    (1, 64, 2, 2, 128, 256, 0),       # cross-length kv
    (1, 257, 3, 3, 16, 257, 64),      # odd sizes
])
def test_flash_attention_matches_oracle(b, s, h, kv, d, tk, win):
    q, k, v = t((b, s, h, d)), t((b, tk, kv, d)), t((b, tk, kv, d))
    out = fa_raw(q, k, v, causal=True, window=win, block_q=64, block_k=64,
                 interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=True, window=win)
    np.testing.assert_allclose(out, want, atol=5e-6, rtol=5e-5)


@given(
    b=st.integers(1, 2), s=st.sampled_from([17, 64, 130]),
    h=st.sampled_from([2, 4]), groups=st.sampled_from([1, 2]),
    d=st.sampled_from([16, 64]),
    dtype=st.sampled_from([jnp.float32, jnp.bfloat16]),
    causal=st.booleans(),
)
@settings(max_examples=12, deadline=None)
def test_flash_attention_hypothesis_sweep(b, s, h, groups, d, dtype, causal):
    kv = h // groups
    q, k, v = t((b, s, h, d), dtype), t((b, s, kv, d), dtype), t((b, s, kv, d), dtype)
    out = fa_raw(q, k, v, causal=causal, block_q=32, block_k=32, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(
        out.astype(jnp.float32), want.astype(jnp.float32), atol=tol(dtype),
        rtol=tol(dtype))


def test_flash_attention_grad_via_ops():
    q, k, v = t((1, 64, 4, 32)), t((1, 64, 2, 32)), t((1, 64, 2, 32))
    g1 = jax.grad(lambda q: ops.flash_attention(q, k, v, interpret=True).sum())(q)
    g2 = jax.grad(lambda q: ref.flash_attention_ref(q, k, v).sum())(q)
    np.testing.assert_allclose(g1, g2, atol=5e-6, rtol=5e-5)


# -- RG-LRU scan ---------------------------------------------------------------
@pytest.mark.parametrize("b,s,c,bt,bc", [
    (2, 100, 96, 32, 32),
    (1, 257, 64, 64, 64),
    (3, 16, 300, 16, 128),
])
def test_rglru_matches_oracle(b, s, c, bt, bc):
    a = jnp.asarray(RNG.uniform(0.2, 0.999, (b, s, c)), jnp.float32)
    bb = t((b, s, c))
    h0 = t((b, c))
    out = rg_raw(a, bb, h0, block_c=bc, block_t=bt, interpret=True)
    want = ref.rglru_scan_ref(a, bb, h0)
    np.testing.assert_allclose(out, want, atol=1e-5, rtol=1e-5)


@given(
    b=st.integers(1, 3), s=st.sampled_from([1, 33, 128]),
    c=st.sampled_from([8, 130]), h0none=st.booleans(),
)
@settings(max_examples=10, deadline=None)
def test_rglru_hypothesis_sweep(b, s, c, h0none):
    a = jnp.asarray(RNG.uniform(0.0, 1.0, (b, s, c)), jnp.float32)
    bb = t((b, s, c))
    h0 = None if h0none else t((b, c))
    out = rg_raw(a, bb, h0, block_c=64, block_t=64, interpret=True)
    want = ref.rglru_scan_ref(a, bb, h0)
    np.testing.assert_allclose(out, want, atol=1e-5, rtol=1e-5)


# -- RMSNorm ---------------------------------------------------------------------
@pytest.mark.parametrize("shape,dtype", [
    ((4, 37, 128), jnp.bfloat16),
    ((8, 256), jnp.float32),
    ((1, 1, 512), jnp.float32),
])
def test_rmsnorm_matches_oracle(shape, dtype):
    x = t(shape, dtype)
    w = t(shape[-1:])
    out = rn_raw(x, w, interpret=True)
    want = ref.rmsnorm_ref(x, w)
    np.testing.assert_allclose(
        out.astype(jnp.float32), want.astype(jnp.float32),
        atol=tol(dtype), rtol=tol(dtype))


@given(rows=st.integers(1, 70), d=st.sampled_from([32, 128, 384]),
       dtype=st.sampled_from([jnp.float32, jnp.bfloat16]))
@settings(max_examples=10, deadline=None)
def test_rmsnorm_hypothesis_sweep(rows, d, dtype):
    x = t((rows, d), dtype)
    w = t((d,))
    out = rn_raw(x, w, interpret=True)
    want = ref.rmsnorm_ref(x, w)
    np.testing.assert_allclose(
        out.astype(jnp.float32), want.astype(jnp.float32),
        atol=tol(dtype), rtol=tol(dtype))


# -- xla flash (model path) ------------------------------------------------------
def test_xla_flash_fwd_bwd_vs_naive():
    from repro.models.common import flash_attention_xla, naive_attention

    q, k, v = t((2, 100, 4, 32)), t((2, 100, 2, 32)), t((2, 100, 2, 32))
    for win in (0, 16):
        out = flash_attention_xla(q, k, v, causal=True, window=win,
                                  block_q=32, block_k=32)
        want = naive_attention(q, k, v, causal=True, window=win)
        np.testing.assert_allclose(out, want, atol=5e-6, rtol=5e-5)
        gf = jax.grad(lambda a, b, c: flash_attention_xla(
            a, b, c, causal=True, window=win, block_q=32, block_k=32).sum(),
            argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(lambda a, b, c: naive_attention(
            a, b, c, causal=True, window=win).sum(), argnums=(0, 1, 2))(q, k, v)
        for x, y in zip(gf, gr):
            np.testing.assert_allclose(x, y, atol=1e-5, rtol=1e-4)
