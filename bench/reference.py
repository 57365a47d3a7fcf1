"""Plain reference of a cell's first training steps.

A Qwen2-style decoder (RMSNorm, rotary q/k, grouped-query causal attention
with q/k/v biases, SwiGLU MLP, tied or untied LM head; Hugging Face
``Qwen2ForCausalLM``) in float32 ``jax.numpy`` with every matrix product at
``Precision.HIGHEST``, its mean token cross entropy, global-norm gradient
clipping and AdamW with decoupled weight decay and the warm-up/cosine
learning rate.  Its sizes come from the benchmark's configuration file and
its weights from the seed, by the initialisation recipe the benchmark
states (``init_params``).  It imports nothing of the program.

The gradient of a step is accumulated over blocks of rows, so that a cell
whose batch does not fit at float32 runs in pieces.

``variant`` puts a deliberately broken reference in the program's place:
``"fp8"`` computes every matrix product in float8 as FP8 training does
(the control: the next precision below the bfloat16 compute that the
configurations state): the operands of the forward product in e4m3, and
in the backward products the incoming gradient in e5m2, each rounded
under a per-tensor scale;
``"half_batch"`` leaves out the second half of each batch and takes the
mean over the rest.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
VARIANTS = ("fp32", "fp8", "half_batch")  # the reference, the control, planted faults
ROWS_PER_BLOCK = 1  # rows of a batch whose gradient is taken at once


# -- sizes ---------------------------------------------------------------------
def sizes(c: dict) -> dict:
    d, h = c["hidden_size"], c["num_attention_heads"]
    return {"d": d, "f": c["intermediate_size"], "h": h,
            "kv": c["num_key_value_heads"], "hd": c.get("head_dim") or d // h,
            "layers": c["num_hidden_layers"], "vocab": c["vocab_size"],
            "eps": c["rms_norm_eps"], "theta": c["rope_theta"],
            "tied": c["tie_word_embeddings"]}


# -- weights from the seed -------------------------------------------------------
def _dense(key, shape, fan_in):
    return jax.random.normal(key, shape) * (1.0 / math.sqrt(fan_in))


def init_params(seed: int, c: dict):
    """Weights from ``seed``: split the key into layers + 3; layer i splits
    its key into 4 (attention, MLP, two unused), attention into wq, wk, wv,
    wo and the MLP into wi (up), wo (down), wg (gate); matrices are
    N(0, 1/fan_in), the embedding N(0, 0.02^2) from the last key, an untied
    head from the one before it; biases 0, norm weights 1."""
    s = sizes(c)
    d, f, h, kv, hd, n = s["d"], s["f"], s["h"], s["kv"], s["hd"], s["layers"]

    def make(key):
        ks = jax.random.split(key, n + 3)
        blocks = []
        for i in range(n):
            bk = jax.random.split(ks[i], 4)
            ak = jax.random.split(bk[0], 4)
            mk = jax.random.split(bk[1], 3)
            blocks.append({
                "ln1": jnp.ones((d,)),
                "attn": {"wq": _dense(ak[0], (d, h * hd), d),
                         "wk": _dense(ak[1], (d, kv * hd), d),
                         "wv": _dense(ak[2], (d, kv * hd), d),
                         "wo": _dense(ak[3], (h * hd, d), h * hd),
                         "bq": jnp.zeros((h * hd,)),
                         "bk": jnp.zeros((kv * hd,)),
                         "bv": jnp.zeros((kv * hd,))},
                "ln2": jnp.ones((d,)),
                "mlp": {"wi": _dense(mk[0], (d, f), d),
                        "wo": _dense(mk[1], (f, d), f),
                        "wg": _dense(mk[2], (d, f), d)},
            })
        p = {"embed": jax.random.normal(ks[-1], (s["vocab"], d)) * 0.02,
             "blocks": jax.tree.map(lambda *xs: jnp.stack(xs), *blocks),
             "ln_f": jnp.ones((d,))}
        if not s["tied"]:
            p["lm_head"] = _dense(ks[-2], (d, s["vocab"]), d)
        return p

    return jax.jit(make)(jax.random.key(seed))


# -- products --------------------------------------------------------------------
def _fp8(x, dtype=jnp.float8_e4m3fn):
    """x rounded to a float8 type under a per-tensor scale, back in float32."""
    scale = float(jnp.finfo(dtype).max) / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * scale).astype(dtype).astype(jnp.float32) / scale


def _einsum(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _fp8_einsum(spec):
    @jax.custom_vjp
    def f(a, b):
        return _einsum(spec, _fp8(a), _fp8(b))

    def fwd(a, b):
        qa, qb = _fp8(a), _fp8(b)
        return _einsum(spec, qa, qb), (qa, qb)

    def bwd(res, g):
        _, vjp = jax.vjp(partial(_einsum, spec), *res)
        return vjp(_fp8(g, jnp.float8_e5m2))

    f.defvjp(fwd, bwd)
    return f


def _products(fp8: bool):
    if not fp8:
        return _einsum
    cache = {}

    def ein(spec, a, b):
        if spec not in cache:
            cache[spec] = _fp8_einsum(spec)
        return cache[spec](a, b)

    return ein


# -- model -----------------------------------------------------------------------
def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """Rotary embedding on (B, S, H, hd): the two halves of each head rotate
    by position * theta^(-i / (hd/2))."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def logits(p, tokens, c: dict, ein=_einsum):
    s = sizes(c)
    h, kv, hd, eps = s["h"], s["kv"], s["hd"], s["eps"]
    b, t = tokens.shape
    causal = jnp.tril(jnp.ones((t, t), bool))

    def layer(x, lp):
        a = lp["attn"]
        y = _rms(x, lp["ln1"], eps)
        q = (ein("bsd,de->bse", y, a["wq"]) + a["bq"]).reshape(b, t, h, hd)
        k = (ein("bsd,de->bse", y, a["wk"]) + a["bk"]).reshape(b, t, kv, hd)
        v = (ein("bsd,de->bse", y, a["wv"]) + a["bv"]).reshape(b, t, kv, hd)
        q, k = _rope(q, s["theta"]), _rope(k, s["theta"])
        k = jnp.repeat(k, h // kv, axis=2)
        v = jnp.repeat(v, h // kv, axis=2)
        sc = ein("bshd,bthd->bhst", q, k) / math.sqrt(hd)
        pr = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        o = ein("bhst,bthd->bshd", pr, v).reshape(b, t, h * hd)
        x = x + ein("bse,ed->bsd", o, a["wo"])
        m = lp["mlp"]
        y = _rms(x, lp["ln2"], eps)
        u = jax.nn.silu(ein("bsd,df->bsf", y, m["wg"])) * ein("bsd,df->bsf", y, m["wi"])
        return x + ein("bsf,fd->bsd", u, m["wo"]), None

    x, _ = jax.lax.scan(layer, p["embed"][tokens], p["blocks"])
    x = _rms(x, p["ln_f"], eps)
    head = p["embed"].T if s["tied"] else p["lm_head"]
    return ein("bsd,dv->bsv", x, head)


def nll_sum(p, tokens, labels, c, ein=_einsum):
    lg = logits(p, tokens, c, ein)
    lse = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - gold)


# -- optimizer ---------------------------------------------------------------------
def lr_at(t: dict, count: int) -> float:
    """Linear warm-up to the peak, then a cosine from the peak to a tenth
    of it over the rest of ``total_steps``."""
    warm = min(count / max(t["warmup_steps"], 1), 1.0)
    prog = min(max((count - t["warmup_steps"])
                   / max(t["total_steps"] - t["warmup_steps"], 1), 0.0), 1.0)
    return t["learning_rate"] * warm * (0.1 + 0.9 * 0.5 * (1 + math.cos(math.pi * prog)))


def _leaf_norms(tree):
    return jax.tree.map(lambda x: jnp.sqrt(jnp.sum(jnp.square(x))), tree)


def _update(p, m, v, g, *, lr, count, t):
    """Clip g by its global norm, then one AdamW step; returns the state
    and the per-leaf norms of the clipped gradient."""
    gn = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(g)))
    g = jax.tree.map(lambda x: x * jnp.minimum(1.0, t["grad_clip"] / jnp.maximum(gn, 1e-9)), g)
    b1, b2 = t["beta1"], t["beta2"]
    c1, c2 = 1 - b1 ** count, 1 - b2 ** count
    m = jax.tree.map(lambda a, x: b1 * a + (1 - b1) * x, m, g)
    v = jax.tree.map(lambda a, x: b2 * a + (1 - b2) * x * x, v, g)
    p = jax.tree.map(
        lambda w, a, b: w - lr * ((a / c1) / (jnp.sqrt(b / c2) + t["eps"])
                                  + t["weight_decay"] * w), p, m, v)
    return p, m, v, _leaf_norms(g)


class Reference:
    """The first steps of a cell, computed plainly.  Build once per process
    and call ``run`` per seed: the compiled pieces are shared."""

    def __init__(self, c: dict, train: dict, variant: str = "fp32"):
        if variant not in VARIANTS:
            raise ValueError(f"unknown reference variant {variant!r}")
        self.c, self.t, self.variant = c, train, variant
        ein = _products(variant == "fp8")

        def acc_grad(p, acc, tokens, labels, inv_n):
            loss, g = jax.value_and_grad(nll_sum)(p, tokens, labels, c, ein)
            return loss * inv_n, jax.tree.map(lambda a, x: a + x * inv_n, acc, g)

        self._acc_grad = jax.jit(acc_grad, donate_argnums=(1,))
        self._update = jax.jit(partial(_update, t=train), donate_argnums=(0, 1, 2))
        self._change = jax.jit(lambda a, b: _leaf_norms(jax.tree.map(jnp.subtract, a, b)))

    def run(self, seed: int, batches: list[dict]) -> dict:
        """Steps over ``batches`` (host arrays) from the weights of ``seed``.

        Returns the loss of each step, the per-leaf norms of the first
        step's clipped gradient, and the per-leaf norms of the change of
        the weights over all the steps."""
        p = init_params(seed, self.c)
        m = jax.tree.map(jnp.zeros_like, p)
        v = jax.tree.map(jnp.zeros_like, p)
        losses, g_norms = [], None
        for count, batch in enumerate(batches, start=1):
            toks, labs = batch["tokens"], batch["labels"]
            if self.variant == "half_batch":
                toks, labs = toks[: len(toks) // 2], labs[: len(labs) // 2]
            inv_n = np.float32(1.0 / toks.size)
            acc = jax.tree.map(jnp.zeros_like, p)
            loss = 0.0
            for r in range(0, len(toks), ROWS_PER_BLOCK):
                part, acc = self._acc_grad(p, acc, toks[r:r + ROWS_PER_BLOCK],
                                           labs[r:r + ROWS_PER_BLOCK], inv_n)
                loss += float(part)
            losses.append(loss)
            p, m, v, gn = self._update(p, m, v, acc, lr=np.float32(lr_at(self.t, count)),
                                       count=np.float32(count))
            if g_norms is None:
                g_norms = jax.device_get(gn)
        del m, v
        change = jax.device_get(self._change(p, init_params(seed, self.c)))
        return {"losses": losses, "grad": g_norms, "change": change}
