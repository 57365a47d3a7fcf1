"""GPipe-style pipeline parallelism over a mesh axis (optional feature).

Stage s holds its slice of the layer stack; microbatches stream through
``collective_permute`` boundary transfers inside a ``shard_map`` manual
over the "pipe" axis.  Autodiff flows through the permutes (their transpose
is the reversed permute), giving 1F1B-equivalent semantics under XLA's
scheduler.  Demonstrated on the dense decoder family; intended for
cross-pod pipelining where DCN latency would dominate an FSDP/TP layout.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import transformer as tr
from repro.models.common import cross_entropy, rms_norm


def pipeline_forward(params, tokens, cfg: ModelConfig, *, n_micro: int,
                     axis: str = "pipe"):
    """Runs inside shard_map manual over ``axis``.

    params: this stage's slice — blocks (L/S, ...) plus embed/head
    (replicated; stage 0 embeds, last stage projects logits).
    tokens: (B, S) local copy (replicated over the pipe axis).
    Returns per-token logits computed on the last stage (other stages
    return zeros — the loss is psum'd over the axis).
    """
    stage = jax.lax.axis_index(axis)
    n_stage = jax.lax.axis_size(axis)
    dt = jnp.dtype(cfg.dtype)
    b, s = tokens.shape
    assert b % n_micro == 0
    mb = b // n_micro
    positions = jnp.arange(s)

    def run_stage(x_in, mtokens):
        h = jnp.where(stage == 0,
                      params["embed"].astype(dt)[mtokens], x_in)

        def body(carry, p_l):
            out, _ = tr.apply_block(p_l, carry, cfg, positions=positions)
            return out, None

        h, _ = jax.lax.scan(body, h, params["blocks"])
        return h

    # microbatch loop: ring-advance activations stage->stage+1
    micro = tokens.reshape(n_micro, mb, s)
    perm = [(i, (i + 1) % n_stage) for i in range(n_stage)]

    def step(carry, mtok):
        x_prev = carry                      # activation arriving from stage-1
        h = run_stage(x_prev, mtok)
        x_next = jax.lax.ppermute(h, axis, perm)
        return x_next, h

    x0 = jnp.zeros((mb, s, cfg.d_model), dt)
    # n_stage warmup cycles: every microbatch must traverse all stages.
    outs = []
    carry = x0
    for m in range(n_micro + n_stage - 1):
        mtok = micro[jnp.minimum(m, n_micro - 1)]
        carry, h = step(carry, mtok)
        outs.append(h)
    # last-stage outputs for microbatch m appear at cycle m + n_stage - 1
    hs = jnp.stack(outs[n_stage - 1:])       # (n_micro, mb, s, D)
    hs = hs.reshape(b, s, cfg.d_model)
    x = rms_norm(hs, params["ln_f"].astype(dt), cfg.norm_eps)
    logits = x @ params["lm_head"].astype(dt)
    return logits


def make_pipeline_loss(cfg: ModelConfig, mesh: Mesh, n_micro: int,
                       axis: str = "pipe"):
    """(stage_params, tokens, labels) -> scalar loss; shard_map'd."""

    def loss_shard(params, tokens, labels):
        n_stage = jax.lax.axis_size(axis)
        stage = jax.lax.axis_index(axis)
        logits = pipeline_forward(params, tokens, cfg, n_micro=n_micro,
                                  axis=axis)
        l = cross_entropy(logits, labels)
        # only the last stage's logits are meaningful
        l = jnp.where(stage == n_stage - 1, l, 0.0)
        return jax.lax.psum(l, axis)

    return jax.shard_map(
        loss_shard, mesh=mesh,
        in_specs=({"embed": P(), "blocks": P(axis), "ln_f": P(),
                   "lm_head": P()}, P(), P()),
        out_specs=P(),
        check_vma=False,
    )


def stage_split_params(params, n_stage: int):
    """Split a full LM param tree into per-stage stacked block slices."""
    blocks = params["blocks"]
    total = jax.tree.leaves(blocks)[0].shape[0]
    assert total % n_stage == 0
    return {
        "embed": params["embed"],
        "blocks": blocks,          # sharded over the pipe axis by in_specs
        "ln_f": params["ln_f"],
        "lm_head": params.get("lm_head", params["embed"].T),
    }
