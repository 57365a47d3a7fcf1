"""The geometry of the Themis chunk buffer.

The gradient, ``n`` elements in ``ravel_pytree`` order, is held as
``(chunks, per_chunk)``, where ``per_chunk`` is a multiple of ``world * 1024`` so that every
device's shard of a chunk is whole f32 tiles; row ``c`` is segment ``c``
of the ``ravel_pytree`` order, and the padding lies at the tail alone.
``bench/check.py`` reads the optimizer state through that layout.
"""
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.flatten_util import ravel_pytree

from repro.comms.hierarchical import TILE, chunk_len, join_chunks, split_chunks
from repro.configs import ParallelConfig, TrainConfig, get_arch
from repro.launch.mesh import make_mesh
from repro.models import build_model
from repro.models.registry import count_params
from repro.train.step import make_themis_train_step

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from bench import check  # noqa: E402


@pytest.mark.parametrize("n,chunks,world", [
    (1, 1, 1), (1024, 1, 1), (1025, 1, 1), (542397952, 16, 1),
    (542397952, 16, 4), (469915648, 16, 1), (12345, 4, 8), (4096 * 16, 16, 4),
])
def test_chunk_len_is_whole_tiles_per_shard(n, chunks, world):
    per = chunk_len(n, chunks, world)
    assert per % (world * TILE) == 0
    assert chunks * per >= n
    # the padding is less than one tile per shard of each chunk
    assert chunks * per - n < chunks * world * TILE


@pytest.mark.parametrize("sizes,chunks,per", [
    ([10], 4, 3), ([12], 4, 3), ([5000], 3, 2048),
    ([1, 7, 2, 9], 5, 4), ([3, 0, 8], 2, 8), ([4096, 1, 4095], 2, 4096),
])
def test_split_chunks_lays_parts_end_to_end_padded_at_the_tail(sizes, chunks, per):
    parts = []
    at = 1
    for n in sizes:
        parts.append(jnp.arange(at, at + n, dtype=jnp.float32))
        at += n
    n = at - 1
    rows = np.asarray(split_chunks(parts, chunks, per))
    assert rows.shape == (chunks, per)
    np.testing.assert_array_equal(rows.reshape(-1)[:n], np.arange(1, n + 1))
    assert not rows.reshape(-1)[n:].any()
    back = join_chunks(jnp.asarray(rows), sizes)
    for got, want in zip(back, parts):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _tiny():
    cfg = get_arch("qwen2.5-3b", reduced=True).replace(remat=False)
    tcfg = TrainConfig(learning_rate=1e-3, warmup_steps=1, total_steps=10)
    rng = np.random.default_rng(0)
    tok = rng.integers(0, cfg.vocab_size, (4, 17), dtype=np.int32)
    return build_model(cfg), tcfg, {"tokens": jnp.asarray(tok[:, :-1]),
                                    "labels": jnp.asarray(tok[:, 1:])}


def expected_first_moment(api, params, batch, tcfg) -> dict:
    """(1 - beta1) times the clipped gradient, per leaf path: what AdamW's
    first moment holds after one step, whatever layout holds it."""
    g = jax.tree.map(lambda x: np.asarray(x, np.float64),
                     jax.grad(api.loss_fn)(params, batch))
    norm = math.sqrt(sum(float(np.sum(x * x)) for x in jax.tree.leaves(g)))
    scale = min(1.0, tcfg.grad_clip / max(norm, 1e-9))
    return {k: (1 - tcfg.beta1) * scale * v for k, v in check.by_path(g).items()}


def test_one_step_state_reads_back_per_leaf_on_one_device():
    api, tcfg, batch = _tiny()
    mesh = make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    step, init_state, orders = make_themis_train_step(
        api, mesh, ParallelConfig(data=1, model=1, dp_sync="themis",
                                  chunks_per_collective=4), tcfg)
    params, opt = init_state(0)
    n = count_params(api.param_spec())
    n_chunks, per_chunk = opt["m"].shape
    assert n_chunks == 4 and per_chunk % TILE == 0 and n_chunks * per_chunk >= n
    # the fp32 master copy is the raveled parameters, chunked, padded at the tail
    master = np.asarray(opt["master"]).reshape(-1)
    np.testing.assert_array_equal(master[:n], np.asarray(ravel_pytree(params)[0]))
    assert not master[n:].any()

    want = expected_first_moment(api, params, batch, tcfg)
    like = check.by_path(params)
    new_params, opt, _ = step(params, opt, batch)
    # the parameters are the updated master copy, read back leaf by leaf
    np.testing.assert_array_equal(np.asarray(ravel_pytree(new_params)[0]),
                                  np.asarray(opt["master"]).reshape(-1)[:n])
    m = np.asarray(opt["m"])
    assert not m.reshape(-1)[n:].any()
    got = check.split_flat(check.themis_flat(m, orders, None, dict(mesh.shape)), like)
    assert got.keys() == want.keys()
    for k, w in want.items():
        # the same arithmetic in another summation order: f32 round-off
        scale = max(check.norm(w), 1e-12)
        assert check.norm(got[k] - w.reshape(-1)) <= 1e-4 * scale, k
