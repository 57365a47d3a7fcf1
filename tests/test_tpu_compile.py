"""Compile the device path for a described TPU v5e, with no chip attached.

The TPU compiler refuses what interpret mode and the CPU backend accept:
unaligned blocks, primitives Mosaic cannot lower, programs that do not fit
HBM.  These tests compile the Pallas kernels at real widths and the Themis
step on one chip and on a 2x2 mesh.  Nothing runs.

Only one process at a time may load the TPU library, so the topology is
described inside a fixture (never at import) and every test that needs it
stays in this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding

from repro.comms.schedule_bridge import collective_stats
from repro.configs import ParallelConfig, TrainConfig, get_arch
from repro.kernels import ops
from repro.launch.mesh import make_mesh
from repro.models import build_model
from repro.sharding.specs import batch_pspec
from repro.train.step import make_themis_train_step


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a program compiled for a described chip is written to the persistent
    # cache but cannot be read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(f, *args):
    compiled = jax.jit(f).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_flash_attention_compiles_at_qwen_heads(one_chip):
    # qwen2.5-3b: 16 query heads, 2 kv heads, head_dim 128; seq 1024
    q = jax.ShapeDtypeStruct((4, 1024, 16, 128), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((4, 1024, 2, 128), jnp.bfloat16, sharding=one_chip)
    _compile(ops.flash_attention, q, kv, kv)


def test_rmsnorm_compiles(one_chip):
    x = jax.ShapeDtypeStruct((4096, 2048), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((2048,), jnp.float32, sharding=one_chip)
    _compile(ops.rmsnorm, x, w)


def test_rglru_scan_compiles_at_recurrentgemma_width(one_chip):
    # recurrentgemma-2b d_rnn = 2560; B > 1 exercises the h0 block
    a = jax.ShapeDtypeStruct((2, 1024, 2560), jnp.float32, sharding=one_chip)
    h0 = jax.ShapeDtypeStruct((2, 2560), jnp.float32, sharding=one_chip)
    _compile(ops.rglru_scan, a, a, h0)


@pytest.mark.parametrize("data,model", [(1, 1), (2, 2)])
def test_themis_step_compiles(topo, data, model):
    mesh = make_mesh((data, model), ("data", "model"),
                     devices=topo.devices[: data * model])
    api = build_model(get_arch("qwen2.5-3b", reduced=True))
    step, init_state, orders = make_themis_train_step(
        api, mesh, ParallelConfig(data=data, model=model, dp_sync="themis"),
        TrainConfig(total_steps=5, warmup_steps=1))
    jax.jit(lambda: init_state(0)).lower().compile()
    params, opt = jax.eval_shape(init_state)
    gb = 4 * data * model
    tok = jax.ShapeDtypeStruct(
        (gb, 64), jnp.int32,
        sharding=NamedSharding(mesh, batch_pspec((gb, 64), mesh, gb)))
    compiled = step.lower(params, opt, {"tokens": tok, "labels": tok}).compile()
    ops_by_kind = collective_stats(compiled.as_text())["op_counts"]
    if data * model == 1:
        assert orders == [()] * len(orders)
        assert ops_by_kind == {}
    else:
        assert {frozenset(o) for o in orders} == {frozenset({"data", "model"})}
        assert ops_by_kind
