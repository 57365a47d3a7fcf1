"""Compile the device path for a described TPU v5e, with no chip attached.

The TPU compiler refuses what interpret mode and the CPU backend accept:
unaligned blocks, primitives Mosaic cannot lower, programs that do not fit
HBM.  These tests compile the Pallas kernels at real widths and the Themis
step on one chip and on a 2x2 mesh, and check the names the step's ops
carry for a profile (``bench/scopes.py`` reads them).  Nothing runs.

Only one process at a time may load the TPU library, so the topology is
described inside a fixture (never at import) and every test that needs it
stays in this file.
"""
import os
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding

from repro.comms.schedule_bridge import collective_stats
from repro.configs import ParallelConfig, TrainConfig, get_arch
from repro.kernels import ops
from repro.launch.mesh import make_mesh
from repro.models import build_model
from repro.models.registry import count_params
from repro.sharding.specs import batch_pspec
from repro.train.step import make_themis_train_step

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from bench import scopes  # noqa: E402


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


def _result_shapes(line: str) -> list[set[int]]:
    """The dimensions of each result shape of an HLO instruction (several
    where the compiler combined small collectives into one tuple)."""
    shape = re.match(r"\s*(?:ROOT )?%?[\w.-]+ = (.*?) [\w-]+\(", line)
    return [{int(d) for d in dims.split(",") if d}
            for dims in re.findall(r"\[([\d,]*)\]", shape.group(1))] if shape else []


@pytest.mark.parametrize("data,model", [(1, 1), (2, 2)])
def test_themis_step_has_no_layout_loop(topo, data, model):
    """The flat gradient becomes the chunk buffer, and the gathered chunks
    the parameters, with no ``while`` loop over either: each device's shard
    of a chunk is whole (8, 128) f32 tiles.  On 2x2 every all-gather hop
    of a chunk is an all-gather (a reduce-scatter hop is still an
    all-reduce and a slice): one of each per chunk and axis.

    The vocabulary is widened to 65536 (4.3M parameters): below a few
    million elements the compiler copies even an unaligned layout without
    a loop, and a chunk that holds only padding is folded away."""
    mesh = make_mesh((data, model), ("data", "model"),
                     devices=topo.devices[: data * model])
    api = build_model(get_arch("qwen2.5-3b", reduced=True).replace(vocab_size=65536))
    step, init_state, orders = make_themis_train_step(
        api, mesh, ParallelConfig(data=data, model=model, dp_sync="themis"),
        TrainConfig(total_steps=5, warmup_steps=1))
    params, opt = jax.eval_shape(init_state)
    n_chunks, per_chunk = opt["m"].shape
    assert n_chunks == 16
    n_params = count_params(api.param_spec())
    assert (n_chunks - 1) * per_chunk < n_params
    gb = 4 * data * model
    tok = jax.ShapeDtypeStruct(
        (gb, 64), jnp.int32,
        sharding=NamedSharding(mesh, batch_pspec((gb, 64), mesh, gb)))
    text = step.lower(params, opt, {"tokens": tok, "labels": tok}).compile().as_text()
    flat = {n_params, n_chunks * per_chunk, per_chunk}
    loops = [line for line in text.splitlines() if re.search(r" while\(", line)]
    assert loops  # the model's own loops: the layer scan, attention
    for line in loops:
        assert not any(dims & flat for dims in _result_shapes(line)), line[:300]
    hops = {"all-gather": 0, "all-reduce": 0}
    for line in text.splitlines():
        kind = re.search(r" (all-gather|all-reduce)(-start)?\(", line)
        if kind:
            hops[kind.group(1)] += sum(bool(dims & {per_chunk, per_chunk // 2})
                                       for dims in _result_shapes(line))
    hops_per_kind = n_chunks * sum(n > 1 for n in (data, model))
    assert hops == {"all-gather": hops_per_kind, "all-reduce": hops_per_kind}


@pytest.fixture(scope="module")
def themis_2x2(topo):
    """The reduced qwen2.5-3b Themis step lowered for the 2x2 mesh, its mesh,
    its chunk orders and its flat-vector sizes."""
    mesh = make_mesh((2, 2), ("data", "model"), devices=topo.devices[:4])
    api = build_model(get_arch("qwen2.5-3b", reduced=True))
    step, init_state, orders = make_themis_train_step(
        api, mesh, ParallelConfig(data=2, model=2, dp_sync="themis"),
        TrainConfig(total_steps=5, warmup_steps=1))
    params, opt = jax.eval_shape(init_state)
    tok = jax.ShapeDtypeStruct(
        (16, 64), jnp.int32, sharding=NamedSharding(mesh, batch_pspec((16, 64), mesh, 16)))
    lowered = step.lower(params, opt, {"tokens": tok, "labels": tok})
    return lowered, mesh, orders, count_params(api.param_spec()), opt["m"].shape[1]


def test_themis_hops_name_their_axis(themis_2x2):
    """Each chunk's reduce-scatter and all-gather hop runs under
    ``rs_<axis>`` / ``ag_<axis>``, and that axis is the one its replica
    groups span; the hops follow the chunk orders."""
    lowered, mesh, orders, _, _ = themis_2x2
    text = lowered.as_text(dialect="hlo", debug_info=True)
    hops = {"rs": [], "ag": []}
    for line in text.splitlines():
        kind = re.search(r" (reduce-scatter|all-gather)\(", line)
        if not kind:
            continue
        ax = scopes.axes_spanned(scopes.replica_groups(line), mesh.devices.shape,
                                 mesh.axis_names)
        scope = [p for p in scopes.OP_NAME.search(line).group(1).split("/")
                 if re.fullmatch(r"(rs|ag)_\w+", p)]
        prefix = "rs" if kind.group(1) == "reduce-scatter" else "ag"
        assert len(ax) == 1 and scope == [f"{prefix}_{ax[0]}"], line[:300]
        hops[prefix].append(ax[0])
    assert hops["rs"] == [a for o in orders for a in o]
    assert hops["ag"] == [a for o in orders for a in reversed(o)]


def test_themis_flat_path_lies_under_step_scopes(themis_2x2):
    """In the compiled step, every instruction over the flat gradient, the
    chunk buffer or a chunk's shards belongs to a named phase, also where
    the compiler made it and dropped its metadata; each collective spans
    one mesh axis, and the chunk collectives are the RS and AG phases'."""
    lowered, mesh, orders, n_params, per_chunk = themis_2x2
    text = lowered.compile().as_text()
    names = scopes.hlo_map(text, mesh.devices.shape, mesh.axis_names)
    flat = {n_params, len(orders) * per_chunk, per_chunk, per_chunk // 2, per_chunk // 4}
    seen = 0
    for line in text.splitlines():
        m = scopes.INSTR.match(line)
        shape = re.match(r"\s*(?:ROOT )?%?[\w.-]+ = \(?\w+\[([\d,]*)\]", line)
        if not (m and shape) or re.search(r" (parameter|get-tuple-element|constant)\(", line):
            continue
        dims = {int(d) for d in shape.group(1).split(",") if d}
        if dims & flat:
            seen += 1
            assert scopes.phase_of(names[m.group(1)][0]) != "unscoped", line[:300]
    assert seen
    chunk_phases = set()
    for name, (op, axes) in names.items():
        if re.match(r"(all-reduce|all-gather|reduce-scatter)(-start)?(\.\d+)?$", name):
            assert len(axes) == 1, name
            chunk_phases.add(scopes.phase_of(op))
    assert {"themis_rs", "themis_ag"} <= chunk_phases
