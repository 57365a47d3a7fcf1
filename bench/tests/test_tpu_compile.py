"""Compile every cell's step, and its reference, at real size for a described
TPU v5e (one chip, or the 2x2 mesh), with no chip attached.

The TPU compiler refuses a program that does not fit the chip's memory, so
this shows before any chip time is spent that each configuration's cut
fits.  Each test prints the step's ``memory_analysis`` and the compiler's
count of bytes accessed; the configuration files record what these read
when the cells were set.  Nothing runs.

Only one process at a time may load the TPU library, so the topology is
described inside a fixture, never at import, and every test that needs it
stays in this file.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding

from bench import harness as H

HBM = 15.75 * 2**30  # what the compiler lets a program use of a v5e chip
CELLS = [w["name"] for w in json.loads((H.ROOT / "BENCHMARK.json").read_text())["workloads"]]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
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


def _used(ma) -> int:
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)


@pytest.mark.parametrize("workload", CELLS)
def test_step_fits_the_chip(topo, workload):
    found = H.resolve(workload)
    chips = found["cell"]["chips"]
    cell = H.Cell(found["config"], found["traffic"], topo.devices[:chips])
    params, opt = jax.eval_shape(lambda: cell.init(0))
    shape = (cell.global_batch, cell.seq)
    from repro.sharding.specs import batch_pspec

    tok = jax.ShapeDtypeStruct(shape, jnp.int32, sharding=NamedSharding(
        cell.mesh, batch_pspec(shape, cell.mesh, cell.global_batch)))
    compiled = cell.jit_step.lower(params, opt, {"tokens": tok, "labels": tok}).compile()
    ma = compiled.memory_analysis()
    print(f"{workload}: arguments {ma.argument_size_in_bytes} outputs "
          f"{ma.output_size_in_bytes} aliased {ma.alias_size_in_bytes} temporaries "
          f"{ma.temp_size_in_bytes} bytes; bytes accessed "
          f"{compiled.cost_analysis()['bytes accessed']}")
    assert _used(ma) < HBM


@pytest.mark.parametrize("workload", CELLS)
def test_reference_fits_one_chip(topo, workload):
    found = H.resolve(workload)
    config, traffic = found["config"], found["traffic"]
    one = SingleDeviceSharding(topo.devices[0])
    reference = H.config_module(config, "reference")
    ref = reference.Reference(config, traffic["train"])
    p = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
                     jax.eval_shape(lambda: reference.init_params(0, config)))
    rows = jax.ShapeDtypeStruct((reference.ROWS_PER_BLOCK, traffic["seq"]),
                                jnp.int32, sharding=one)
    scalar = jax.ShapeDtypeStruct((), jnp.float32, sharding=one)
    grad = ref._acc_grad.lower(p, p, rows, rows, scalar).compile().memory_analysis()
    upd = ref._update.lower(p, p, p, p, lr=scalar, count=scalar).compile().memory_analysis()
    state = 4 * sum(int(np.prod(x.shape)) * 4 for x in jax.tree.leaves(p))
    print(f"{workload} reference: gradient block temporaries {grad.temp_size_in_bytes}, "
          f"update temporaries {upd.temp_size_in_bytes}, state {state} bytes")
    # weights, m, v and the gradient sum live through the step
    assert state + grad.temp_size_in_bytes < HBM
    assert _used(upd) < HBM
