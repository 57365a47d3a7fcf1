"""The step's phases and the input pipeline are named for a profile.

``train/step.py`` wraps each phase in ``jax.named_scope``; the names reach
the ``op_name`` metadata of the HLO, where a profile of the device reads
them.  ``data/pipeline.py`` marks the production of each batch with a
``data.produce`` trace span on the host.
"""
import glob
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding

from repro.configs import ParallelConfig, TrainConfig, get_arch
from repro.data import Prefetcher, SyntheticLM
from repro.launch.mesh import make_mesh
from repro.models import build_model
from repro.sharding.specs import batch_pspec
from repro.train.step import gspmd_init_state, make_train_step

STEP = {"jvp(forward)", "transpose(jvp(forward))", "optimizer"}
THEMIS = {"themis_flatten", "themis_rs", "themis_ag", "themis_unravel"}


def _scope_names(text: str) -> set[str]:
    """Every path component of every ``op_name`` in an HLO text."""
    return {part for op in re.findall(r'op_name="([^"]*)"', text)
            for part in re.split(r"[/;]", op)}


def _lowered_step(dp_sync: str):
    mesh = make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    api = build_model(get_arch("qwen2.5-3b", reduced=True))
    parallel = ParallelConfig(data=1, model=1, dp_sync=dp_sync)
    built = make_train_step(api, mesh, parallel, TrainConfig(total_steps=5, warmup_steps=1))
    if dp_sync == "gspmd":
        params, opt = jax.eval_shape(lambda: gspmd_init_state(api, mesh, parallel, 0))
    else:
        params, opt = jax.eval_shape(built[1])
    tok = jax.ShapeDtypeStruct((4, 32), jnp.int32,
                               sharding=NamedSharding(mesh, batch_pspec((4, 32), mesh, 4)))
    return built[0].lower(params, opt, {"tokens": tok, "labels": tok})


@pytest.mark.parametrize("dp_sync", ["themis", "gspmd"])
def test_step_phases_reach_the_compiled_hlo(dp_sync):
    lowered = _lowered_step(dp_sync)
    compiled = _scope_names(lowered.compile().as_text())
    assert STEP <= compiled
    if dp_sync == "gspmd":
        assert not THEMIS & compiled
        return
    # On one device the all-gather phase compiles to nothing: the
    # parameters are fp32, so the cast is the identity, and there is no
    # exchange.  Its ops are named before the compiler removes them.
    assert THEMIS - {"themis_ag"} <= compiled
    assert THEMIS <= _scope_names(lowered.as_text(dialect="hlo", debug_info=True))


def test_prefetcher_marks_each_batch_it_produces(tmp_path):
    from jax.profiler import ProfileData

    mesh = make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    jax.profiler.start_trace(str(tmp_path))
    try:
        pf = Prefetcher(SyntheticLM(64, global_batch=2, seq_len=8, seed=1), mesh)
        for _ in range(3):
            next(pf)
        pf.close()
    finally:
        jax.profiler.stop_trace()
    [path] = glob.glob(os.path.join(tmp_path, "plugins", "profile", "*", "*.xplane.pb"))
    spans = [e for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events if e.name == "data.produce"]
    assert len(spans) >= 3
    assert all(e.duration_ns > 0 for e in spans)
