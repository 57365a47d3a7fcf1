"""Helpers of the benchmark's tests: a copy of the benchmark at a tiny size,
run on the CPU in a child process.

``tiny_copy`` copies ``BENCHMARK.json`` and ``bench/`` into a directory and
cuts every configuration to a few small layers and every traffic mix to a
small batch; names stay as the cells name them, and a head dimension that
the file expects of the program follows the tiny widths.  ``run_cell``
runs one cell of such a copy through ``bench.harness.main`` in a child
process on the CPU, with as many virtual devices as the cell asks for.
The child skips the harness's look for a chip (it reports the CPU as the
device, with a made-up peak) and may plant a fault first: ``FAULTS`` maps
each fault's name to the code that plants it.
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]

TINY = {"hidden_size": 128, "intermediate_size": 256, "num_attention_heads": 4,
        "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 512}
PROGRAM_KEYS = {"hidden_size": "d_model", "intermediate_size": "d_ff",
                "num_attention_heads": "num_heads", "num_key_value_heads": "num_kv_heads",
                "num_hidden_layers": "num_layers", "vocab_size": "vocab_size"}
TINY_TRAFFIC = {"batch_per_chip": 2, "seq": 64, "chunks": 4}
# Limits of the tiny copy on the CPU, set as the cells' limits are (PERF.md)
# from 16 sound runs (grad_gap at most 1.62e-3, change_gap 6.1e-3), the
# float8 control on 8 seeds (grad_gap at least 7.19e-3) and a step that
# leaves its state unchanged (1.0): lower^(1/3) * upper^(2/3).  At this
# size loss_gap does not separate (sound up to 4.8e-4, control from 3.6e-4)
# and is not compared.
TINY_LIMITS = {"grad_gap": 4.4e-3, "change_gap": 0.18, "nonfinite_losses": 0}

FAULTS = {
    # the step returns its state unchanged
    "unchanged": """
        import jax, repro.train.step as S
        make = S.make_train_step
        def broken(*a, **k):
            built = make(*a, **k)
            inner = built[0]
            step = jax.jit(lambda p, o, b: (p, o, inner(p, o, b)[2]))
            return (step,) + tuple(built[1:])
        S.make_train_step = broken
    """,
    # half of the batch left out, the mean taken over the rest
    "half_batch": """
        import jax, repro.models as M
        build = M.build_model
        def broken(cfg):
            api = build(cfg)
            loss = api.loss_fn
            def half(params, batch):
                n = batch["tokens"].shape[0] // 2
                return loss(params, jax.tree.map(lambda x: x[:n], batch))
            api.loss_fn = half
            return api
        M.build_model = broken
    """,
    # the exchange between chips left out: each device keeps its own shard
    # of its own gradient in place of the reduce-scatter
    "no_exchange": """
        import repro.train.step as S
        from bench.calibrate import local_reduce_scatter
        S.chunked_reduce_scatter = local_reduce_scatter
    """,
}


def tiny_copy(dest: Path, limits: dict | None = None) -> Path:
    """A copy of the benchmark at a tiny size under ``dest``."""
    dest.mkdir(parents=True, exist_ok=True)
    shutil.copy(REPO / "BENCHMARK.json", dest / "BENCHMARK.json")
    shutil.copytree(REPO / "bench", dest / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    for path in (dest / "bench" / "configs").glob("*.json"):
        c = json.loads(path.read_text())
        c.update(TINY)
        c["program"]["replace"].update({PROGRAM_KEYS[k]: v for k, v in TINY.items()})
        if "resolved_head_dim" in c["program"]["expect"]:
            c["program"]["expect"]["resolved_head_dim"] = (
                TINY["hidden_size"] // TINY["num_attention_heads"])
        path.write_text(json.dumps(c))
    for path in (dest / "bench" / "traffic").glob("*.json"):
        t = json.loads(path.read_text())
        t.update(TINY_TRAFFIC)
        path.write_text(json.dumps(t))
    for path in (dest / "bench" / "limits").glob("*.json"):
        path.write_text(json.dumps(limits or TINY_LIMITS))
    return dest


CHILD = """
import sys
sys.path[:0] = [{root!r}, {src!r}]
{fault}
import jax
import bench.harness as H
import bench.peaks as P
P.PEAKS["cpu"] = {{"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}
def cpu_chips(chips):
    return jax.devices()[:chips], {{"platform": "cpu", "kind": "cpu", "count": chips}}
H.require_chips = cpu_chips
H.peak_memory = lambda devs: 0
sys.exit(H.main({argv!r}))
"""


def child_env(root: Path, devices: int) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(root / ".jax_cache"),
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    env.pop("PYTHONPATH", None)
    return env


def run_cell(root: Path, workload: str, *, seed: int = 7, seconds: float = 1.0,
             trace: int = 0, fault: str | None = None, devices: int = 4,
             timeout: float = 600) -> subprocess.CompletedProcess:
    argv = ["--workload", workload, "--seed", str(seed), "--seconds", str(seconds),
            "--trace", str(trace)]
    code = CHILD.format(root=str(root), src=str(REPO / "src"), argv=argv,
                        fault=textwrap.dedent(FAULTS[fault]) if fault else "")
    return subprocess.run([sys.executable, "-c", code], cwd=root, env=child_env(root, devices),
                          capture_output=True, text=True, timeout=timeout)


def result_of(proc: subprocess.CompletedProcess) -> dict:
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])
