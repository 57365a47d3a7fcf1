"""The benchmark's harness: one run of one cell.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is found by name: the cell in ``BENCHMARK.json``,
its configuration in the file that entry names, its traffic in
``bench/traffic/<traffic>.json``, its correctness limits in
``bench/limits/<cell>.json`` and each metric's reader in
``bench/metrics/<metric>.py``.  The configuration file names the rest of
what depends on the architecture: the program's settings it is checked
against (``program.expect``), its plain reference module (``reference``)
and the module that counts its model FLOPs (``flops``).  So a new
architecture enters as new files.

A run builds the program's train step for the cell (``make_train_step``),
initialises its state on the device from the seed, and feeds it through
the program's ``Prefetcher`` with the benchmark's token batches.  Set-up
ends after the first three steps, which compile (or read the compile
cache), warm up and are compared with the plain reference once the window
has closed.  The window then steps the same state for ``--seconds``,
waiting on the host only for a step two behind the newest, and ends with
one ``block_until_ready``.  With ``--trace 1`` the window runs under the
profiler and the run reports the per-layer metrics, among them the
device time of the step's named scopes (``bench/scopes.py``).
"""
from __future__ import annotations

import argparse
import collections
import contextlib
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path

import psutil

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CHECK_STEPS = 3
LAG = 2  # the window waits for the step this many behind the newest


class NoChip(RuntimeError):
    pass


# -- finding things by name --------------------------------------------------------
def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def resolve(workload: str) -> dict:
    """The cell, its configuration, traffic and limits, found by name."""
    bm = _json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bm["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    centry = {c["name"]: c for c in bm["configs"]}[cell["config"]]
    return {
        "benchmark": bm,
        "cell": cell,
        "config": _json(ROOT / centry["file"]),
        "traffic": _json(BENCH / "traffic" / f"{cell['traffic']}.json"),
        "limits": _json(BENCH / "limits" / f"{workload}.json"),
    }


def metrics_for(bm: dict, workload: str, trace: bool) -> list[dict]:
    kind = "per_layer" if trace else "end_to_end"
    return [m for m in bm[kind] if workload in m.get("workloads", [workload])]


def config_module(config: dict, key: str):
    """The module that the configuration file names under ``key``
    (``"reference": "bench/reference.py"``), as a path from the root of
    the checkout."""
    path = Path(config[key])
    if path.is_absolute() or ".." in path.parts or path.suffix != ".py":
        raise ValueError(f"{config['name']}: {key} {config[key]!r} is no module "
                         f"path inside the checkout")
    return importlib.import_module(".".join(path.with_suffix("").parts))


def read_metric(name: str, record: dict):
    """The value the metric's own reader takes from the run's record."""
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name}", BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(record)


# -- the device ----------------------------------------------------------------------
def require_chips(chips: int):
    """The cell's devices and their description; no TPU is an error."""
    import jax

    from bench.peaks import peak

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX runs on {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"{chips} chips wanted, {len(devs)} found")
    peak(devs[0].device_kind)
    return devs[:chips], {"platform": devs[0].platform, "kind": devs[0].device_kind,
                          "count": chips}


def peak_memory(devs) -> int:
    """The process's peak device memory, on the fullest of ``devs``."""
    return max(d.memory_stats()["peak_bytes_in_use"] for d in devs)


# -- the program under test -------------------------------------------------------------
# program attribute <- key of a configuration file: every decoder file has these
DECODER = {"d_model": "hidden_size", "num_heads": "num_attention_heads",
           "num_layers": "num_hidden_layers", "vocab_size": "vocab_size",
           "norm_eps": "rms_norm_eps", "rope_theta": "rope_theta",
           "tie_embeddings": "tie_word_embeddings", "dtype": "compute_dtype",
           "param_dtype": "param_dtype"}
# ... and these are checked where the file states them
STATED = {"d_ff": "intermediate_size", "num_kv_heads": "num_key_value_heads",
          "resolved_head_dim": "head_dim"}


def program_want(config: dict) -> dict:
    """What the program's model configuration must hold for a benchmark
    configuration file: the decoder sizes the file states, and the settings
    of its architecture that the file lists under ``program.expect``."""
    want = {attr: config[key] for attr, key in DECODER.items()}
    want.update({attr: config[key] for attr, key in STATED.items()
                 if config.get(key) is not None})
    return {**want, **config["program"]["expect"]}


def program_config(config: dict):
    """The program's model configuration for a benchmark configuration file,
    checked against ``program_want``; a difference raises."""
    from repro.configs import get_arch

    prog = config["program"]
    cfg = get_arch(prog["arch"]).replace(**prog.get("replace", {}))
    want = program_want(config)
    wrong = {k: (getattr(cfg, k), v) for k, v in want.items() if getattr(cfg, k) != v}
    if wrong:
        raise ValueError(f"program config differs from {config['name']}: {wrong}")
    return cfg


class Cell:
    """The program's train step for one cell, and the readers of its state."""

    def __init__(self, config: dict, traffic: dict, devices):
        from repro.configs import ParallelConfig, TrainConfig
        from repro.launch.mesh import make_mesh
        from repro.models import build_model
        from repro.train.step import gspmd_init_state, make_train_step

        mesh = traffic["mesh"]
        self.mesh = make_mesh((mesh["data"], mesh["model"]), ("data", "model"),
                              devices=devices)
        self.api = build_model(program_config(config))
        self.parallel = ParallelConfig(data=mesh["data"], model=mesh["model"],
                                       dp_sync=traffic["dp_sync"],
                                       chunks_per_collective=traffic["chunks"])
        self.tcfg = TrainConfig(**traffic["train"])
        self.global_batch = traffic["batch_per_chip"] * len(devices)
        self.seq = traffic["seq"]
        self.vocab = config["vocab_size"]
        built = make_train_step(self.api, self.mesh, self.parallel, self.tcfg)
        self.jit_step, self.orders = built[0], None
        if traffic["dp_sync"] == "gspmd":
            self.init = lambda seed: gspmd_init_state(self.api, self.mesh, self.parallel, seed)
        else:
            _, self.init, self.orders = built
        self.compiled = None

    def feed(self, seed: int):
        from repro.data import Prefetcher

        from bench.data import Tokens

        return Prefetcher(Tokens(self.vocab, self.global_batch, self.seq, seed), self.mesh)

    def first_moment(self, opt):
        """The AdamW first moment as the program holds it, on the host."""
        import jax
        import numpy as np

        if self.orders is None:
            return jax.device_get(opt["m"])
        m = opt["m"]
        spec = m.sharding.spec
        return np.asarray(jax.device_get(m)), (spec[1] if len(spec) > 1 else None)

    def grad_norms(self, moment, like: dict) -> dict:
        """Per-leaf norms of the first gradient, from the first moment after
        one step (m1 = (1 - beta1) g)."""
        from bench import check

        if self.orders is None:
            leaves = check.by_path(moment)
        else:
            host, axes = moment
            flat = check.themis_flat(host, self.orders, axes, dict(self.mesh.shape))
            leaves = check.split_flat(flat, like)
        return {k: check.norm(v) / (1 - self.tcfg.beta1) for k, v in leaves.items()}


def first_steps(cell: Cell, seed: int):
    """Initialise from the seed and run the first steps through the
    window's own step and feed.  Returns the state, the feed, and what the
    comparison needs, still raw: losses, weights before and after, first
    moment after step one."""
    import jax

    from bench import check

    params, opt = cell.init(seed)
    pf = cell.feed(seed)
    _, batch = next(pf)
    if cell.compiled is None:
        cell.compiled = cell.jit_step.lower(params, opt, batch).compile()
    before = check.by_path(jax.device_get(params))
    losses, moment = [], None
    for i in range(CHECK_STEPS):
        if i:
            _, batch = next(pf)
        params, opt, out = cell.compiled(params, opt, batch)
        losses.append(out["loss"])
        if i == 0:
            moment = cell.first_moment(opt)
    after = check.by_path(jax.device_get(params))
    raw = {"losses": [float(x) for x in losses], "before": before,
           "after": after, "moment": moment}
    return params, opt, pf, raw


def program_readings(cell: Cell, raw: dict) -> dict:
    from bench import check

    return {"losses": raw["losses"],
            "grad": cell.grad_norms(raw["moment"], raw["before"]),
            "change": check.change_norms(raw["before"], raw["after"])}


def reference_readings(ref, config: dict, traffic: dict, chips: int, seed: int) -> dict:
    from bench import check
    from bench.data import Tokens

    data = Tokens(config["vocab_size"], traffic["batch_per_chip"] * chips,
                  traffic["seq"], seed)
    out = ref.run(seed, [data.batch_at(s) for s in range(CHECK_STEPS)])
    return {"losses": out["losses"],
            "grad": {k: float(v) for k, v in check.by_path(out["grad"]).items()},
            "change": {k: float(v) for k, v in check.by_path(out["change"]).items()}}


def enable_cache() -> str:
    """JAX's persistent compilation cache in its fixed directory, keyed on
    the programs' metadata too: the ``op_name`` of each instruction, which
    the scopes of a traced run are read from, so that a program compiled
    before the scopes were named is never read back.  Traced and untraced
    runs read one executable."""
    import jax

    from repro.launch.cache import enable_compile_cache

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return enable_compile_cache()


# -- the window ---------------------------------------------------------------------------
class CompileCounter:
    """Counts the programs JAX compiles or reads from the persistent cache
    (``n``), and of those the cache reads (``hits``)."""

    def __init__(self):
        import jax

        self.n = self.hits = 0

        def on_duration(event, duration, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.n += 1

        def on_event(event, **kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


def window(step, params, opt, pf, seconds: float):
    """Step back to back for ``seconds``; returns the state, the window's
    losses (on the device) and its length on the host clock."""
    import jax
    from jax.profiler import TraceAnnotation

    losses, pending = [], collections.deque()
    with TraceAnnotation("bench.window"):
        t0 = time.perf_counter()
        while True:
            with TraceAnnotation("bench.next_batch"):
                _, batch = next(pf)
            with TraceAnnotation("bench.dispatch"):
                params, opt, out = step(params, opt, batch)
            losses.append(out["loss"])
            pending.append(out["loss"])
            if len(pending) > LAG:
                with TraceAnnotation("bench.wait"):
                    pending.popleft().block_until_ready()
            if time.perf_counter() - t0 >= seconds:
                break
        with TraceAnnotation("bench.wait"):
            jax.block_until_ready((params, opt))
        t1 = time.perf_counter()
    return params, opt, losses, t1 - t0


@contextlib.contextmanager
def profiled(on: bool):
    """Run the body under the profiler when ``on``; yields a dict that
    holds the trace's neutral record afterwards, with the program's own
    ``data.*`` spans apart under ``"data"``."""
    import jax

    from bench import scopes, trace

    out = {}
    if not on:
        yield out
        return
    tmp = tempfile.mkdtemp(prefix="bench_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    try:
        jax.profiler.start_trace(tmp, profiler_options=opts)
        try:
            yield out
        finally:
            jax.profiler.stop_trace()
        out["record"] = trace.load(tmp)
        out["record"]["data"] = scopes.load_spans(tmp)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def trace_readings(tr: dict, ops_map: dict, steps: int) -> dict:
    """What a traced window adds to the record the metric readers get: the
    trace's reduction (``"trace"``) and the device and host ms per step of
    the step's named scopes, mesh axes and ``data.*`` spans (``"scopes"``)."""
    from bench import scopes, trace

    return {"trace": trace.reduce(tr), "scopes": scopes.per_step_ms(tr, ops_map, steps)}


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def measure(workload: str, seed: int, seconds: float, trace_on: bool) -> tuple[dict, dict]:
    """One run of one cell: its result line, and the record the metrics
    were read from.  A traced run's record also holds the trace's neutral
    record (``"trace_record"``) and the compiled step's map of op names
    (``"ops_map"``, ``bench/scopes.py``)."""
    t_proc = psutil.Process().create_time()
    found = resolve(workload)
    bm, cell_spec, config, traffic = (found[k] for k in
                                      ("benchmark", "cell", "config", "traffic"))
    devs, device = require_chips(cell_spec["chips"])

    import jax

    from repro.comms.schedule_bridge import collective_stats

    from bench import check, peaks, scopes

    reference, flops = config_module(config, "reference"), config_module(config, "flops")
    log(f"[setup] cache dir {enable_cache()}")
    compiles = CompileCounter()
    cell = Cell(config, traffic, devs)
    params, opt, pf, raw = first_steps(cell, seed)
    cost = cell.compiled.cost_analysis()
    hlo = cell.compiled.as_text()
    log(f"[setup] step collectives {collective_stats(hlo)['op_counts']}")
    log(f"[setup] first losses {raw['losses']}; programs compiled or read from the "
        f"cache {compiles.n}, of them cache reads {compiles.hits}")

    n0 = compiles.n
    t_setup = time.time() - t_proc
    with profiled(trace_on) as prof:
        params, opt, losses, window_s = window(cell.compiled, params, opt, pf, seconds)
    log(f"[window] {len(losses)} steps in {window_s} s; programs compiled or read "
        f"from the cache in the window {compiles.n - n0}")
    peak_bytes = peak_memory(devs)
    losses = [float(x) for x in jax.device_get(losses)]
    pf.close()
    del params, opt

    prog = program_readings(cell, raw)
    ref = reference.Reference(config, traffic["train"])
    t_ref = time.perf_counter()
    refr = reference_readings(ref, config, traffic, cell_spec["chips"], seed)
    log(f"[reference] {time.perf_counter() - t_ref} s; losses {refr['losses']}")
    found_nums = check.numbers(prog, refr)
    found_nums["nonfinite_losses"] = sum(not math.isfinite(x) for x in losses)
    correct, checks = check.judge(found_nums, found["limits"])

    record = {
        "steps": len(losses), "tokens_per_step": cell.global_batch * cell.seq,
        "window_s": window_s, "setup_s": t_setup, "chips": cell_spec["chips"],
        "memory_peak_bytes": peak_bytes, "peak": peaks.peak(device["kind"]),
        "model_flops_per_step": flops.train_step_flops(config, cell.global_batch, cell.seq),
        "compiled_bytes_per_step": cost["bytes accessed"],
        "trace": None, "scopes": None,
    }
    if trace_on:
        t_read = time.perf_counter()
        ops_map = scopes.hlo_map(hlo, cell.mesh.devices.shape, cell.mesh.axis_names)
        record.update(trace_readings(prof["record"], ops_map, len(losses)),
                      trace_record=prof["record"], ops_map=ops_map)
        log(f"[trace] read in {time.perf_counter() - t_read} s")
    metrics = {}
    for m in metrics_for(bm, workload, trace_on):
        v = read_metric(m["name"], record)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device["memory_peak_bytes"] = peak_bytes
    result = {"correct": correct, "attempted": len(losses),
              "failed": found_nums["nonfinite_losses"], "metrics": metrics,
              "device": device}
    if trace_on:
        tr = record["trace"]
        device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        result["breakdown"] = {"device_ops": tr["device_ops"], "idle_gaps": tr["idle_gaps"]}
    for k, v in found_nums.items():
        if k not in checks:
            log(f"[info] {k} {v} (not compared)")
    for k, c in checks.items():
        log(f"[check] {k} {c['value']} limit {c['limit']}")
    result["checks"] = checks
    return _finite(result), record


def _finite(x):
    """JSON has no infinity or NaN: such a number is written as null."""
    if isinstance(x, float) and not math.isfinite(x):
        return None
    if isinstance(x, dict):
        return {k: _finite(v) for k, v in x.items()}
    if isinstance(x, list):
        return [_finite(v) for v in x]
    return x


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result, _ = measure(args.workload, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        log(f"[fail] {e}")
        return 2
    print(json.dumps(result), flush=True)
    return 0
