"""Where the launchers keep JAX's persistent compilation cache."""
import os
import subprocess
import sys
from pathlib import Path

import jax

from repro.launch import cache

HERE = os.path.dirname(__file__)

# One compile with the cache on; prints its hits and misses.  The minimum
# compile time is lowered only here, so that a CPU-sized program is cached.
PROGRAM = """
import jax, jax.numpy as jnp
from repro.launch.cache import enable_compile_cache
seen = {"hits": 0, "misses": 0}
def on_event(event, **kw):
    for k in seen:
        if event == "/jax/compilation_cache/cache_" + k:
            seen[k] += 1
jax.monitoring.register_event_listener(on_event)
print("dir", enable_compile_cache())
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.jit(lambda x: jnp.sin(x) @ x.T).lower(jnp.ones((64, 64))).compile()
print("hits", seen["hits"], "misses", seen["misses"])
"""


def test_checkout_path_is_fixed_and_used_without_env(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        path = cache.enable_compile_cache()
        assert path == str(cache.CHECKOUT_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
    assert cache.CHECKOUT_CACHE_DIR == Path(HERE).resolve().parent / ".jax_cache"


def test_env_dir_is_written_then_read_by_a_second_run(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jc"),
               PYTHONPATH=os.path.join(os.path.dirname(HERE), "src"))
    env.pop("XLA_FLAGS", None)
    runs = [subprocess.run([sys.executable, "-c", PROGRAM], env=env,
                           capture_output=True, text=True, timeout=300)
            for _ in range(2)]
    for r in runs:
        assert r.returncode == 0, r.stderr[-2000:]
    first, second = (r.stdout.split() for r in runs)
    assert first[:2] == ["dir", str(tmp_path / "jc")]
    assert first[-4] == "hits" and first[-3] == "0" and int(first[-1]) >= 1
    # every program the first run compiled, the second reads back
    assert second[-4:] == ["hits", first[-1], "misses", "0"]
    assert os.listdir(tmp_path / "jc")
