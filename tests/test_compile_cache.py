"""Where the entry points keep JAX's persistent compilation cache
(``repro.launch.compile_cache``).  Each case runs in a fresh interpreter:
the cache directory is process-wide JAX state."""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]

_PROBE = """
import json, jax, jax.numpy as jnp
from repro.launch.compile_cache import configure_compile_cache
used = configure_compile_cache()
compile_now = {compile_now}
if compile_now:
    jax.jit(lambda x: jnp.sin(x) @ x.T).lower(jnp.ones((8, 8))).compile()
print(json.dumps({{"used": used,
                  "config": jax.config.jax_compilation_cache_dir}}))
"""


def _probe(env_dir, *, compile_now: bool) -> dict:
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    proc = subprocess.run(
        [sys.executable, "-c", _PROBE.format(compile_now=compile_now)],
        env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("where", ["env", "checkout"])
def test_compile_cache_directory(tmp_path, where):
    """Set: JAX's own variable wins and the compiled program lands there.
    Unset: the fixed ``.jax_cache/`` at the checkout root."""
    if where == "env":
        cache = tmp_path / "cache"
        got = _probe(cache, compile_now=True)
        assert got["used"] == got["config"] == str(cache)
        assert any(cache.iterdir())
    else:
        got = _probe(None, compile_now=False)
        assert got["used"] == got["config"] == str(ROOT / ".jax_cache")


_SCOPED = """
import json, jax, jax.numpy as jnp
from repro.launch.compile_cache import configure_compile_cache
configure_compile_cache()

@jax.jit
def f(x):
    with jax.named_scope({name!r}):
        return jnp.sin(x) @ x.T

print(json.dumps({{"hlo": f.lower(jnp.ones((8, 8))).compile().as_text()}}))
"""


def test_cached_program_keeps_its_own_scope_names(tmp_path):
    """Two programs with the same ops under other scope names: the second
    is not served the first's compile, so its trace names its own phases."""
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"),
               JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    for name in ("qbs.first", "qbs.second"):
        proc = subprocess.run([sys.executable, "-c", _SCOPED.format(name=name)],
                              env=env, capture_output=True, text=True, timeout=300)
        assert proc.returncode == 0, proc.stderr[-4000:]
        hlo = json.loads(proc.stdout.strip().splitlines()[-1])["hlo"]
        assert f"/{name}/" in hlo
    assert any(tmp_path.iterdir())
