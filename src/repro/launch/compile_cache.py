"""Where the entry points keep JAX's persistent compilation cache.

JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself; where it is set, that
directory is used and no other.  Where it is not, the cache goes to a
fixed ``.jax_cache/`` at the checkout root: the path is part of the cache
key, so a directory that moved between runs would never hit.

The programs' HLO metadata is part of the key too.  JAX leaves it out by
default, so a program compiled from other source with the same ops (a
checkout without the profiler's scope names, ``repro.tracing``) would be
served in place of this one, and a trace of it would name no phase.

A function the entry points call, never an import-time side effect.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_ROOT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT_ROOT / ".jax_cache"


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory, with
    the programs' metadata in its key; returns the path in use."""
    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
