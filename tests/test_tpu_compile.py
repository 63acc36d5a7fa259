"""Compile the Pallas kernels of the serving path for a TPU v5e chip that is
described, not attached.

The TPU compiler ships with libtpu, so these compiles run on any host that
has it installed: a kernel Mosaic refuses (an unaligned slice, a cast it
cannot lower, a block that breaks the tiling rule) fails here, at the
widths the serving path uses, before it reaches a chip.  Interpret-mode
tests cannot see such faults.  Each compile asserts that the program holds
the kernel as a ``tpu_custom_call``.

The topology is described inside a fixture, never at import: only one
process at a time may load libtpu, and every pytest worker imports this
file.
"""
from __future__ import annotations

import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels.frontier import bitmap_expand, bitmap_expand_packed
from repro.kernels.minplus import minplus


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or it is held by another process
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _compile_text(fn, one_chip, *shapes) -> str:
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("m,k,n", [
    (32, 20, 20),     # the general lane's sketch: chunk 32 x R=20 landmarks
    (128, 64, 64),
])
def test_minplus_compiles_for_v5e(one_chip, no_persistent_cache, m, k, n):
    text = _compile_text(lambda a, b: minplus(a, b, interpret=False),
                         one_chip, ((m, k), jnp.int32), ((k, n), jnp.int32))
    assert "tpu_custom_call" in text


def test_bitmap_expand_compiles_for_v5e(one_chip, no_persistent_cache):
    text = _compile_text(lambda f, a: bitmap_expand(f, a, interpret=False),
                         one_chip, ((64, 128), jnp.bool_),
                         ((128, 128), jnp.bool_))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("n_hubs", [128, 256])
def test_bitmap_expand_packed_compiles_for_v5e(one_chip, no_persistent_cache,
                                               n_hubs):
    """The hybrid relay's hub block (default 128 hubs, and a wider one
    whose word rows span more than one 4-word tile)."""
    text = _compile_text(
        lambda f, w: bitmap_expand_packed(f, w, n_cols=n_hubs,
                                          interpret=False),
        one_chip, ((32, n_hubs), jnp.bool_), ((n_hubs, n_hubs // 32),
                                              jnp.uint32))
    assert "tpu_custom_call" in text
