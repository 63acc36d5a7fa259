"""Compile the Pallas kernels of the serving path for a TPU v5e chip that is
described, not attached.

The TPU compiler ships with libtpu, so these compiles run on any host that
has it installed: a kernel Mosaic refuses (an unaligned slice, a cast it
cannot lower, a block that breaks the tiling rule) fails here, at the
widths the serving path uses, before it reaches a chip.  Interpret-mode
tests cannot see such faults.  Each kernel compile asserts that the
program holds the kernel as a ``tpu_custom_call``; the serving lanes'
compiles assert that no scatter runs inside their search loops.

The topology is described inside a fixture, never at import: only one
process at a time may load libtpu, and every pytest worker imports this
file.
"""
from __future__ import annotations

import os
import re

import numpy as np
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


# Scopes of the serving loops whose relays must hold no scatter: the
# frontier relay reduces each vertex's sorted CSR row instead.
LOOP_SCOPES = ("qbs.onesided.bfs", "qbs.bfs", "qbs.reverse", "qbs.recover")


def _on_chip(tree, one_chip):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)


def _loop_scatters(text: str) -> list[str]:
    """``op_name``s of scatters inside a while body of a serving loop."""
    names = re.findall(r'op_name="([^"]*)"', text)
    return [n for n in names if "while/body/scatter" in n
            and any(s in n for s in LOOP_SCOPES)]


@pytest.fixture(scope="module")
def small_index():
    from repro.core import QbSIndex, gnp_random_graph
    return QbSIndex.build(gnp_random_graph(300, 4.0, seed=3), n_landmarks=4,
                          chunk=8)


# The relay backend each lane compile runs: ``segment``, the serving
# default, pulls over CSR rows; ``csr`` still scatters by key, so the check
# must find scatters in its loops.
LOOP_BACKENDS = {"segment": False, "csr": True}


@pytest.mark.parametrize("backend", sorted(LOOP_BACKENDS))
def test_onesided_lane_loop_has_no_scatter(one_chip, no_persistent_cache,
                                           small_index, backend):
    """The one-sided lane's BFS loop relays by rows on the ``segment``
    backend; the ``csr`` backend's loop shows its scatter to the check."""
    from repro.core.frontier import make_relay
    from repro.core.qbs import _landmark_onesided_lanes

    g = small_index.graph
    engine = make_relay(g, backend=backend)
    b = 8
    args = _on_chip((engine, small_index._lm_dist, g.src, g.dst,
                     small_index._rev_edge_j, jnp.zeros((b,), jnp.int32),
                     jnp.zeros((b,), jnp.int32)), one_chip)
    text = _landmark_onesided_lanes.lower(
        *args, max_levels=small_index.max_levels).compile().as_text()
    assert "qbs.onesided.bfs" in text
    found = _loop_scatters(text)
    assert bool(found) == LOOP_BACKENDS[backend], found[:3]


def _general_loop_scatters(text: str) -> set[str]:
    for s in ("qbs.bfs", "qbs.reverse", "qbs.recover"):
        assert s in text, s
    _assert_recover_has_no_scatter(text)
    return {s for s in ("qbs.bfs", "qbs.reverse", "qbs.recover")
            if any(s in n for n in _loop_scatters(text))}


def _assert_recover_has_no_scatter(text: str) -> None:
    """Recover holds no scatter on any backend, in or out of its loops:
    the job compaction is a sort, the blocks' OR into their queries a
    one-hot contraction, and the anchor chain a row pull."""
    names = [n for n in re.findall(r'op_name="([^"]*)"', text)
             if "qbs.recover" in n]
    assert any(n.endswith("/sort") for n in names), "no job compaction"
    assert any("/while/body/bj,je->be/dot_general" in n for n in names), \
        "no one-hot OR in the block loop"
    assert not [n for n in names if "scatter" in n], names


@pytest.mark.parametrize("backend", sorted(LOOP_BACKENDS))
def test_general_lane_loops_have_no_scatter(one_chip, no_persistent_cache,
                                            small_index, backend):
    """bfs, reverse and recover (the anchor chain) relay by rows on the
    ``segment`` backend; the ``csr`` backend's bfs and reverse loops show
    their scatters to the check."""
    from repro.core.qbs import _make_search_batch
    from repro.core.search import make_search_context

    idx = small_index
    ctx = make_search_context(idx.graph, idx.scheme, packed=idx.packed,
                              backend=backend)
    search = _make_search_batch(idx.graph.n_vertices, idx.max_levels,
                                idx.max_chain, False)
    b = idx.chunk
    args = _on_chip((ctx, idx.packed.label_dist, idx.packed.meta_w,
                     idx.packed.meta_dist, jnp.zeros((b,), jnp.int32),
                     jnp.zeros((b,), jnp.int32)), one_chip)
    found = _general_loop_scatters(search.lower(*args).compile().as_text())
    if LOOP_BACKENDS[backend]:
        assert {"qbs.bfs", "qbs.reverse"} <= found, found
    else:
        assert found == set(), found


@pytest.mark.parametrize("backend", sorted(LOOP_BACKENDS))
def test_sharded_serve_step_loops_have_no_scatter(topo, no_persistent_cache,
                                                  small_index, backend):
    """The batch-sharded serve step (``make_serve_step``, the service's
    path when a mesh is given) replicates the index's engine under
    shard_map, so on a 2x2 v5e mesh its loops relay by rows too."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core.distributed import make_serve_step
    from repro.core.search import make_search_context

    idx = small_index
    ctx = make_search_context(idx.graph, idx.scheme, packed=idx.packed,
                              backend=backend)
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))
    serve = make_serve_step(ctx, idx.scheme, mesh,
                            n_vertices=idx.graph.n_vertices,
                            max_levels=idx.max_levels,
                            max_chain=idx.max_chain, packed=idx.packed)
    rep = NamedSharding(mesh, P())
    tables = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep),
        serve.args)
    batch = jax.ShapeDtypeStruct((idx.chunk,), jnp.int32,
                                 sharding=NamedSharding(mesh,
                                                        P(("data", "model"))))
    text = serve.func.lower(*tables, batch, batch).compile().as_text()
    found = _general_loop_scatters(text)
    if LOOP_BACKENDS[backend]:
        assert {"qbs.bfs", "qbs.reverse"} <= found, found
    else:
        assert found == set(), found


def test_update_programs_compile_for_v5e_with_their_scopes(
        one_chip, no_persistent_cache, small_index):
    """The update path's programs (the device insert test, the table
    update with its keep / affected-rows / rebuild branches, the table
    packing) compile for the chip and carry ``qbs.update``; the table
    update's BFS loops carry ``qbs.update.relabel`` and relay by rows."""
    from repro.core.labelling import _inserts_affected, _update_tables
    from repro.core.packing import _pack_tables

    idx = small_index
    s = idx.scheme
    lm = jnp.asarray(idx._lm_table, jnp.int32)
    aff = jnp.zeros((s.n_landmarks,), bool)
    text = _update_tables.lower(*_on_chip(
        (idx._full_engine, s.landmarks, s.is_landmark, s.label_dist, s.meta_w,
         s.meta_dist, lm, aff), one_chip),
        k_max=2, max_levels=256).compile().as_text()
    assert "qbs.update.relabel" in text
    assert not [n for n in re.findall(r'op_name="([^"]*)"', text)
                if "while/body/scatter" in n], text[:200]
    texts = [text, _inserts_affected.lower(*_on_chip(
        (s.label_dist, s.meta_w, lm, s.lid, s.is_landmark,
         jnp.zeros((64, 2), jnp.int32), aff), one_chip)).compile().as_text()]
    for dtype in (np.uint8, np.uint16):
        texts.append(_pack_tables.lower(
            *_on_chip((s.label_dist, s.meta_w, s.meta_dist, lm), one_chip),
            dtype=np.dtype(dtype)).compile().as_text())
    for text in texts:
        assert "qbs.update" in text
