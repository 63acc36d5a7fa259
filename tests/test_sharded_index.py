"""Vertex-sharded index (born-sharded labels + sharded serving) ==
replicated ``QbSIndex``, bit for bit — single-shard in-process over the
replicated path's graph families and every lane, 8-device emulated mesh
in a subprocess."""
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
from jax.sharding import Mesh

from repro.core import QbSIndex, gnp_random_graph, grid_graph
from repro.core.distributed import distributed_build_sharded
from repro.core.sharded import ShardedIndex

from helpers.graph_families import CASES, FAMILIES, lane_pairs


def _graphs():
    return [(gnp_random_graph(60, 3.5, seed=42), 5), (grid_graph(6, 6), 3)]


def _queries(g, lms, n_q=24, seed=0):
    rng = np.random.default_rng(seed)
    us = rng.integers(0, g.n_vertices, n_q).astype(np.int32)
    vs = rng.integers(0, g.n_vertices, n_q).astype(np.int32)
    us[:3] = lms[:3]          # exercise the landmark lanes
    vs[1:4] = lms[:3]
    return us, vs


def test_sharded_build_single_shard_matches_packed():
    mesh = Mesh(np.array(jax.devices()[:1]), ("shards",))
    for g, nl in _graphs():
        ref = QbSIndex.build(g, n_landmarks=nl, use_pallas=False)
        lms = np.asarray(ref.scheme.landmarks)
        sl, part = distributed_build_sharded(g, lms, mesh)
        v = g.n_vertices
        assert sl.pack_dtype == ref.packed.dtype
        np.testing.assert_array_equal(np.asarray(sl.labels_sh)[0, :v],
                                      np.asarray(ref.packed.label_dist))
        np.testing.assert_array_equal(np.asarray(sl.lm_sh)[0, :, :v],
                                      np.asarray(ref.packed.lm_dist))
        np.testing.assert_array_equal(np.asarray(sl.meta_w),
                                      np.asarray(ref.packed.meta_w))
        np.testing.assert_array_equal(np.asarray(sl.meta_dist),
                                      np.asarray(ref.packed.meta_dist))


def test_sharded_serving_single_shard_matches_replicated():
    for g, nl in _graphs():
        ref = QbSIndex.build(g, n_landmarks=nl, use_pallas=False)
        lms = np.asarray(ref.scheme.landmarks)
        sh = ShardedIndex.build(g, landmarks=lms, mesh=1)
        us, vs = _queries(g, lms)
        d_ref, m_ref = ref.query_batch_arrays(us, vs)
        d_sh, m_sh = sh.query_batch_arrays(us, vs)
        np.testing.assert_array_equal(d_sh, d_ref)
        np.testing.assert_array_equal(m_sh, m_ref)


@pytest.fixture(scope="module")
def family_indexes():
    """``name -> (replicated, one-shard sharded)`` index pair, built once
    a family so the lane cases of a family share their compiles."""
    built = {}

    def get(name):
        if name not in built:
            make, nl = FAMILIES[name]
            g = make()
            ref = QbSIndex.build(g, n_landmarks=nl, use_pallas=False)
            sh = ShardedIndex.build(
                g, landmarks=np.asarray(ref.scheme.landmarks), mesh=1)
            built[name] = ref, sh
        return built[name]

    return get


@pytest.mark.parametrize("family,lane", CASES)
def test_sharded_lane_matches_replicated(family_indexes, family, lane):
    ref, sh = family_indexes(family)
    us, vs = lane_pairs(ref.graph, ref.scheme.landmarks, lane)
    d_ref, m_ref = ref.query_batch_arrays(us, vs)
    d_sh, m_sh = sh.query_batch_arrays(us, vs)
    np.testing.assert_array_equal(d_sh, d_ref)
    np.testing.assert_array_equal(m_sh, m_ref)


def test_qbs_build_sharded_kwarg_returns_sharded_index():
    g = gnp_random_graph(40, 3.0, seed=1)
    idx = QbSIndex.build(g, n_landmarks=4, sharded=1)
    assert isinstance(idx, ShardedIndex) and idx.is_sharded
    ref = QbSIndex.build(g, n_landmarks=4, use_pallas=False,
                         landmarks=np.asarray(idx.labels.landmarks))
    a, b = idx.query(1, 17), ref.query(1, 17)
    assert a.dist == b.dist
    np.testing.assert_array_equal(a.edge_ids, b.edge_ids)


def test_service_rejects_batch_sharding_a_sharded_index():
    g = gnp_random_graph(40, 3.0, seed=1)
    sh = ShardedIndex.build(g, n_landmarks=4, mesh=1)
    with pytest.raises(ValueError, match="sharded index"):
        sh.make_service(devices=1)


def test_sharded_size_accounting():
    g = gnp_random_graph(40, 3.0, seed=1)
    sh = ShardedIndex.build(g, n_landmarks=4, mesh=1)
    info = sh.sharded_size_bytes()
    item = sh.labels.pack_dtype.itemsize
    v, r = g.n_vertices, sh.labels.n_landmarks
    assert sh.labels.per_device_label_bytes() == \
        2 * sh.labels.v_loc * r * item + 2 * r * r * item
    assert info["n_shards"] == 1
    assert info["per_device_label_bytes"] == \
        sh.labels.per_device_label_bytes()
    assert info["per_device_csr_bytes"] == 4 * sh.part.e_max * 4
    assert info["replicated_label_bytes"] == (2 * v * r + 2 * r * r) * item
    assert info["replicated_csr_bytes"] == 3 * g.n_edges * 4
    assert info["per_device_bytes"] == \
        info["per_device_label_bytes"] + info["per_device_csr_bytes"]
    assert info["replicated_bytes"] == \
        info["replicated_label_bytes"] + info["replicated_csr_bytes"]
    assert info["per_device_frac"] == pytest.approx(
        info["per_device_bytes"] / info["replicated_bytes"])
    # one shard holds the whole label table: bytes match the replicated one
    assert info["per_device_label_bytes"] == info["replicated_label_bytes"]


@pytest.mark.slow
def test_sharded_eight_devices_bit_identical_subprocess():
    script = os.path.join(os.path.dirname(__file__), "helpers",
                          "sharded_check.py")
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")])
    out = subprocess.run([sys.executable, script], env=env,
                         capture_output=True, text=True, timeout=1200)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "ALL-OK" in out.stdout
