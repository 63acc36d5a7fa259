"""Distributed (shard_map) labelling + serving == single-device results.

The 1-device mesh runs in-process; the true multi-device check spawns a
subprocess with ``--xla_force_host_platform_device_count=8`` because the
device count is locked at first jax init.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
from jax.sharding import Mesh

from repro.core import QbSIndex, build_labelling, gnp_random_graph, select_landmarks
from repro.core.baselines import bfs_spg
from repro.core.distributed import (
    distributed_build_labelling,
    make_serve_step,
    partition_edges,
)

from helpers.graph_families import FAMILIES, lane_pairs


def test_partition_edges_covers_all_edges():
    g = gnp_random_graph(50, 4.0, seed=3)
    part = partition_edges(g, 4)
    dst = np.asarray(g.dst)
    total = 0
    vend = np.concatenate([part.vstart[1:], [g.n_vertices]])
    for s in range(4):
        valid = part.dst_local[s] < part.v_loc
        total += int(valid.sum())
        d_glob = part.dst_local[s][valid] + part.vstart[s]
        assert (d_glob >= part.vstart[s]).all() and (d_glob < vend[s]).all()
    assert total == dst.shape[0]


def test_partition_balances_by_edges_not_vertices():
    # hub graph: vertex 0 has half of all edges
    from repro.core import barabasi_albert_graph

    g = barabasi_albert_graph(100, 3, seed=1)
    part = partition_edges(g, 8)
    counts = (part.dst_local < part.v_loc).sum(axis=1)
    # no shard should be pathologically overloaded vs the mean
    assert counts.max() <= max(4 * counts.mean(), counts.max() * 0 + g.degrees().max())


@pytest.mark.parametrize("mode", ["bool", "bitmap", "pull"])
def test_distributed_labelling_single_device_mesh(mode):
    g = gnp_random_graph(40, 3.0, seed=42)
    landmarks = select_landmarks(g, 4)
    ref = build_labelling(g, landmarks)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    got = distributed_build_labelling(g, landmarks, mesh, frontier_mode=mode)
    assert (np.asarray(got.label_dist) == np.asarray(ref.label_dist)).all()
    assert (np.asarray(got.meta_w) == np.asarray(ref.meta_w)).all()


def test_sharded_serving_single_device_mesh():
    g = gnp_random_graph(40, 3.0, seed=7)
    idx = QbSIndex.build(g, n_landmarks=4)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    serve = make_serve_step(idx.ctx, idx.scheme, mesh, n_vertices=g.n_vertices)
    rng = np.random.default_rng(0)
    nl = np.asarray(idx.scheme.is_landmark)
    cand = np.flatnonzero(~nl)
    us = rng.choice(cand, size=4).astype(np.int32)
    vs = rng.choice(cand, size=4).astype(np.int32)
    mask, dist = serve(us, vs)
    mask = np.asarray(mask)
    for k in range(4):
        o = bfs_spg(g, int(us[k]), int(vs[k]))
        m = mask[k] | mask[k][idx._rev_edge]
        pairs = {
            (int(min(a, b)), int(max(a, b)))
            for a, b in zip(np.asarray(g.src)[m], np.asarray(g.dst)[m])
        }
        assert int(dist[k]) == o.dist
        assert pairs == o.edge_pairs(g)


@pytest.mark.parametrize("family", list(FAMILIES))
def test_batch_sharded_serve_step_matches_replicated(family):
    """The batch-sharded general lane, built as ``ServingService(mesh=)``
    builds it, == ``QbSIndex.query_batch_arrays`` bit for bit."""
    make, nl = FAMILIES[family]
    g = make()
    idx = QbSIndex.build(g, n_landmarks=nl, use_pallas=False)
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    serve = make_serve_step(
        idx.ctx, idx.scheme, mesh, n_vertices=g.n_vertices,
        max_levels=idx.max_levels, max_chain=idx.max_chain,
        use_pallas=idx.use_pallas, packed=idx.packed)
    us, vs = lane_pairs(g, idx.scheme.landmarks, "general")
    mask, dist = serve(us, vs)
    mask = np.asarray(mask)
    d_ref, m_ref = idx.query_batch_arrays(us, vs)
    np.testing.assert_array_equal(np.asarray(dist), d_ref)
    np.testing.assert_array_equal(mask | mask[:, idx._rev_edge], m_ref)


@pytest.mark.slow
def test_distributed_eight_devices_subprocess():
    """Full 8-device exactness check in a fresh process."""
    script = os.path.join(os.path.dirname(__file__), "helpers", "dist_check.py")
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")]
    )
    out = subprocess.run(
        [sys.executable, script], env=env, capture_output=True, text=True, timeout=900
    )
    assert out.returncode == 0, out.stdout + out.stderr
    assert "ALL-OK" in out.stdout
