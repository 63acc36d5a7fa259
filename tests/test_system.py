"""End-to-end behaviour tests for the full system: offline labelling →
online batched serving → exactness, and the serving launcher."""
import subprocess
import sys
import os

import numpy as np

from repro.core import INF, QbSIndex, barabasi_albert_graph, labelling_size_bytes
from repro.core.baselines import bfs_spg


def test_qbs_end_to_end_pipeline():
    """Build → sketch → guided search → exact SPGs on a 2k-vertex graph,
    the whole pipeline through the public facade."""
    g = barabasi_albert_graph(2_000, 3, seed=5)
    idx = QbSIndex.build(g, n_landmarks=20)

    # labelling invariants at system level
    sz = labelling_size_bytes(idx.scheme)
    assert sz["label_bytes"] == 2_000 * 20
    assert sz["n_meta_edges"] > 0

    rng = np.random.default_rng(0)
    us = rng.integers(0, 2_000, size=12)
    vs = rng.integers(0, 2_000, size=12)
    results = idx.query_batch(us, vs)
    n_checked = 0
    for r in results:
        o = bfs_spg(g, r.u, r.v)
        assert r.dist == o.dist
        assert r.edge_pairs(g) == o.edge_pairs(g)
        if r.dist < INF and r.dist > 1:
            n_checked += 1
    assert n_checked >= 6  # the graph regime actually exercised multi-hop SPGs


def test_serve_launcher_end_to_end():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--graph", "ba",
         "--n", "3000", "--landmarks", "10", "--queries", "24"],
        env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "queries in" in out.stdout
