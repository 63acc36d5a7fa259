"""Cross-backend equivalence for the pluggable frontier engine.

The ``segment`` backend is the bit-identical reference (the seed's
``segment_max`` relay).  ``csr`` (pull over the src-sorted layout) and
``hybrid`` (dense hub block + compacted tail) must produce *identical*
booleans on every generator regime — OR-reductions are order-invariant, so
there is no tolerance anywhere in this file.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import (
    QbSIndex,
    barabasi_albert_graph,
    build_labelling,
    gnp_random_graph,
    grid_graph,
    make_relay,
    random_regular_graph,
    ring_of_cliques,
    select_landmarks,
)
from repro.core.baselines import bfs_spg, bibfs_spg
from repro.core.frontier import segment_or

BACKENDS = ("segment", "csr", "hybrid")

GRAPHS = {
    "gnp": lambda: gnp_random_graph(60, 3.0, seed=7),
    "barabasi_albert": lambda: barabasi_albert_graph(70, 2, seed=3),
    "random_regular": lambda: random_regular_graph(48, 4, seed=5),
    "ring_of_cliques": lambda: ring_of_cliques(6, 5),
    "grid": lambda: grid_graph(6, 6),
}


def _engines(g, **kw):
    return {
        "segment": make_relay(g, backend="segment", **kw),
        "csr": make_relay(g, backend="csr", block_size=64, **kw),
        "hybrid": make_relay(g, backend="hybrid", n_hubs=16, **kw),
    }


@pytest.mark.parametrize("gen", sorted(GRAPHS))
def test_relay_identical_across_backends(gen):
    g = GRAPHS[gen]()
    rng = np.random.default_rng(11)
    vals = jnp.asarray(rng.random((5, g.n_vertices)) < 0.25)
    engines = _engines(g)
    want = np.asarray(engines["segment"].relay(vals))
    for name in ("csr", "hybrid"):
        got = np.asarray(engines[name].relay(vals))
        assert (got == want).all(), name
    # 1-D convenience form round-trips
    got1 = np.asarray(engines["hybrid"].relay(vals[0]))
    assert (got1 == want[0]).all()


@pytest.mark.parametrize("gen", sorted(GRAPHS))
def test_masked_relay_identical_across_backends(gen):
    """Vertex-factored (hence symmetric) edge masks — the G- shape."""
    g = GRAPHS[gen]()
    rng = np.random.default_rng(13)
    vkeep = rng.random(g.n_vertices) < 0.7
    emask = vkeep[np.asarray(g.src)] & vkeep[np.asarray(g.dst)]
    vals = jnp.asarray(rng.random((3, g.n_vertices)) < 0.3)
    engines = _engines(g, edge_mask=emask)
    want = np.asarray(engines["segment"].relay(vals))
    for name in ("csr", "hybrid"):
        got = np.asarray(engines[name].relay(vals))
        assert (got == want).all(), name


def test_scatter_matches_segment_or():
    g = GRAPHS["gnp"]()
    rng = np.random.default_rng(3)
    msgs = jnp.asarray(rng.random((4, g.n_edges)) < 0.2)
    want = np.asarray(segment_or(msgs, g.dst, g.n_vertices))
    for name, eng in _engines(g).items():
        got = np.asarray(eng.scatter(msgs))
        assert (got == want).all(), name


def test_hybrid_pallas_kernel_path():
    """The hybrid backend's dense block through the real Pallas kernel
    (interpret mode) must agree with the jnp matmul path."""
    g = GRAPHS["barabasi_albert"]()
    rng = np.random.default_rng(1)
    vals = jnp.asarray(rng.random((4, g.n_vertices)) < 0.3)
    ref = make_relay(g, backend="hybrid", n_hubs=16, use_pallas=False)
    pal = make_relay(g, backend="hybrid", n_hubs=16, use_pallas=True)
    assert (np.asarray(pal.relay(vals)) == np.asarray(ref.relay(vals))).all()


def test_hub_split_structure():
    g = GRAPHS["barabasi_albert"]()
    split = g.hub_split(8)
    deg = np.asarray(g.degrees())
    assert split.hub_ids.shape == (8,)
    assert deg[split.hub_ids].min() >= np.sort(deg)[-8:].min() - 0  # top-degree
    assert split.adj_hh.shape == (8, 8)
    assert (split.adj_hh == split.adj_hh.T).all()  # symmetrized edge list
    assert not np.diag(split.adj_hh).any()         # no self loops
    # hub_edge marks exactly the edges inside the hub set
    src, dst = np.asarray(g.src), np.asarray(g.dst)
    want = split.is_hub[src] & split.is_hub[dst] & (src != dst)
    assert (split.hub_edge == want).all()


@pytest.mark.parametrize("gen", sorted(GRAPHS))
def test_labelling_scheme_bit_identical(gen):
    g = GRAPHS[gen]()
    lms = select_landmarks(g, 5)
    ref = build_labelling(g, lms, backend="segment")
    for name in ("csr", "hybrid"):
        kw = {"block_size": 64} if name == "csr" else {"n_hubs": 16}
        got = build_labelling(g, lms, backend=name, **kw)
        assert (np.asarray(got.label_dist) == np.asarray(ref.label_dist)).all(), name
        assert (np.asarray(got.meta_w) == np.asarray(ref.meta_w)).all(), name
        assert (np.asarray(got.meta_dist) == np.asarray(ref.meta_dist)).all(), name


@pytest.mark.parametrize("gen", sorted(GRAPHS))
def test_spg_results_identical_across_backends(gen):
    """End-to-end: every backend must return the seed path's exact SPG
    (dist + edge-id set) and match the two-BFS oracle."""
    g = GRAPHS[gen]()
    idxs = {
        "segment": QbSIndex.build(g, n_landmarks=5),
        "csr": QbSIndex.build(g, n_landmarks=5, backend="csr",
                              engine_opts={"block_size": 64}),
        "hybrid": QbSIndex.build(g, n_landmarks=5, backend="hybrid",
                                 engine_opts={"n_hubs": 16}),
    }
    rng = np.random.default_rng(17)
    lms = np.asarray(idxs["segment"].scheme.landmarks)
    pairs = [(int(rng.integers(0, g.n_vertices)),
              int(rng.integers(0, g.n_vertices))) for _ in range(6)]
    pairs += [(int(lms[0]), int(rng.integers(0, g.n_vertices))),
              (int(lms[0]), int(lms[1]))]  # landmark-endpoint path too
    for u, v in pairs:
        o = bfs_spg(g, u, v)
        ref = idxs["segment"].query(u, v)
        assert ref.dist == o.dist, (u, v)
        assert ref.edge_pairs(g) == o.edge_pairs(g), (u, v)
        for name in ("csr", "hybrid"):
            r = idxs[name].query(u, v)
            assert r.dist == ref.dist, (name, u, v)
            assert (r.edge_ids == ref.edge_ids).all(), (name, u, v)


def test_bibfs_baseline_across_backends():
    g = GRAPHS["random_regular"]()
    ref = bibfs_spg(g, 1, 17)
    for name in ("csr", "hybrid"):
        r = bibfs_spg(g, 1, 17, backend=name)
        assert r.dist == ref.dist
        assert (r.edge_ids == ref.edge_ids).all(), name


def test_unknown_backend_rejected():
    g = GRAPHS["grid"]()
    with pytest.raises(ValueError):
        make_relay(g, backend="nope")
