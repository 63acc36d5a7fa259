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
from repro.core.frontier import bfs_depths, segment_or
from repro.core.graph import INF, from_edges

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
    """Per-edge conditions pull into their rows on every backend: the
    same booleans as a ``src``-keyed ``segment_or`` of the messages."""
    g = GRAPHS["gnp"]()
    rng = np.random.default_rng(3)
    vals = jnp.asarray(rng.random((4, g.n_vertices)) < 0.4)
    emask = jnp.asarray(rng.random((4, g.n_edges)) < 0.5)
    want = np.asarray(segment_or(vals[:, g.dst] & emask, g.src, g.n_vertices))
    for name, eng in _engines(g).items():
        got = np.asarray(eng.pull(vals, emask))
        assert (got == want).all(), name


def _push(g, vals, emask=None):
    """The seed's relay: messages keyed by ``dst``, reduced by scatter."""
    msgs = vals[..., g.src]
    if emask is not None:
        msgs = msgs & emask
    return np.asarray(segment_or(jnp.atleast_2d(msgs), g.dst, g.n_vertices))


def _star_plus(n, hub_deg, seed):
    """A hub whose row spans several reduction blocks, plus sparse edges."""
    rng = np.random.default_rng(seed)
    star = np.stack([np.zeros(hub_deg, np.int64), np.arange(1, hub_deg + 1)], 1)
    rest = rng.integers(0, n, (n, 2))
    return from_edges(np.concatenate([star, rest]), n)


def _case_rows_relay(name):
    g = {
        "gnp": lambda: GRAPHS["gnp"](),
        "ring_of_cliques": lambda: GRAPHS["ring_of_cliques"](),
        # isolated vertices (empty rows) and self-loop pad slots; 512 slots
        # is a whole number of reduction blocks
        "padded": lambda: gnp_random_graph(50, 2.0, seed=4, pad_vertices_to=64,
                                           pad_edges_to=512),
        "hub_rows": lambda: _star_plus(700, 600, seed=9),
    }[name]()
    rng = np.random.default_rng(21)
    eng = make_relay(g)
    vals = jnp.asarray(rng.random((5, g.n_vertices)) < 0.2)
    assert (np.asarray(eng.relay(vals)) == _push(g, vals)).all()
    # (V,) in, (V,) out
    assert (np.asarray(eng.relay(vals[2])) == _push(g, vals[2])[0]).all()


def _case_gminus(_):
    g = GRAPHS["barabasi_albert"]()
    idx = QbSIndex.build(g, n_landmarks=5)
    eng = idx.ctx.engine
    rng = np.random.default_rng(5)
    vals = jnp.asarray(rng.random((3, g.n_vertices)) < 0.3)
    want = _push(g, vals, idx.ctx.gminus_e)
    assert (np.asarray(eng.relay(vals)) == want).all()


def _case_edge_mask(_):
    """A per-query (K, E) condition and an (E,) one, on a masked engine."""
    g = GRAPHS["random_regular"]()
    rng = np.random.default_rng(8)
    vkeep = rng.random(g.n_vertices) < 0.8
    gm = vkeep[np.asarray(g.src)] & vkeep[np.asarray(g.dst)]
    eng = make_relay(g, edge_mask=gm)
    vals = jnp.asarray(rng.random((4, g.n_vertices)) < 0.4)
    for em in (rng.random((4, g.n_edges)) < 0.5, rng.random(g.n_edges) < 0.5):
        em = jnp.asarray(em)
        want = np.asarray(segment_or(vals[:, g.dst] & gm & em, g.src,
                                     g.n_vertices))
        assert (np.asarray(eng.pull(vals, em)) == want).all()


def _case_unsorted(_):
    """The relays pull over CSR rows: an edge list out of ``src`` order is
    refused on every backend."""
    g = GRAPHS["gnp"]()
    perm = np.random.default_rng(2).permutation(g.n_edges)
    shuffled = type(g)(g.indptr, g.src[perm], g.dst[perm])
    for backend in BACKENDS:
        with pytest.raises(ValueError, match="not sorted by src"):
            make_relay(shuffled, backend=backend)


def _case_recover_chain(_):
    """Recover's pulled anchor chain against the seed's dst-keyed scatter
    form, on balls much smaller than the paths they attach."""
    from repro.core.search import _label_col, _side_attach

    g = grid_graph(3, 14)   # two landmarks, long corridors between them
    idx = QbSIndex.build(g, n_landmarks=2)
    ctx = idx.ctx
    src, dst = np.asarray(g.src), np.asarray(g.dst)
    full = make_relay(g)
    grew = False
    for k in range(idx.scheme.n_landmarks):
        ld = _label_col(ctx, k)
        ls, ldd = ld[ctx.src], ld[ctx.dst]
        dec = ctx.gminus_e & (ldd < INF) & (ldd == ls - 1)
        inc = ctx.gminus_e & (ls < INF) & (ls == ldd - 1)
        ld_np = np.asarray(ld)
        for t in np.flatnonzero(ld_np < INF)[::3]:
            depth = bfs_depths(full, jnp.int32(t), 64, bound=jnp.int32(1))
            sigma = jnp.int32(ld_np[t])
            got = np.asarray(_side_attach(ctx, depth, sigma, ld, dec, inc,
                                          jnp.int32(k), 64))
            # the seed's chain: scatter dec & on[src] by dst to a fixpoint
            on = np.asarray((ld < INF) & (depth < INF) & (depth + ld == sigma))
            start = on.sum()
            while True:
                new = on | np.asarray(segment_or(
                    jnp.asarray(np.asarray(dec) & on[src])[None],
                    g.dst, g.n_vertices))[0]
                if (new == on).all():
                    break
                on = new
            grew |= on.sum() > start + 2
            lid, dec_np = np.asarray(ctx.lid), np.asarray(dec)
            want = ((dec_np & on[src] & on[dst])
                    | ((lid[dst] == k) & on[src] & (ld_np[src] == 1))
                    | ((lid[src] == k) & on[dst] & (ld_np[dst] == 1)))
            assert (got == want).all(), (k, t)
    assert grew, "no chain ran past its ball"


ROW_CASES = {
    "rows_gnp": _case_rows_relay,
    "rows_ring_of_cliques": _case_rows_relay,
    "rows_padded": _case_rows_relay,
    "rows_hub_rows": _case_rows_relay,
    "gminus_mask": _case_gminus,
    "per_query_edge_mask": _case_edge_mask,
    "unsorted_list_refused": _case_unsorted,
    "recover_chain": _case_recover_chain,
}


@pytest.mark.parametrize("case", sorted(ROW_CASES))
def test_row_pull_bit_identical(case):
    """The row-pull reduction gives the seed scatter relay's booleans."""
    ROW_CASES[case](case.removeprefix("rows_"))


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
