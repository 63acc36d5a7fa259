"""The benchmark's generator, traffic and plain reference, against the
program on small graphs (CPU)."""
import json
from pathlib import Path

import numpy as np
import pytest

import graphgen
import plugins
import traffic
from reference import INF, RefGraph

BENCH = Path(__file__).resolve().parents[1]
pa = plugins.load("generators", "preferential_attachment")


@pytest.fixture(scope="module")
def small():
    n = 300
    edges = pa.preferential_attachment(n, 480, traffic.rng_for(2**31 + 5, 0))
    return n, edges


def test_layout_matches_from_edges(small):
    from repro.core.graph import INF as PROGRAM_INF, from_edges

    n, edges = small
    g, r = from_edges(edges, n), RefGraph(edges, n)
    assert INF == PROGRAM_INF
    for name in ("indptr", "src", "dst"):
        assert np.array_equal(np.asarray(getattr(g, name)), getattr(r, name)), name


@pytest.fixture(scope="module")
def index(small):
    from repro.core import QbSIndex
    from repro.core.graph import from_edges

    n, edges = small
    return QbSIndex.build(from_edges(edges, n), n_landmarks=8, chunk=8)


def _assert_same(ref, results):
    for r in results:
        d, eids = ref.spg(r.u, r.v)
        assert r.dist == d, (r.u, r.v)
        assert np.array_equal(np.asarray(r.edge_ids), eids), (r.u, r.v)


def test_general_lane_bit_identical(small, index):
    n, edges = small
    top = graphgen.top_degree(edges, n, 8)
    assert np.array_equal(np.asarray(index.scheme.landmarks), top)
    us, vs = traffic.draw_pairs({"kind": "uniform"}, n, top, 48,
                                np.random.default_rng(1))
    keep = ~np.isin(us, top) & ~np.isin(vs, top)
    assert keep.sum() >= 24
    _assert_same(RefGraph(edges, n), index.query_batch(us[keep], vs[keep]))


def test_onesided_lane_bit_identical(small, index):
    n, edges = small
    top = graphgen.top_degree(edges, n, 8)
    us, vs = traffic.draw_pairs({"kind": "top_degree_anchored", "k": 8}, n,
                                top, 24, np.random.default_rng(2))
    assert (np.isin(us, top) ^ np.isin(vs, top)).all()
    _assert_same(RefGraph(edges, n), index.query_batch(us, vs))


def test_after_one_update_epoch(small, index):
    n, edges = small
    ref = RefGraph(edges, n)
    (ins, dels), = traffic.update_batches(
        ref.keys, n, {"inserts": 6, "deletes": 6}, 1, np.random.default_rng(3))
    new = index.apply_update(inserts=ins, deletes=dels)
    ref1 = ref.updated(ins, dels)
    assert ref1.keys.size == ref.keys.size
    us, vs = traffic.draw_pairs({"kind": "uniform"}, n,
                                graphgen.top_degree(edges, n, 8), 32,
                                np.random.default_rng(4))
    _assert_same(ref1, new.query_batch(us, vs))


def test_control_breaks_the_guarantee(small):
    n, edges = small
    ref = RefGraph(edges, n)
    us, vs = traffic.draw_pairs({"kind": "uniform"}, n,
                                graphgen.top_degree(edges, n, 8), 64,
                                np.random.default_rng(5))
    differ = 0
    for u, v in zip(us.tolist(), vs.tolist()):
        d, all_eids = ref.spg(u, v)
        d1, one = ref.one_path(u, v)
        assert d1 == d and np.isin(one, all_eids).all() and one.size == 2 * d
        differ += not np.array_equal(one, all_eids)
    assert differ > 0


def test_edge_count_does_not_depend_on_seed():
    for name in ("douban-r20", "youtube-r20"):
        g = json.loads((BENCH / "configs" / f"{name}.json").read_text())["graph"]
        k0, m = pa.attachment_schedule(g["n_vertices"], g["n_edges"])
        assert k0 * (k0 - 1) // 2 + int(m.sum()) == g["n_edges"]
    g = json.loads((BENCH / "configs" / "douban-r20.json").read_text())["graph"]
    for seed in (0, 2**31 + 3):
        e = graphgen.generate(g, traffic.rng_for(seed, 0))
        keys = RefGraph.canonical_keys(e, g["n_vertices"])
        assert keys.size == g["n_edges"] and (e[:, 0] != e[:, 1]).all()


def test_generator_and_traffic_deterministic_in_seed():
    spec = {"generator": "preferential_attachment", "n_vertices": 500,
            "n_edges": 800}
    a = graphgen.generate(spec, traffic.rng_for(9, 0))
    b = graphgen.generate(spec, traffic.rng_for(9, 0))
    c = graphgen.generate(spec, traffic.rng_for(10, 0))
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    top = graphgen.top_degree(a, 500, 20)
    for kind in ({"kind": "uniform"}, {"kind": "top_degree_anchored", "k": 20}):
        p1 = traffic.draw_pairs(kind, 500, top, 100, traffic.rng_for(2**33, 1))
        p2 = traffic.draw_pairs(kind, 500, top, 100, traffic.rng_for(2**33, 1))
        p3 = traffic.draw_pairs(kind, 500, top, 100, traffic.rng_for(2**33 + 1, 1))
        assert all(np.array_equal(x, y) for x, y in zip(p1, p2))
        assert not np.array_equal(p1[0], p3[0])
        assert (p1[0] != p1[1]).all()
    keys = RefGraph.canonical_keys(a, 500)
    spec = {"inserts": 5, "deletes": 5}
    b1 = traffic.update_batches(keys, 500, spec, 3, traffic.rng_for(4, 3))
    b2 = traffic.update_batches(keys, 500, spec, 3, traffic.rng_for(4, 3))
    assert all(np.array_equal(x[0], y[0]) and np.array_equal(x[1], y[1])
               for x, y in zip(b1, b2))
    live = set(keys.tolist())
    for ins, dels in b1:
        ik = set((ins[:, 0] * 500 + ins[:, 1]).tolist())
        dk = set((dels[:, 0] * 500 + dels[:, 1]).tolist())
        assert len(ik) == 5 and len(dk) == 5
        assert not ik & live and dk <= live
        live = (live - dk) | ik
