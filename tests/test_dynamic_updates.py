"""Dynamic graph updates (DESIGN.md §13): epoch-versioned edge updates
with incremental label maintenance.

The contract under test: ``QbSIndex.apply_update`` returns a *new* index
for the next epoch whose tables are bit-identical to a fresh build on the
post-update graph with the same (pinned) landmark set — whichever branch
resolved it (affected-landmark recompute or the churn-threshold full
rebuild) — while the pre-update index stays untouched, so in-flight work
pinned to it keeps serving its own epoch.  The serving layer on top pins
admission epochs end-to-end: in-flight chunks resolve under the epoch
they were admitted at, the result cache keys carry the epoch (a stale
SPG is unreachable, never served), and every future records the epoch
that answered it (checked against the per-epoch numpy oracle).
"""
import numpy as np
import pytest

from helpers.serving_oracle import EpochOracle, oracle_spg

from repro.core import QbSIndex, gnp_random_graph
from repro.core.graph import edge_set, from_edges
from repro.serving import AdmissionPolicy, ManualClock, StreamingService

V = 48


@pytest.fixture(scope="module")
def graph():
    return gnp_random_graph(V, 3.0, seed=7)


@pytest.fixture(scope="module")
def index(graph):
    return QbSIndex.build(graph, n_landmarks=5, chunk=8)


def _update_trace(graph, rng, n_events):
    """Deterministic alternating insert/delete single-edge events."""
    events = []
    present = {tuple(int(x) for x in e) for e in edge_set(graph)}
    for i in range(n_events):
        if i % 2 == 0:
            while True:
                a, b = (int(x) for x in rng.integers(0, graph.n_vertices, 2))
                if a != b and (min(a, b), max(a, b)) not in present:
                    break
            edge = (min(a, b), max(a, b))
            present.add(edge)
            events.append({"inserts": [edge]})
        else:
            edge = sorted(present)[int(rng.integers(len(present)))]
            present.discard(edge)
            events.append({"deletes": [edge]})
    return events


def _assert_index_identical(a: QbSIndex, b: QbSIndex, us, vs) -> None:
    """Bit-identity of the full serving surface: scheme tables, landmark
    distances, packed tables (including the chosen dtype), and query
    results (dist + edge_mask)."""
    assert np.array_equal(a.scheme.landmarks, b.scheme.landmarks)
    assert np.array_equal(a.scheme.label_dist, b.scheme.label_dist)
    assert np.array_equal(a.scheme.meta_w, b.scheme.meta_w)
    assert np.array_equal(a.scheme.meta_dist, b.scheme.meta_dist)
    assert np.array_equal(a._lm_dist_host, b._lm_dist_host)
    assert a.packed.label_dist.dtype == b.packed.label_dist.dtype
    assert np.array_equal(a.packed.label_dist, b.packed.label_dist)
    assert np.array_equal(a.packed.lm_dist, b.packed.lm_dist)
    da, ma = a.query_batch_arrays(us, vs)
    db, mb = b.query_batch_arrays(us, vs)
    assert np.array_equal(da, db)
    assert np.array_equal(ma, mb)


# ------------------------------------------------------------- maintenance


@pytest.mark.parametrize("backend", ["segment", "csr", "hybrid"])
def test_incremental_update_bit_identical_to_fresh_build(graph, backend):
    """Six alternating single-edge updates: after each epoch the
    incrementally-maintained index equals a from-scratch build on the
    new graph with the same landmarks — on every backend."""
    rng = np.random.default_rng(3)
    cur = QbSIndex.build(graph, n_landmarks=5, chunk=8, backend=backend)
    lms = np.asarray(cur.scheme.landmarks)
    us = rng.integers(0, V, 12).astype(np.int32)
    vs = rng.integers(0, V, 12).astype(np.int32)
    for i, ev in enumerate(_update_trace(graph, rng, 6)):
        cur = cur.apply_update(**ev, churn_threshold=1.1)  # never rebuild
        assert cur.epoch == i + 1
        assert not cur.last_update_info["full_rebuild"]
        fresh = QbSIndex.build(cur.graph, landmarks=lms, chunk=8,
                               backend=backend)
        _assert_index_identical(cur, fresh, us, vs)


def test_rebuild_branch_bit_identical_and_source_untouched(graph, index):
    """churn_threshold=0 forces the full-rebuild branch; it must produce
    the same servable index as the incremental branch, and neither may
    mutate the source epoch's tables."""
    rng = np.random.default_rng(5)
    before = np.asarray(index.packed.label_dist).copy()
    es = edge_set(graph)
    ev = {"deletes": [tuple(int(x) for x in es[7])]}
    inc = index.apply_update(**ev, churn_threshold=1.1)
    reb = index.apply_update(**ev, churn_threshold=0.0)
    assert not inc.last_update_info["full_rebuild"]
    assert reb.last_update_info["full_rebuild"]
    assert inc.epoch == reb.epoch == index.epoch + 1
    us = rng.integers(0, V, 10).astype(np.int32)
    vs = rng.integers(0, V, 10).astype(np.int32)
    _assert_index_identical(inc, reb, us, vs)
    # the admitted epoch's tables survived both branches untouched
    assert np.array_equal(np.asarray(index.packed.label_dist), before)
    assert index.epoch == 0 and index.last_update_info == {}


def test_disconnect_and_reconnect_transitions():
    """Deleting a cut edge takes the pair to INF/no-edges; inserting a
    bridge brings it back — both epochs exact vs the numpy oracle."""
    edges = np.array([[0, 1], [1, 2], [2, 3], [3, 4], [0, 5]])
    g = from_edges(edges, 6)
    cur = QbSIndex.build(g, landmarks=np.array([0, 3]), chunk=4)
    oracle = EpochOracle(g)

    cur = cur.apply_update(deletes=[(0, 5)])
    oracle.advance(cur.graph, deletes=[(0, 5)])
    d, m = cur.query_batch_arrays([5, 0], [2, 4])
    assert d[0] >= (1 << 20) and not m[0].any()     # 5 cut off
    od, oe = oracle.spg(0, 4, 1)
    assert d[1] == od and np.array_equal(np.flatnonzero(m[1]), oe)

    cur = cur.apply_update(inserts=[(5, 4)])
    oracle.advance(cur.graph, inserts=[(5, 4)])
    d, m = cur.query_batch_arrays([5], [2])
    od, oe = oracle.spg(5, 2, 2)
    assert d[0] == od < (1 << 20)
    assert np.array_equal(np.flatnonzero(m[0]), oe)


def test_update_batch_semantics(graph, index):
    """Phantom inserts/deletes are no-ops, an insert wins a same-batch
    tie, self-loops are dropped — and the epoch graph always matches the
    oracle's independent edge algebra."""
    es = edge_set(graph)
    present = tuple(int(x) for x in es[0])
    absent = None
    allset = {tuple(int(x) for x in e) for e in es}
    for a in range(V):
        for b in range(a + 1, V):
            if (a, b) not in allset:
                absent = (a, b)
                break
        if absent:
            break
    oracle = EpochOracle(graph)
    ins = [present, absent, (3, 3)]          # phantom + real + self-loop
    dels = [absent, present]                 # tie with ins (insert wins) +
    nxt = index.apply_update(inserts=ins, deletes=dels)
    oracle.advance(nxt.graph, inserts=ins, deletes=dels)
    info = nxt.last_update_info
    # net effect: insert `absent` (ins wins its tie), keep `present`
    # (its delete ties a requested insert), drop the self-loop
    want = allset | {absent}
    assert {tuple(int(x) for x in e) for e in edge_set(nxt.graph)} == want
    assert nxt.epoch == index.epoch + 1
    assert int(info["n_affected"]) == int(np.count_nonzero(info["affected"]))

    # an all-phantom batch still advances the epoch, touching nothing
    noop = index.apply_update(inserts=[present], deletes=[absent])
    assert noop.epoch == index.epoch + 1
    assert noop.last_update_info["n_affected"] == 0
    assert np.array_equal(noop.packed.label_dist, index.packed.label_dist)
    assert np.array_equal(edge_set(noop.graph), edge_set(index.graph))


def test_star_fixture_double_delete_one_batch():
    """Two deletes sharing an endpoint in ONE batch — the affected-set
    criteria must see the batch's joint effect, not each edge alone."""
    edges = np.array([[0, 1], [0, 2], [1, 3], [2, 3], [3, 4]])
    g = from_edges(edges, 5)
    cur = QbSIndex.build(g, landmarks=np.array([0, 3]), chunk=4)
    cur = cur.apply_update(deletes=[(1, 3), (2, 3)])
    fresh = QbSIndex.build(cur.graph, landmarks=np.array([0, 3]), chunk=4)
    us = np.array([0, 0, 3, 1], np.int32)
    vs = np.array([4, 3, 4, 2], np.int32)
    _assert_index_identical(cur, fresh, us, vs)
    d, m = cur.query_batch_arrays(us, vs)
    assert d[0] >= (1 << 20) and d[1] >= (1 << 20)  # {3,4} split off
    assert d[2] == 1 and d[3] == 2


# ---------------------------------------------------------------- serving


def test_inflight_chunks_resolve_under_admission_epoch(graph, index):
    """Chunks already dispatched when an update lands resolve from their
    admission epoch's tables; later submissions of the *same pairs*
    resolve from the new epoch — each checked against its own oracle."""
    st = StreamingService(
        index, clock=ManualClock(),
        policy=AdmissionPolicy(adaptive=False, chunk=4, min_chunk=4),
        async_depth=4, cache_size=64)
    rng = np.random.default_rng(11)
    us = rng.integers(0, V, 8).astype(np.int32)
    vs = (us + rng.integers(1, V - 1, 8).astype(np.int32)) % V
    oracle = EpochOracle(graph)

    futs0 = st.submit_batch(us, vs)          # size trigger: dispatches now
    assert st.n_inflight > 0                 # window still holds chunks
    es = edge_set(graph)
    ev = {"deletes": [tuple(int(x) for x in es[3])]}
    new = st.submit_update(**ev, churn_threshold=0.5)
    oracle.advance(new.graph, **ev)
    assert st.index.epoch == 1 and st.stats["updates"] == 1
    futs1 = st.submit_batch(us, vs)          # must NOT join the old flight
    st.drain()
    assert not st._flight and not st._waiting
    assert {f.epoch for f in futs0} == {0}
    assert {f.epoch for f in futs1} == {1}
    for f in futs0 + futs1:
        oracle.assert_future(f)
    st.close()


def test_stale_cache_entry_never_served_across_epochs(graph, index):
    """A pair whose cached SPG an update invalidates: the resident
    epoch-0 entry stays resident but unreachable, the post-update query
    misses and recomputes the new answer."""
    rng = np.random.default_rng(13)
    st = StreamingService(index, clock=ManualClock(), cache_size=64,
                          policy=AdmissionPolicy(adaptive=False, chunk=64))
    # pick a pair at distance >= 2 and delete an edge on its SPG
    u = v = None
    for _ in range(50):
        a, b = (int(x) for x in rng.integers(0, V, 2))
        d, eids = oracle_spg(graph, a, b)
        if 2 <= d < (1 << 20):
            u, v = a, b
            cut = (int(np.asarray(graph.src)[eids[0]]),
                   int(np.asarray(graph.dst)[eids[0]]))
            break
    assert u is not None
    st.submit(u, v)
    st.drain()
    key = (min(u, v), max(u, v))
    assert (key[0], key[1], 0) in st.service.cache
    hits0 = st.stats["cache_hits"]
    st.submit(u, v)                          # same epoch: pure cache hit
    assert st.stats["cache_hits"] == hits0 + 1

    new = st.submit_update(deletes=[cut])
    fut = st.submit(u, v)
    st.drain()
    assert st.stats["cache_hits"] == hits0 + 1   # stale entry not consulted
    assert (key[0], key[1], 0) in st.service.cache   # resident, unreachable
    d1, e1 = oracle_spg(new.graph, u, v)
    assert fut.epoch == 1 and fut.result().dist == d1
    assert np.array_equal(np.asarray(fut.result().edge_ids), e1)
    st.close()


def test_install_index_guards(index):
    svc = index.make_service()
    with pytest.raises(ValueError, match="not ahead"):
        svc.install_index(index)             # same epoch: stale install
    nxt = index.apply_update(inserts=[(0, 37)])
    svc.install_index(nxt)
    assert svc.index is nxt and svc.stats["installs"] == 1
    with pytest.raises(ValueError, match="not ahead"):
        svc.install_index(nxt)

    class FakeSharded:
        is_sharded = True
        epoch = 99

    with pytest.raises(ValueError, match="sharded"):
        svc.install_index(FakeSharded())


def test_sharded_index_reports_epoch_zero():
    from repro.core.sharded import ShardedIndex
    assert ShardedIndex.epoch == 0
