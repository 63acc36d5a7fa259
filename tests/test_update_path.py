"""The update path at scale (DESIGN.md §13): the host mirror of the edge
slots, the edge-slot capacity ladder, padding, the compile-stable update
programs, and a dense insert-only stream through the router against the
per-epoch oracle.

The dense stream runs on the benchmark's LDBC knows stand-in
(``perfbench/generators/ldbc_knows.py``) at about 2,000 persons."""
import contextlib
import importlib.util
import threading
from pathlib import Path

import jax
import numpy as np
import pytest

from helpers.serving_oracle import EpochOracle, _reverse_edge_map

from repro.core import QbSIndex, gnp_random_graph
from repro.core.graph import (
    EdgeSlots,
    apply_edge_updates,
    capacity_step,
    edge_set,
    from_edges,
)
from repro.core.labelling import update_path
from repro.serving import MetricsRegistry, ReplicaRouter

ROOT = Path(__file__).resolve().parents[1]


def _ldbc_knows():
    path = ROOT / "perfbench" / "generators" / "ldbc_knows.py"
    spec = importlib.util.spec_from_file_location("ldbc_knows_for_tests", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Compiles:
    """Counts the programs JAX traces or compiles while entered."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __enter__(self):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def _on(self, event, duration, **kw):
        if event in self.EVENTS:
            self.n += 1

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on)


def _absent(keys_present: set, n: int, k: int, rng) -> list:
    out = []
    while len(out) < k:
        a, b = (int(x) for x in rng.integers(0, n, 2))
        e = (min(a, b), max(a, b))
        if a != b and e not in keys_present:
            keys_present.add(e)
            out.append(e)
    return out


# ----------------------------------------------------------- the mirror


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_mirror_merge_is_from_edges_at_the_same_capacity(seed):
    """Batches of inserts and deletes merged into the mirror give the CSR
    ``from_edges`` builds over the same edges at the same capacity, slot
    for slot, and the reverse map the oracle computes."""
    rng = np.random.default_rng(seed)
    n = 60
    g = gnp_random_graph(n, 4.0, seed=seed)
    slots = EdgeSlots.of_graph(g)
    present = {tuple(int(x) for x in e) for e in edge_set(g)}
    for step in range(6):
        ins = _absent(present, n, int(rng.integers(0, 9)), rng)
        cur = sorted(present - set(ins))
        dels = [cur[i] for i in rng.choice(len(cur), int(rng.integers(0, 6)),
                                           replace=False)]
        present -= set(dels)
        slots, ki, kd = apply_edge_updates(
            slots, np.asarray(ins, np.int64).reshape(-1, 2),
            np.asarray(dels, np.int64).reshape(-1, 2))
        assert ki.size == len(ins) and kd.size == len(dels)
        got = slots.graph()
        want = from_edges(np.asarray(sorted(present), np.int64), n,
                          pad_vertices_to=n, pad_edges_to=slots.capacity)
        for a, b in zip(got, want):
            assert a.dtype == b.dtype and np.array_equal(a, b), step
        assert np.array_equal(slots.rev_slots(), _reverse_edge_map(
            np.asarray(want.src), np.asarray(want.dst), n))
        assert slots.n_live == 2 * len(present)


def test_mirror_batch_semantics():
    """Phantom inserts and deletes are no-ops, self loops drop, and an
    insert wins a tie with a delete of the same edge."""
    g = from_edges(np.array([[0, 1], [1, 2], [2, 3]]), 5)
    slots = EdgeSlots.of_graph(g)
    new, ins, dels = apply_edge_updates(
        slots, np.array([[1, 0], [3, 3], [4, 0]]), np.array([[4, 0], [2, 1], [3, 4]]))
    assert ins.tolist() == [0 * 5 + 4]            # (0, 1) present, (3, 3) a loop
    assert dels.tolist() == [1 * 5 + 2]           # (0, 4) inserted, (3, 4) absent
    assert edge_set(new.graph()).tolist() == [[0, 1], [0, 4], [2, 3]]


# ---------------------------------------------------- the capacity ladder


@pytest.fixture(scope="module")
def ladder_index():
    return QbSIndex.build(gnp_random_graph(80, 4.0, seed=11), n_landmarks=4,
                          chunk=8)


def test_a_graph_never_updated_keeps_its_exact_size(ladder_index):
    g = ladder_index.graph
    assert g.n_edges == 2 * len(edge_set(g)) == ladder_index.n_live_slots
    router = ReplicaRouter(ladder_index, n_replicas=1)
    stats = router.replicas[0].stats
    assert stats["edge_slot_capacity"] == stats["edge_slots_live"] == g.n_edges


def test_capacity_takes_one_step_and_holds_within_it(ladder_index):
    """The first insert takes one 1/16 step (never x2); inserts within
    the step change no shape, so the lanes compile nothing; a batch that
    overflows takes one more step."""
    rng = np.random.default_rng(4)
    n = ladder_index.graph.n_vertices
    present = {tuple(int(x) for x in e) for e in edge_set(ladder_index.graph)}
    exact = ladder_index.graph.n_edges
    cur = ladder_index.apply_update(inserts=_absent(present, n, 1, rng))
    cap = cur.graph.n_edges
    assert cap == capacity_step(exact) < 2 * exact
    assert cur.last_update_info["capacity_step"]
    us = rng.integers(0, n, 8).astype(np.int32)
    vs = rng.integers(0, n, 8).astype(np.int32)
    cur.query_batch_arrays(us, vs)                      # the lanes at this step
    while cur.n_live_slots + 2 <= cap:
        cur = cur.apply_update(inserts=_absent(present, n, 1, rng))
        assert cur.graph.n_edges == cap
        assert not cur.last_update_info["capacity_step"]
        with Compiles() as c:
            cur.query_batch_arrays(us, vs)
        assert c.n == 0
    room = (capacity_step(cap) - cur.n_live_slots) // 2
    nxt = cur.apply_update(inserts=_absent(present, n, room, rng))
    assert nxt.graph.n_edges == capacity_step(cap)
    assert nxt.last_update_info["capacity_step"]


# ------------------------------------------------------------- padding


def test_padding_enters_no_answer_and_no_label():
    """With free slots (self loops on the last vertex) the answers name
    live slots only, queries at the last vertex included, and the labels
    equal a build on the same edges with no padding at all."""
    rng = np.random.default_rng(8)
    g = gnp_random_graph(50, 3.0, seed=3)
    n = g.n_vertices
    index = QbSIndex.build(g, n_landmarks=4, chunk=8)
    present = {tuple(int(x) for x in e) for e in edge_set(g)}
    cur = index
    for _ in range(3):
        cur = cur.apply_update(inserts=_absent(present, n, 2, rng))
    assert cur.graph.n_edges > cur.n_live_slots
    src = np.asarray(cur.graph.src)
    assert np.all(src[cur.n_live_slots:] == n - 1)
    us = np.r_[np.full(8, n - 1), rng.integers(0, n, 24)].astype(np.int32)
    vs = np.r_[rng.integers(0, n - 1, 8), rng.integers(0, n, 24)].astype(np.int32)
    dist, mask = cur.query_batch_arrays(us, vs)
    assert not mask[:, cur.n_live_slots:].any()
    exact = QbSIndex.build(from_edges(np.asarray(sorted(present)), n),
                           landmarks=np.asarray(index.scheme.landmarks), chunk=8)
    for name in ("label_dist", "meta_w", "meta_dist"):
        assert np.array_equal(getattr(cur.scheme, name),
                              getattr(exact.scheme, name)), name
    assert np.array_equal(cur._lm_dist_host, exact._lm_dist_host)
    d_exact, _ = exact.query_batch_arrays(us, vs)
    assert np.array_equal(dist, d_exact)


# ------------------------------------------------- compile-stable updates


def test_after_the_warm_up_no_update_width_compiles():
    """Once an update has taken the capacity step through the router
    (which warms the update path), updates within the step compile
    nothing, whatever their affected width: none, some rows, past the
    threshold (the rebuild), or a delete read on the host."""
    rng = np.random.default_rng(6)
    index = QbSIndex.build(gnp_random_graph(200, 4.0, seed=12), n_landmarks=4,
                           chunk=8)
    n = index.graph.n_vertices
    present = {tuple(int(x) for x in e) for e in edge_set(index.graph)}
    router = ReplicaRouter(index, n_replicas=1)
    cur = router.apply_update(inserts=_absent(present, n, 1, rng))
    assert cur.last_update_info["capacity_step"]
    paths = set()
    with Compiles() as c:
        for k in (1, 1, 3, 6, 0):
            cur = router.apply_update(inserts=_absent(present, n, k, rng))
            assert not cur.last_update_info["capacity_step"]
            paths.add(update_path(cur.last_update_info))
        gone = sorted(present)[0]
        present.discard(gone)
        cur = router.apply_update(deletes=[gone])
        paths.add(update_path(cur.last_update_info))
    assert c.n == 0
    assert len(paths) >= 2


@contextlib.contextmanager
def no_device_reads():
    """Refuse every host read of a device array's values while entered:
    through the array (``int``, ``bool``, ``device_get``) and through
    numpy in the update path's modules (numpy reads a CPU array through
    the buffer protocol, which no transfer guard sees)."""
    from jax._src import array

    from repro.core import frontier, graph, labelling, packing, qbs, search

    def refuse(*_a, **_k):
        raise AssertionError("the update path read a device array")

    class NumpyRefusingDeviceArrays:
        def __getattr__(self, name):
            fn = getattr(np, name)
            if isinstance(fn, type) or not callable(fn):
                return fn

            def call(*a, **k):
                if any(isinstance(x, jax.Array) for x in (*a, *k.values())):
                    refuse()
                return fn(*a, **k)
            return call

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(array.ArrayImpl, "_value", property(refuse))
        for mod in (frontier, graph, labelling, packing, qbs, search):
            mp.setattr(mod, "np", NumpyRefusingDeviceArrays())
        yield


def test_insert_only_updates_never_wait_for_the_device():
    """An insert-only batch between reached endpoints reads nothing back
    from the device: its affected set, relabel and packing stay there, so
    the epoch advance is host work alone and never waits for the chunks
    ahead of it on the device."""
    rng = np.random.default_rng(2)
    g = gnp_random_graph(120, 6.0, seed=4)
    index = QbSIndex.build(g, n_landmarks=4, chunk=8)
    n = g.n_vertices
    present = {tuple(int(x) for x in e) for e in edge_set(g)}
    reached = np.flatnonzero(~index._unreached)
    cur = index.apply_update(inserts=_absent(present, n, 2, rng))
    oracle = EpochOracle(cur.graph)
    batches = []
    for k in (1, 4, 9):
        ins = []
        while len(ins) < k:
            a, b = (int(x) for x in rng.choice(reached, 2))
            e = (min(a, b), max(a, b))
            if a != b and e not in present:
                present.add(e)
                ins.append(e)
        batches.append(ins)
    epochs = []
    with no_device_reads():
        for ins in batches:
            cur = cur.apply_update(inserts=ins)
            epochs.append((cur.graph, ins))
    for graph, ins in epochs:
        oracle.advance(graph, inserts=ins)
    fresh = QbSIndex.build(cur.graph, landmarks=np.asarray(index.scheme.landmarks),
                           chunk=8)
    for name in ("label_dist", "meta_w", "meta_dist"):
        assert np.array_equal(getattr(cur.scheme, name),
                              getattr(fresh.scheme, name)), name
    for a, b in zip(cur.packed, fresh.packed):
        assert a.dtype == b.dtype and np.array_equal(a, b)
    us = rng.integers(0, n, 16).astype(np.int32)
    vs = rng.integers(0, n, 16).astype(np.int32)
    dist, mask = cur.query_batch_arrays(us, vs)
    for i in range(len(us)):
        od, oe = oracle.spg(int(us[i]), int(vs[i]), oracle.epoch)
        assert dist[i] == od and np.array_equal(np.flatnonzero(mask[i]), oe)


def test_an_update_never_waits_for_a_busy_scheduler():
    """While a replica's scheduler lock is held (as by a thread blocked on
    the device for a chunk), the tier's update still returns; the index
    is installed once the lock is free, and the first read submitted
    after the update is admitted at its epoch and answers exactly."""
    rng = np.random.default_rng(3)
    index = QbSIndex.build(gnp_random_graph(60, 4.0, seed=2), n_landmarks=4,
                           chunk=8)
    n = index.graph.n_vertices
    present = {tuple(int(x) for x in e) for e in edge_set(index.graph)}
    router = ReplicaRouter(index, n_replicas=1)
    svc = router.replicas[0]
    oracle = EpochOracle(index.graph)
    held, release = threading.Event(), threading.Event()

    def hold():
        with svc._lock:
            held.set()
            release.wait(30)

    holder = threading.Thread(target=hold)
    holder.start()
    assert held.wait(10)
    ins = _absent(present, n, 3, rng)
    done = []
    writer = threading.Thread(
        target=lambda: done.append(router.apply_update(inserts=ins)))
    writer.start()
    writer.join(60)
    assert done, "the update waited for the scheduler lock"
    assert svc.index.epoch == 0          # offered, not installed: lock held
    release.set()
    holder.join(10)
    assert not holder.is_alive()
    new = done[0]
    oracle.advance(new.graph, inserts=ins)
    futs = router.submit_batch(rng.integers(0, n, 6), rng.integers(0, n, 6))
    router.drain()
    assert svc.index is new
    for f in futs:
        assert f.epoch == new.epoch
        oracle.assert_future(f)


# ------------------------------------------- a dense insert-only stream


def test_dense_insert_stream_through_the_router_matches_the_oracle():
    """A dense clustered graph (the LDBC knows stand-in: 2,000 persons,
    mean degree 30) takes an insert-only stream through the router; every
    epoch's answers are bit-identical to the oracle of that epoch, on
    batches that take the incremental path and batches that rebuild, and
    the router's replica counts them on ``/metrics``."""
    gen = _ldbc_knows()
    n, m = 2000, 30000
    knows = {"degree_sigma": 1.14, "degree_cap": 26.3,
             "shares": [0.45, 0.45, 0.10], "window": 50, "overshoot": 1.03}
    edges = gen.ldbc_knows(n, m, knows, 5)
    index = QbSIndex.build(from_edges(edges, n), n_landmarks=8, chunk=16)
    router = ReplicaRouter(index, n_replicas=1)
    oracle = EpochOracle(index.graph)
    rng = np.random.default_rng(9)
    present = {tuple(int(x) for x in e) for e in edge_set(index.graph)}
    paths = []
    for k in (1, 12, 1, 2, 12, 1):
        ins = _absent(present, n, k, rng)
        new = router.apply_update(inserts=ins)
        assert oracle.advance(new.graph, inserts=ins) == new.epoch
        paths.append(update_path(new.last_update_info))
        us = rng.integers(0, n, 12)
        vs = rng.integers(0, n, 12)
        futs = router.submit_batch(us, vs)
        router.drain()
        for f in futs:
            assert f.epoch == new.epoch
            oracle.assert_future(f)
    assert {"incremental", "rebuild"} <= set(paths)
    stats = router.replicas[0].stats
    assert stats["edge_slots_live"] == 2 * (m + 29)
    assert stats["edge_slot_capacity"] == capacity_step(2 * m)
    text = MetricsRegistry()
    text.register("r0", router.replicas[0])
    body = text.render_text()
    for p in ("incremental", "rebuild", "noop"):
        assert f'qbs_updates_total{{service="r0",path="{p}"}} {paths.count(p)}' in body
    assert f'qbs_edge_slot_capacity{{service="r0"}} {capacity_step(2 * m)}' in body
    assert f'qbs_edge_slots_live{{service="r0"}} {2 * (m + 29)}' in body
    assert 'qbs_update_affected_landmarks{service="r0"} ' in body
