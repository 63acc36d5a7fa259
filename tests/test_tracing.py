"""Spans and scopes of the serving path (``repro.tracing``): the collector
hook, the names a recorded trace holds and where they fall against the
lane programs on one clock, the scopes in the compiled programs, the
result-byte counter and the latency that holds the wait for the device."""
import gc
import re

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import tracing
from repro.core import QbSIndex, gnp_random_graph
from repro.serving import ManualClock, MetricsRegistry, ReplicaRouter, StreamingService
from repro.serving import stream as stream_mod


@pytest.fixture(scope="module")
def index():
    return QbSIndex.build(gnp_random_graph(60, 3.0, seed=5), n_landmarks=4, chunk=8)


def _lane_pairs(index, rng):
    """One chunk of general pairs and one chunk of one-sided pairs."""
    lm = np.asarray(index.scheme.landmarks)
    non = rng.permutation(np.flatnonzero(~index._is_landmark_np))
    general = (non[:8], non[8:16])
    onesided = (lm[np.arange(8) % lm.size], non[16:24])
    return general, onesided


def test_gc_spans_leaves_the_callbacks_as_it_found_them():
    before = list(gc.callbacks)
    with tracing.gc_spans():
        assert len(gc.callbacks) == len(before) + 1
        gc.collect()
    assert gc.callbacks == before
    with pytest.raises(RuntimeError):
        with tracing.gc_spans():
            raise RuntimeError("out through the hook")
    assert gc.callbacks == before


def test_recorded_trace_holds_every_span_around_its_lane_program(index, tmp_path):
    router = ReplicaRouter(index, n_replicas=1)
    general, onesided = _lane_pairs(index, np.random.default_rng(1))
    router.query_batch(*general)
    router.query_batch(*onesided)                      # compiled before the trace
    jax.profiler.start_trace(str(tmp_path))
    with tracing.gc_spans():
        router.query_batch(*general[::-1])
        router.query_batch(*onesided[::-1])
        gc.collect()
    jax.profiler.stop_trace()
    (path,) = list(tmp_path.rglob("*.xplane.pb"))
    spans, modules = {}, {}
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for e in line.events:
                end = e.start_ns + e.duration_ns
                if e.name.startswith("qbs."):
                    spans.setdefault(e.name, []).append((e.start_ns, end))
                stats = dict(e.stats)
                if "hlo_module" in stats:      # the CPU's op events name their program
                    a, b = modules.get(stats["hlo_module"], (e.start_ns, end))
                    modules[stats["hlo_module"]] = (min(a, e.start_ns), max(b, end))
    assert set(spans) == {s for s, _ in tracing.SPANS}
    # chunk 1 is the general one, chunk 2 the one-sided one; the dispatch
    # starts before the chunk's program and the wait ends after it
    dispatch = sorted(spans["qbs.service.dispatch"])
    wait = sorted(spans["qbs.service.device_wait"])
    assert len(dispatch) == len(wait) == 2
    for k, names in enumerate([("jit_search_batch", "jit__symmetrize"),
                               ("jit__landmark_onesided_lanes",)]):
        lo = min(modules[n][0] for n in names)
        hi = max(modules[n][1] for n in names)
        assert dispatch[k][0] <= lo and hi <= wait[k][1]
    # the router's span holds no replica work: it ends before the dispatch
    assert sorted(spans["qbs.router.route"])[0][1] <= dispatch[0][0]


def _has_scope(hlo: str, name: str) -> bool:
    """An op's metadata names the scope, bare or inside a transformation's
    wrapper (``jit(f)/vmap(qbs.bfs)/while``)."""
    return re.search(r'op_name="[^"]*[/(]%s[)/"]' % re.escape(name), hlo) is not None


def test_compiled_lane_programs_carry_the_scopes(index):
    rng = np.random.default_rng(2)
    (gu, gv), (ou, ov) = _lane_pairs(index, rng)
    general = index._search_batch.lower(
        index.ctx, index.packed.label_dist, index.packed.meta_w,
        index.packed.meta_dist, gu.astype(np.int32), gv.astype(np.int32),
    ).compile().as_text()
    for name in ("qbs.sketch", "qbs.bfs", "qbs.reverse", "qbs.recover"):
        assert _has_scope(general, name)
    lid = index._lid_np
    roots = ov.astype(np.int32)
    onesided = jax.jit(index.landmark_onesided_step).lower(
        roots, lid[ou].astype(np.int32)).compile().as_text()
    for name in ("qbs.onesided.bfs", "qbs.onesided.certify"):
        assert _has_scope(onesided, name)
    assert not _has_scope(onesided, "qbs.bfs")


def test_result_bytes_are_counted_and_exported(index):
    svc = StreamingService(index, clock=ManualClock())
    general, onesided = _lane_pairs(index, np.random.default_rng(3))
    svc.query_batch(*general)
    svc.query_batch(*onesided)
    E = index.graph.n_edges
    chunk = svc.service.chunk
    want = svc.stats["chunks"] * chunk * (4 + E)      # int32 dist + bool mask rows
    assert svc.stats["chunks"] >= 2
    assert svc.stats["result_bytes"] == want
    reg = MetricsRegistry()
    reg.register("r0", svc)
    assert f'qbs_result_bytes_total{{service="r0"}} {want}' in reg.render_text()
    # the one-shot path counts on the service's own counters
    svc.service.query_batch(*general[::-1])
    assert svc.service.stats["result_bytes"] == chunk * (4 + E)


def test_latency_holds_the_wait_for_the_device(index, monkeypatch):
    """The fetch blocks until the device is done; the resolution time is
    read after it, so a fetch that takes 0.25 s shows in the latency."""
    clock = ManualClock()
    svc = StreamingService(index, clock=clock)
    orig = stream_mod.fetch_chunk

    def slow_fetch(out, stats):
        got = orig(out, stats)
        clock.advance(0.25)
        return got

    general, _ = _lane_pairs(index, np.random.default_rng(4))
    monkeypatch.setattr(stream_mod, "fetch_chunk", slow_fetch)
    futs = svc.submit_batch(*general)
    svc.drain()
    assert all(f.done() for f in futs)
    hist = svc.lat_hist["default"]
    assert hist.total == len(futs)
    assert hist.sum_us == pytest.approx(len(futs) * 0.25e6)
