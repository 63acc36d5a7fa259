"""The program's own spans and scopes in a trace: the reduction
(``programtrace``), the readers of the metrics that read it, and the run
that records them (``spans.py``), on a hand-built trace and on the CPU."""
import ast
import json
import time
from pathlib import Path

import pytest

import harness
import programtrace
import spans
import tracereduce
from repro import tracing

DATA = Path(__file__).resolve().parent / "data"
ROOT = Path(__file__).resolve().parents[2]
SCOPE_READERS = {m: s for s, m in tracing.SCOPES}
SPAN_READERS = {m: s for s, m in tracing.SPANS}


@pytest.fixture
def tiny():
    return programtrace.load_json(DATA / "tiny_trace_program.json")


def obs(tr, pt, *, lanes=(0, 0, 4, 10)):
    o = harness.Observed(tr, lanes, [], 0, "TPU v5 lite", 32, 20)
    o.program = pt
    return o


def test_span_times(tiny):
    tr, pt = tiny
    ns = 1e-9
    # waits on two threads: summed, and united where they overlap; the
    # one running past the close is clipped
    assert programtrace.span_s(tr, pt, "qbs.service.device_wait") == pytest.approx(730 * ns)
    assert programtrace.span_union_s(tr, pt, "qbs.service.device_wait") \
        == pytest.approx(680 * ns)
    assert programtrace.span_max_s(tr, pt, "qbs.gc") == pytest.approx(50 * ns)
    assert programtrace.span_max_s(tr, pt, "qbs.nothing") is None
    # the route span after the close does not count
    assert programtrace.span_count(tr, pt, "qbs.router.route") == 2
    assert programtrace.span_arg_sum(tr, pt, "qbs.service.fetch", "bytes") == 1000


def test_self_time_leaves_out_nested_spans_of_the_same_thread(tiny):
    tr, pt = tiny
    # the collections inside the resolution, on its thread, are not the
    # resolution's own time (one opens with it); the one on the other
    # thread is no child
    assert programtrace.self_s(tr, pt, "qbs.stream.resolve") == pytest.approx(160e-9)
    assert programtrace.span_s(tr, pt, "qbs.stream.resolve") == pytest.approx(200e-9)
    assert programtrace.self_s(tr, pt, "qbs.service.device_wait") \
        == pytest.approx(730e-9)


def test_scope_union_counts_a_while_and_its_body_once(tiny):
    tr, pt = tiny
    ns = 1e-9
    assert programtrace.scope_s(tr, pt, "jit_search_batch", "qbs.recover") \
        == pytest.approx(400 * ns)
    # a transformation's wrapper around the scope's name is seen through
    assert programtrace.scope_s(tr, pt, "jit_search_batch", "qbs.bfs") \
        == pytest.approx(60 * ns)
    # fusion.2 is looked up in the program it ran in
    assert programtrace.scope_s(tr, pt, "jit__landmark_onesided_lanes",
                                "qbs.onesided.bfs") == pytest.approx(200 * ns)
    assert programtrace.scope_s(tr, pt, "jit__landmark_onesided_lanes",
                                "qbs.onesided.certify") == pytest.approx(30 * ns)
    assert programtrace.scope_s(tr, pt, "jit_search_batch", "qbs.sketch") == 0
    # "qbs.bfs" is a whole name, not a part of "qbs.onesided.bfs"
    assert programtrace.scope_s(tr, pt, "jit__landmark_onesided_lanes", "qbs.bfs") == 0


def test_scope_table_drops_an_op_two_programs_disagree_on():
    text = 'HloModule jit_f, x\n  %fusion.1 = f32[] fusion(), metadata={op_name="jit(f)/S/add"}\n'
    a, b = text.replace("S", "qbs.a"), text.replace("S", "qbs.b")
    assert programtrace.scope_table([a, a])[("jit_f", "fusion.1")] == ("jit(f)", "qbs.a", "add")
    assert programtrace.scope_table([a, b])[("jit_f", "fusion.1")] == ()


def test_idle_gaps_named_by_the_innermost_program_span(tiny):
    tr, pt = tiny
    gaps = programtrace.idle_gaps_program(tr, pt)
    # the same gaps as tracereduce.idle_gaps, in the same order
    assert [s for _, s in gaps] == [s for _, s in tracereduce.idle_gaps(tr)]
    assert [n for n, _ in gaps] == [
        "qbs.service.dispatch",
        "qbs.service.device_wait+qbs.stream.resolve",
        "qbs.service.device_wait", "qbs.service.device_wait",
        "qbs.service.device_wait", "qbs.service.device_wait"]
    assert [s for _, s in gaps] == pytest.approx([100e-9, 100e-9, 60e-9, 20e-9, 10e-9, 10e-9])


def test_readers(tiny):
    tr, pt = tiny
    o = obs(tr, pt)
    read = harness.load_reader
    ms = 1e-9 * 1e3
    assert read("general_lane.recover_ms_per_query")(o) == pytest.approx(400 * ms / 10)
    assert read("general_lane.bfs_ms_per_query")(o) == pytest.approx(60 * ms / 10)
    assert read("onesided_lane.bfs_ms_per_query")(o) == pytest.approx(200 * ms / 4)
    assert read("onesided_lane.certify_ms_per_query")(o) == pytest.approx(30 * ms / 4)
    # per chunk: two dispatches start inside the window
    assert read("planner.host_ms_per_chunk")(o) == pytest.approx(20 * ms / 2)
    assert read("dispatch.host_ms_per_chunk")(o) == pytest.approx(80 * ms / 2)
    assert read("fetch.host_ms_per_chunk")(o) == pytest.approx(50 * ms / 2)
    assert read("resolve.host_ms_per_chunk")(o) == pytest.approx(200 * ms / 2)
    assert read("router.host_us_per_query")(o) == pytest.approx(20e-9 * 1e6 / 2)
    assert read("gc.max_pause_ms")(o) == pytest.approx(50 * ms)
    assert read("host.device_wait_share")(o) == pytest.approx(68.0)
    assert read("result_bytes_per_query")(o) == pytest.approx(1000 / 14)


@pytest.mark.parametrize("name", sorted(SCOPE_READERS) + sorted(SPAN_READERS)
                         + ["result_bytes_per_query"])
def test_reader_finds_nothing_returns_none(tiny, name):
    tr, pt = tiny
    read = harness.load_reader(name)
    # no program spans, and scopes that name no phase
    empty = programtrace.ProgramTrace(
        spans=[], scopes=[[(None, ())] * len(ops) for ops in tr.ops])
    assert read(obs(tr, empty)) is None
    # no pairs admitted to the lanes the metric divides by
    if name in SCOPE_READERS or name == "result_bytes_per_query":
        assert read(obs(tr, pt, lanes=(0, 0, 0, 0))) is None
    # the benchmark's own traced run, which records no program spans
    if name not in SCOPE_READERS:
        assert read(harness.Observed(tr, (0, 0, 4, 10), [], 0, "TPU v5 lite",
                                     32, 20)) is None


def test_every_name_is_read():
    """Each span and scope the program emits is listed in ``repro.tracing``
    beside the metric that reads it, and each such metric has a reader:
    the scopes' in ``BENCHMARK.json``, the spans' in ``spans.HOST_METRICS``."""
    emitted = {"span": set(), "scope": set()}
    for path in (ROOT / "src").rglob("*.py"):
        for node in ast.walk(ast.parse(path.read_text())):
            if isinstance(node, ast.Call) and isinstance(node.func, ast.Name) \
                    and node.func.id in emitted and node.args \
                    and isinstance(node.args[0], ast.Constant):
                emitted[node.func.id].add(node.args[0].value)
    assert emitted["span"] == {s for s, _ in tracing.SPANS}
    assert emitted["scope"] == {s for s, _ in tracing.SCOPES}
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    per_layer = {m["name"] for m in bench["per_layer"]}
    assert set(SCOPE_READERS) <= per_layer
    assert set(SPAN_READERS) <= set(spans.HOST_METRICS)
    for name in list(SCOPE_READERS) + list(spans.HOST_METRICS):
        assert callable(harness.load_reader(name))


def test_recorded_run_reads_every_host_metric(monkeypatch, tmp_path):
    """A tiny run of a cell under ``spans.py`` on the CPU: still correct,
    and every host metric reads something (the CPU's ops are no device
    ops, so the scopes read nothing here)."""
    from test_perfbench_runs import SEED, tiny_cell
    import jax

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    keys = ("jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    try:
        out = spans.traced_run(ROOT, tiny_cell("youtube-r20.hub-anchored"), SEED, 2.0,
                               t_start=time.perf_counter(), require_chip=False,
                               grace_s=20.0, log=lambda m: None)
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
    assert out["correct"], out["checks"]
    assert set(spans.HOST_METRICS) - {"gc.max_pause_ms"} <= set(out["program"])
    counts = out["breakdown"]["span_counts"]
    assert {s for s, _ in tracing.SPANS} - {"qbs.gc"} <= set(counts)
    assert len(out["breakdown"]["idle_gaps_program"]) >= 1
