"""The trace reduction and the per-layer metric readers, on a hand-built
trace and on one recorded from the CPU backend."""
from pathlib import Path

import pytest

import harness
import roofline
import tracereduce

DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture
def tiny():
    return tracereduce.load_json(DATA / "tiny_trace.json")


def obs(tr, *, lanes=(0, 0, 4, 10), epochs=1):
    return harness.Observed(tr, lanes, [0.002, 0.004], epochs, "TPU v5 lite", 32, 20)


def test_busy_union_and_window(tiny):
    assert tracereduce.window_s(tiny) == pytest.approx(1e-6)
    # overlapping ops count once; the op running past the close is clipped
    assert tracereduce.busy_s(tiny) == pytest.approx(500e-9)


def test_module_and_op_times(tiny):
    assert tracereduce.module_s(tiny, ["jit_search_batch", "jit__symmetrize"]) \
        == pytest.approx(250e-9)
    assert tracereduce.module_s(tiny, ["jit__landmark_onesided_lanes"]) \
        == pytest.approx(200e-9)
    assert tracereduce.op_events(tiny, "minplus") == [(100, 200)]


def test_breakdown(tiny):
    top = tracereduce.top_ops(tiny)
    assert [n for n, _ in top] == [
        "jit__landmark_onesided_lanes/scatter.7", "jit_search_batch/minplus_kernel.3",
        "jit_search_batch/scatter.7", "jit_search_batch/fusion.1",
        "jit__symmetrize/fusion.1", "jit__build_labelling_arrays/fusion.2"]
    assert tracereduce.op_label("%while.285 = (s32[]) while(x)") == "while.285"
    assert top[0][1] == pytest.approx(200e-9)
    gaps = tracereduce.idle_gaps(tiny)
    assert [n for n, _ in gaps] == ["bench.apply_update", "bench.submit",
                                    "bench.submit", "idle"]
    both = tracereduce.Trace(ops=[[]], modules=[[]], spans=[
        (0, 100, "bench.window"), (0, 100, "bench.submit"),
        (10, 80, "bench.apply_update"), (0, 20, "bench.wait")])
    assert tracereduce.idle_gaps(both) == [["bench.apply_update+bench.submit",
                                            pytest.approx(100e-9)]]
    assert [s for _, s in gaps] == pytest.approx([250e-9, 150e-9, 50e-9, 50e-9])


def test_readers(tiny):
    o = obs(tiny)
    read = harness.load_reader
    assert read("device_idle")(o) == pytest.approx(50.0)
    assert read("admit_wait_ms")(o) == pytest.approx(3.0)
    assert read("general_lane.device_ms_per_query")(o) == pytest.approx(250e-9 * 1e3 / 10)
    assert read("onesided_lane.device_ms_per_query")(o) == pytest.approx(200e-9 * 1e3 / 4)
    assert read("relabel.device_ms_per_epoch")(o) == pytest.approx(50e-9 * 1e3)
    need = roofline.minplus_bytes(32, 20) / 819e9
    assert read("minplus_roofline")(o) == pytest.approx(100 * need / 100e-9)


def test_readers_find_nothing_return_none(tiny):
    o = obs(tiny, lanes=(0, 0, 0, 0), epochs=0)
    for name in ("general_lane.device_ms_per_query",
                 "onesided_lane.device_ms_per_query", "relabel.device_ms_per_epoch"):
        assert harness.load_reader(name)(o) is None
    bare = tracereduce.Trace(ops=[[]], modules=[[]], spans=[(0, 10, "bench.window")])
    assert harness.load_reader("minplus_roofline")(obs(bare)) is None
    assert harness.load_reader("admit_wait_ms")(
        harness.Observed(bare, (0,) * 4, [], 0, "TPU v5 lite", 32, 20)) is None


def test_peaks_table():
    assert roofline.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peak("TPU v9 imaginary")
    assert roofline.minplus_bytes(32, 20) == 4 * (32 * 20 * 2 + 400)


def test_recorded_cpu_trace_parses(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x * 2).sum())
    x = jnp.ones((64,))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(tracereduce.WINDOW_SPAN):
        with jax.profiler.TraceAnnotation("bench.submit"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    (path,) = list(tmp_path.rglob("*.xplane.pb"))
    tr = tracereduce.load_xplane(path)
    names = {n for _, _, n in tr.spans}
    assert {"bench.window", "bench.submit"} <= names
    assert tracereduce.window_s(tr) > 0
