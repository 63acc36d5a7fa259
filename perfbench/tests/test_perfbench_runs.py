"""Whole runs of the harness on the CPU at a tiny size: a sound program is
``correct``; the control and each fault the cells can have are not.  The
look for a chip is skipped (``require_chip=False``); everything else is
the run the chip gets."""
import copy
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

import graphgen
import harness
import plugins

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
SEED = 2**31 + 17


@pytest.fixture
def isolated_jax(monkeypatch, tmp_path):
    """Keep the run's JAX settings to this test: the compile cache goes to
    a temporary directory and the cache thresholds are restored."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    keys = ("jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


# an update writer beside the uniform mix: the update path the harness
# drives where a mix has ``updates`` (no cell sends updates yet)
UPDATES = {"period_s": 2, "inserts": 6, "deletes": 6}


def tiny_cell(workload: str, n_vertices=600, n_edges=900, updates=None):
    """The cell at a tiny size, on a structure whose landmark set is clear
    of degree ties; ``updates`` adds a writer to its mix."""
    cell = harness.load_cell(ROOT, workload)
    cell.config = copy.deepcopy(cell.config)
    g = cell.config["graph"]
    g.update(n_vertices=n_vertices, n_edges=n_edges)
    R = cell.config["index"]["n_landmarks"]
    g["structure_seed"] = next(s for s in range(1, 100) if graphgen.clear_top(
        graphgen.generate(g, s), n_vertices, R))
    cell.config["check_sample"] = 24
    if updates:
        cell.mix = {**cell.mix, "updates": updates}
    return cell


def run(cell, seconds=2.0, **kw):
    return harness.run(ROOT, cell, SEED, seconds, False, t_start=time.perf_counter(),
                       require_chip=False, grace_s=20.0, log=lambda m: None, **kw)


@pytest.mark.parametrize("workload,updates", [
    ("douban-r20.uniform", None), ("youtube-r20.hub-anchored", None),
    ("douban-r20.uniform", UPDATES)])
def test_sound_run_is_correct(isolated_jax, workload, updates):
    seconds = 6.0 if updates else 2.0
    out = run(tiny_cell(workload, updates=updates), seconds=seconds, control=True)
    assert out["correct"], out["checks"]
    assert out["load"]["checked"] == 24
    assert set(out["metrics"]) >= {"spg_qps", "spg_p95_ms", "setup_s"}
    assert out["load"]["returns_in_window"] >= 2
    assert out["control"]["mismatched_answers"] > 0      # the control fails
    assert list(out)[-1] == "checks"
    if updates:
        assert max(out["load"]["epochs_checked"]) >= 2
        assert len(out["load"]["updates"]) >= 2


def _alter_one_slot(orig):
    def step(self, *args):
        d, m = orig(self, *args)
        return d, m.at[:, 0].set(~m[:, 0])
    return step


def _drop_half(orig):
    def step(self, *args):
        d, m = orig(self, *args)
        half = d.shape[0] // 2
        return d.at[half:].set(1 << 20), m.at[half:].set(False)
    return step


@pytest.mark.parametrize("fault", [_alter_one_slot, _drop_half])
@pytest.mark.parametrize("workload,step", [
    ("douban-r20.uniform", "serve_step"),
    ("youtube-r20.hub-anchored", "landmark_onesided_step"),
])
def test_fault_in_the_timed_path_is_caught(isolated_jax, monkeypatch, workload,
                                           step, fault):
    from repro.core.qbs import QbSIndex

    monkeypatch.setattr(QbSIndex, step, fault(getattr(QbSIndex, step)))
    out = run(tiny_cell(workload))
    assert not out["correct"]
    assert out["checks"]["mismatched_answers"]["value"] > 0


def test_update_that_leaves_the_state_unchanged_is_caught(isolated_jax, monkeypatch):
    from repro.core.qbs import QbSIndex

    def stale(self, inserts=None, deletes=None, **kw):
        new = QbSIndex(self.graph, self.scheme, max_levels=self.max_levels,
                       max_chain=self.max_chain, chunk=self.chunk,
                       use_pallas=self.use_pallas, backend=self.backend,
                       epoch=self.epoch + 1, lm_dist=self._lm_dist_host,
                       packed=self.packed)
        new.last_update_info = {}
        return new

    monkeypatch.setattr(QbSIndex, "apply_update", stale)
    out = run(tiny_cell("douban-r20.uniform", updates=UPDATES), seconds=6.0)
    assert not out["correct"]
    assert out["checks"]["mismatched_answers"]["value"] > 0


def test_no_chip_no_result(isolated_jax, capsys):
    assert jax.default_backend() == "cpu"
    rc = harness.main(["--workload", "douban-r20.uniform", "--seed", str(SEED),
                       "--seconds", "1", "--trace", "0"], time.perf_counter())
    assert rc != 0
    assert capsys.readouterr().out == ""


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", "douban-r20.uniform",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout == ""


def test_benchmark_json_names_files_that_exist():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        conf = json.loads((ROOT / c["file"]).read_text())
        assert conf["name"] == c["name"] and conf["reduced"] == c["reduced"]
        plugins.load("generators", conf["graph"]["generator"])
    for w in bench["workloads"]:
        cell = harness.load_cell(ROOT, w["name"])     # looks up every kind
        assert cell.chips == w["chips"]
    for m in bench["per_layer"]:
        assert callable(harness.load_reader(m["name"]))
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    assert all(m["moves"] in e2e for m in bench["per_layer"])


def test_percentile_is_nearest_rank():
    assert harness.percentile(range(1, 101), 95) == 95
    assert harness.percentile([3.0], 95) == 3.0
    assert harness.percentile(jnp.arange(20).tolist(), 95) == 18


def test_answer_that_never_comes_is_caught(isolated_jax, monkeypatch):
    from repro.serving.stream import QueryFuture

    orig = QueryFuture._resolve
    n = {"resolved": 0}

    def lose_some(self, dist, eids, d_top):
        n["resolved"] += 1
        # past the warm-up, every seventh query is never answered
        if n["resolved"] < 200 or self.u % 7 != 0:
            orig(self, dist, eids, d_top)

    monkeypatch.setattr(QueryFuture, "_resolve", lose_some)
    out = harness.run(ROOT, tiny_cell("douban-r20.uniform"), SEED, 2.0, False,
                      t_start=time.perf_counter(), require_chip=False,
                      grace_s=3.0, log=lambda m: None)
    assert not out["correct"]
    assert out["checks"]["unanswered_queries"]["value"] > 0


def test_update_that_fails_is_caught(isolated_jax, monkeypatch):
    from repro.core.qbs import QbSIndex

    orig = QbSIndex.apply_update

    def fail_after_warmup(self, *a, **kw):
        if self.epoch >= 1:
            raise RuntimeError("update lost")
        return orig(self, *a, **kw)

    monkeypatch.setattr(QbSIndex, "apply_update", fail_after_warmup)
    out = run(tiny_cell("douban-r20.uniform", updates=UPDATES), seconds=6.0)
    assert not out["correct"]
    assert out["checks"]["failed_updates"]["value"] > 0


def test_compile_inside_the_window_is_caught(isolated_jax, monkeypatch):
    from repro.core.qbs import QbSIndex

    orig = QbSIndex.serve_step
    n = {"calls": 0}

    def recompiling(self, us, vs):
        n["calls"] += 1
        if n["calls"] > 4:                # past the warm-up: a new program
            jax.jit(lambda x: x + n["calls"])(us).block_until_ready()
        return orig(self, us, vs)

    monkeypatch.setattr(QbSIndex, "serve_step", recompiling)
    out = run(tiny_cell("douban-r20.uniform"))
    assert not out["correct"]
    assert out["checks"]["compiles_in_window"]["value"] > 0


@pytest.mark.parametrize("part,edit", [
    ("generators", lambda cell: cell.config["graph"].update(generator="rmat")),
    ("pairs", lambda cell: cell.mix["pairs"].update(kind="hub_repeat")),
    ("arrivals", lambda cell: cell.mix["arrivals"].update(kind="poisson")),
])
def test_unknown_part_is_refused(isolated_jax, part, edit):
    cell = tiny_cell("douban-r20.uniform")
    cell.mix = copy.deepcopy(cell.mix)
    edit(cell)
    with pytest.raises(ValueError, match=f"unknown {part} part"):
        run(cell)


def test_serving_keys_reach_the_router(isolated_jax, monkeypatch):
    from repro.serving import ReplicaRouter

    seen = {}
    orig = ReplicaRouter.__init__

    def spy(self, index, **kw):
        seen.update(kw)
        orig(self, index, **kw)

    monkeypatch.setattr(ReplicaRouter, "__init__", spy)
    cell = tiny_cell("douban-r20.uniform")
    cell.config["serving"]["async_depth"] = 1
    out = run(cell)
    assert out["correct"], out["checks"]
    assert seen == cell.config["serving"]


def test_window_rate_does_not_step_by_a_chunk():
    # answers return 32 at a time, every 2 s; a window of 9 s holds four
    # or five returns depending on where it opens, yet the rate is 16/s
    for t_open in (0.0, 0.5, 1.5):
        returns = [2.0 * k for k in range(12)]
        done = [r for r in returns for _ in range(32)]
        opened = [r for r in returns if r >= t_open][0]
        n, span, _ = harness.window_rate(returns, done, opened, opened + 9.0)
        assert n / span == pytest.approx(16.0)
    assert harness.window_rate([1.0], [1.0] * 32, 0.0, 9.0)[1] == 0.0
