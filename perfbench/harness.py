"""One run of one benchmark cell.

A cell is a deployment (``perfbench/configs/<config>.json``) under a
traffic mix (``perfbench/traffic/<traffic>.json``), both named by the
cell's entry in ``BENCHMARK.json``.  A run

1. makes the deployment's graph with the generator it names, renames its
   vertices by the seed, and builds the index (``index`` holds the
   keyword arguments of ``QbSIndex.build``) and the serving stack
   (``serving``: those of ``ReplicaRouter``);
2. warms every lane the mix's pair kind reaches at the chunk width, and
   the update path where the mix sends updates;
3. sends queries through ``ReplicaRouter.submit`` as the mix's arrival
   kind says: a pre-roll until the arrivals are steady, then the window
   of ``--seconds``, opened at an answer's return, with the writer beside
   it where the mix has one (everything up to the window's opening is
   ``setup_s``), and counts compilations inside the window;
4. waits for every query submitted inside the window (at most
   ``GRACE_S`` past the close), then checks a seeded sample of the
   window's answers against the plain reference (``reference.py``);
5. prints one JSON line.  With ``--trace 1`` the loop runs under the
   profiler and the line carries the per-layer metrics, read by the
   readers in ``perfbench/metrics/``, instead of the end-to-end ones.
"""
from __future__ import annotations

import gc
import json
import math
import os
import shutil
import sys
import tempfile
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

import graphgen
import plugins
import traffic
from reference import RefGraph

HERE = Path(__file__).resolve().parent
GRACE_S = 60.0          # how long past the close a window's query may take
LANES = ("trivial", "landmark_pair", "one_sided", "general")
STUCK_S = 1.0           # no submit and no answer this long: force a flush
CHECK_THREADS = 4       # reference searches run side by side after the window


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: list
    per_layer: list


def load_cell(root: Path, workload: str) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; have {sorted(cells)}")
    w = cells[workload]
    conf = next(c for c in bench["configs"] if c["name"] == w["config"])
    config = json.loads((root / conf["file"]).read_text())
    mix = traffic.load_mix(HERE / "traffic" / f"{w['traffic']}.json")
    plugins.load("generators", config["graph"]["generator"])

    def mine(m):
        return "workloads" not in m or workload in m["workloads"]

    return Cell(workload, int(w["chips"]), config, mix,
                [m for m in bench["end_to_end"] if mine(m)],
                [m for m in bench["per_layer"] if mine(m)])


def require_chips(n: int) -> None:
    import jax

    devs = jax.devices()
    if devs[0].platform == "cpu" or len(devs) < n:
        raise NoChip(f"need {n} accelerator chip(s); JAX found "
                     f"{len(devs)} x {devs[0].platform}")


@dataclass
class Observed:
    """What a per-layer metric reader may read."""

    trace: object                 # tracereduce.Trace of the traced run
    lane_served: tuple            # unique pairs per lane, admitted in window
    admit_waits_s: list           # QoS admission waits added in window
    epochs: int                   # update epochs installed in window
    device_kind: str
    chunk: int
    n_landmarks: int


@dataclass
class Query:
    u: int
    v: int
    t_submit: float
    fut: object
    t_done: float | None = None
    measured: bool = True


@dataclass
class Writer:
    """The update writer: one batch every ``period`` seconds of the window,
    through ``ReplicaRouter.apply_update``; a batch due while the last one
    is still being installed waits for it."""

    router: object
    batches: list
    period: float
    done: list = field(default_factory=list)   # (due, start, end, epoch, info)
    error: list = field(default_factory=list)
    _thread: object = None

    def start(self, t_open: float, t_close: float) -> None:
        self._thread = threading.Thread(target=self._run, args=(t_open, t_close),
                                        name="perfbench-writer")
        self._thread.start()

    def join(self) -> None:
        if self._thread is not None:
            self._thread.join()

    def _run(self, t_open, t_close):
        import jax

        try:
            for k, (ins, dels) in enumerate(self.batches):
                due = t_open + (k + 1) * self.period
                if due >= t_close:
                    return
                _sleep_until(due)
                start = time.perf_counter()
                with jax.profiler.TraceAnnotation("bench.apply_update"):
                    new = self.router.apply_update(inserts=ins, deletes=dels)
                self.done.append((due, start, time.perf_counter(), new.epoch,
                                  dict(new.last_update_info)))
        except Exception as e:     # reported as a failed update
            self.error.append(repr(e))


def warm_pairs(a_pool, b_pool, n: int, rng) -> tuple[np.ndarray, np.ndarray]:
    """``n`` distinct canonical pairs, one end from each pool, no u == v:
    a warm-up batch that fills exactly one chunk of one lane."""
    got: dict[tuple[int, int], None] = {}
    while len(got) < n:
        a, b = int(rng.choice(a_pool)), int(rng.choice(b_pool))
        if a != b:
            got[(min(a, b), max(a, b))] = None
    us, vs = np.asarray(list(got), np.int32).T
    return us, vs


def count_mismatches(answers, versions) -> int:
    """How many ``(u, v, epoch, dist, edge_ids)`` answers differ from the
    reference graph of their epoch (``CHECK_THREADS`` at a time: numpy
    lets go of the interpreter inside each search)."""
    def wrong(answer) -> int:
        u, v, ep, dist, eids = answer
        d, want = versions[ep].spg(u, v)
        return int(dist != d or not np.array_equal(eids, want))

    with ThreadPoolExecutor(CHECK_THREADS) as pool:
        return sum(pool.map(wrong, answers))


def window_rate(returns, done, t_open: float, t_close: float):
    """``(answers, span_s, returns)`` of the window's rate: the answers
    that came back after the window's first return of answers and up to
    its last (``done`` are the answers' return times), over the time
    between those two returns.  Answers come back a chunk at a time, so
    a count over the window's own edges would step by a whole chunk."""
    rets = [r for r in returns if t_open <= r <= t_close]
    if len(rets) < 2 or rets[-1] <= rets[0]:
        return 0, 0.0, len(rets)
    n = sum(1 for t in done if rets[0] < t <= rets[-1])
    return n, rets[-1] - rets[0], len(rets)


def percentile(values, q: float) -> float:
    """Nearest-rank percentile."""
    v = sorted(values)
    return v[max(0, math.ceil(q / 100 * len(v)) - 1)]


def load_reader(name: str):
    return plugins.load("metrics", name).read


class CompileCounter:
    """Records when JAX traced or compiled a program."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.times: list[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event in self.EVENTS:
            self.times.append(time.perf_counter())

    def count_between(self, t0: float, t1: float) -> int:
        return sum(1 for t in self.times if t0 <= t <= t1)

    def close(self) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on)


class Window:
    """Marks the measured window from a thread of its own (the loop's
    thread is blocked inside the service for most of it): the
    ``bench.window`` span for the trace, and the service's lane and
    admission counters at its two ends."""

    def __init__(self, svc):
        self.svc = svc
        self.lane_served: tuple = (0, 0, 0, 0)
        self.waits: list = []
        self._thread = None

    def start(self, t_open: float, t_close: float) -> None:
        self._thread = threading.Thread(target=self._run, args=(t_open, t_close),
                                        name="perfbench-window")
        self._thread.start()

    def _run(self, t_open, t_close):
        import jax

        _sleep_until(t_open)
        lanes0 = list(self.svc.service.lane_served)
        waits = self.svc.qos_stats["default"]["waits"]
        n0 = len(waits)
        with jax.profiler.TraceAnnotation("bench.window"):
            _sleep_until(t_close)
        self.lane_served = tuple(b - a for a, b in
                                 zip(lanes0, self.svc.service.lane_served))
        self.waits = list(waits)[n0:]

    def join(self) -> None:
        if self._thread is not None:
            self._thread.join()


def _collect(pending: list, t: float) -> bool:
    """Stamp ``t`` on every pending query whose answer has come back since
    the last look and drop it from ``pending``; whether any had."""
    got = False
    for q in pending:
        if q.fut.done():
            q.t_done = t
            got = True
    if got:
        pending[:] = [q for q in pending if q.t_done is None]
    return got


def _sleep_until(t: float) -> None:
    while (now := time.perf_counter()) < t:
        time.sleep(min(t - now, 0.05))


def run(root: Path, cell: Cell, seed: int, seconds: float, trace: bool, *,
        t_start: float, require_chip: bool = True, grace_s: float = GRACE_S,
        control: bool = False, log=print) -> dict:
    """One run; returns the result line as a dict.  ``control=True`` also
    puts the control (one shortest path per answer instead of all, from
    the reference, in the program's place) through the same comparison
    and reports its count under ``"control"``; the benchmark's own runs
    never do."""
    setup: dict[str, float] = {}
    import jax

    if require_chip:
        require_chips(cell.chips)
    src = str(root / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    from repro.core import QbSIndex
    from repro.core.graph import from_edges
    from repro.launch.compile_cache import configure_compile_cache
    from repro.serving import ReplicaRouter

    cache_dir = configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    setup["imports_s"] = time.perf_counter() - t_start

    conf, mix = cell.config, cell.mix
    gconf, iconf = conf["graph"], conf["index"]
    n_v = int(gconf["n_vertices"])
    R, chunk = int(iconf["n_landmarks"]), int(iconf["chunk"])

    # one graph structure, its queries and its updates per configuration;
    # the seed renames the vertices (so every seed serves the same work on
    # different inputs), and draws the warm-up, the arrivals' own draws and
    # the sample that is checked
    t = time.perf_counter()
    base_seed = int(gconf["structure_seed"])
    base = graphgen.generate(gconf, base_seed)
    base_top = graphgen.top_degree(base, n_v, R)
    if not graphgen.clear_top(base, n_v, R):
        raise ValueError(f"structure_seed {base_seed}: degree tie at landmark "
                         f"rank {R}, so renaming would change the landmarks")
    name = traffic.rng_for(seed, traffic.STREAM_GRAPH).permutation(n_v)
    edges = name[base]
    top = np.sort(name[base_top])
    g = from_edges(edges, n_v)
    setup["graph_s"] = time.perf_counter() - t

    t = time.perf_counter()
    idx = QbSIndex.build(g, **iconf)
    jax.block_until_ready(idx.packed.label_dist)
    if not np.array_equal(np.asarray(idx.scheme.landmarks), top):
        raise RuntimeError("the program's landmarks are not the top-degree "
                           "vertices the traffic anchors on")
    router = ReplicaRouter(idx, **conf["serving"])
    setup["build_s"] = time.perf_counter() - t

    # warm-up: every lane the mix's pairs can reach, at the chunk width
    # (queries are submitted one at a time, so a flush is one chunk of
    # pending pairs; checked below), and the update path
    t = time.perf_counter()
    wr = traffic.rng_for(seed, traffic.STREAM_WARMUP)
    is_lm = np.zeros(n_v, bool)
    is_lm[top] = True
    non = np.flatnonzero(~is_lm)
    lane_ends = {"general": (non, non), "one_sided": (top, non),
                 "landmark_pair": (top, top)}
    warm = [warm_pairs(*lane_ends[lane], chunk, wr)
            for lane in traffic.pair_lanes(mix["pairs"])]
    upd = mix.get("updates")
    batches = []
    if upd:
        n_b = 2 + int(seconds // float(upd["period_s"]))
        batches = [(name[ins], name[dels]) for ins, dels in traffic.update_batches(
            RefGraph.canonical_keys(base, n_v), n_v, upd, n_b,
            traffic.rng_for(base_seed, traffic.STREAM_UPDATES))]
    for rnd in range(2 if upd else 1):
        for us, vs in warm:
            router.query_batch(us, vs)
        if upd and rnd == 0:
            router.apply_update(inserts=batches[0][0], deletes=batches[0][1])
    if router.replicas[0].chunk != chunk:
        raise RuntimeError(f"warm-up left the admission width at "
                           f"{router.replicas[0].chunk}, not {chunk}")
    setup["warmup_s"] = time.perf_counter() - t

    # the loop: a pre-roll until the arrivals are steady; the window then
    # opens at the latest return of answers, so every run starts its
    # window at the same point of the service's chunk cycle
    pool_u, pool_v = (name[x] for x in traffic.draw_pairs(
        mix["pairs"], n_v, base_top, 1 << 16,
        traffic.rng_for(base_seed, traffic.STREAM_QUERIES)))
    arrivals = traffic.arrivals(mix["arrivals"],
                                traffic.rng_for(seed, traffic.STREAM_ARRIVALS))
    counter = CompileCounter()
    svc = router.replicas[0]
    win = Window(svc)
    writer = Writer(router, batches[1:], float(upd["period_s"])) if upd else None
    tracedir = tempfile.mkdtemp(prefix="perfbench-trace-") if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(tracedir, profiler_options=opts)

    queries: list[Query] = []
    pending: list[Query] = []        # submitted, not answered yet
    returns: list[float] = []        # host times at which answers came back
    nxt = 0
    t_open = t_close = math.inf
    t_loop = t_moved = time.perf_counter()
    while True:
        now = time.perf_counter()
        if t_open == math.inf and returns and \
                arrivals.steady(len(queries), len(returns)):
            t_open = returns[-1]
            t_close = t_open + seconds
            win.start(t_open, t_close)
            if writer is not None:
                writer.start(t_open, t_close)
        if t_open == math.inf and now > t_loop + grace_s:
            raise RuntimeError("the arrivals got no steady answers in a "
                               f"pre-roll of {grace_s:.0f} s")
        if now >= t_close:
            if not any(q.measured for q in pending) or now > t_close + grace_s:
                break
        n_send = max(0, int(arrivals.ready(now, len(pending))))
        for _ in range(n_send):
            u, v = int(pool_u[nxt % pool_u.size]), int(pool_v[nxt % pool_v.size])
            nxt += 1
            t_sub = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.submit"):
                fut = router.submit(u, v)
            t_moved = time.perf_counter()
            q = Query(u, v, t_sub, fut, measured=t_open <= t_sub < t_close)
            queries.append(q)
            pending.append(q)
            if _collect(pending, t_moved):
                returns.append(t_moved)
        now = time.perf_counter()
        if _collect(pending, now):
            returns.append(now)
            t_moved = now
        elif pending and now - t_moved > STUCK_S:
            # every pending query is held back and no arrival is due (a
            # sound service never gets here): force a flush, not a spin
            with jax.profiler.TraceAnnotation("bench.wait"):
                router.drain()
            t_moved = time.perf_counter()
        elif not n_send:
            time.sleep(0.0005)
    if writer is not None:
        writer.join()
    win.join()
    if trace:
        jax.profiler.stop_trace()
    compiles = counter.count_between(t_open, t_close)
    counter.close()

    setup["preroll_s"] = t_open - t_loop
    setup_s = t_open - t_start
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.devices()[:cell.chips])
    measured = [q for q in queries if q.measured]
    answered = [q for q in measured if q.t_done is not None]
    lat_ms = [(q.t_done - q.t_submit) * 1e3 for q in answered]
    in_window, span, n_rets = window_rate(
        returns, [q.t_done for q in queries if q.t_done is not None],
        t_open, t_close)
    results = {id(q): (q.fut.result(), q.fut.epoch) for q in answered}
    served, waits = win.lane_served, win.waits
    update_log = list(writer.done) if writer else []
    del router, svc, idx, g, counter
    for q in queries:
        q.fut = None
    gc.collect()

    # correctness: a seeded sample of the window's answers
    t = time.perf_counter()
    cr = traffic.rng_for(seed, traffic.STREAM_CHECK)
    n_check = min(int(conf["check_sample"]), len(answered))
    pick = cr.choice(len(answered), n_check, replace=False) if n_check else []
    versions = {0: RefGraph(edges, n_v, memo_roots=top)}
    if upd:
        versions[1] = versions[0].updated(*batches[0])
        for due, _, _, ep, _ in update_log:
            b = batches[ep - 1]
            versions[ep] = versions[ep - 1].updated(*b)
    answers = []
    for i in pick:
        res, ep = results[id(answered[i])]
        answers.append((res.u, res.v, ep, res.dist, np.asarray(res.edge_ids)))
    answers.sort(key=lambda a: (a[2], a[0]))
    mismatched = count_mismatches(answers, versions)
    epochs_checked = {a[2] for a in answers}
    if control:
        ctl = [(u, v, ep) + versions[ep].one_path(u, v) for u, v, ep, _, _ in answers]
        control_mismatched = count_mismatches(ctl, versions)
    check_s = time.perf_counter() - t

    unanswered = len(measured) - len(answered)
    failed_updates = len(writer.error) if writer else 0
    checks = {
        "mismatched_answers": {"value": mismatched, "limit": 0},
        "unanswered_queries": {"value": unanswered, "limit": 0},
        "failed_updates": {"value": failed_updates, "limit": 0},
        "compiles_in_window": {"value": compiles, "limit": 0},
    }
    correct = all(c["value"] <= c["limit"] for c in checks.values()) and n_check > 0

    e2e = {}
    if span > 0:
        e2e["spg_qps"] = (in_window / span, "queries/s")
    if lat_ms:
        e2e["spg_p95_ms"] = (percentile(lat_ms, 95), "ms")
    if update_log:
        adv = [(end - due) * 1e3 for due, _, end, _, _ in update_log]
        e2e["epoch_advance_ms"] = (sum(adv) / len(adv), "ms")
    e2e["setup_s"] = (setup_s, "s")

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": int(peak)}
    out = {"correct": bool(correct), "attempted": len(measured) + len(update_log),
           "failed": unanswered + failed_updates}
    breakdown = None
    if trace:
        import tracereduce

        files = list(Path(tracedir).rglob("*.xplane.pb"))
        tr = tracereduce.load_xplane(files[0])
        shutil.rmtree(tracedir, ignore_errors=True)
        obs = Observed(tr, served, waits, len(update_log), dev.device_kind,
                       chunk, R)
        metrics = {}
        for m in cell.per_layer:
            val = load_reader(m["name"])(obs)
            if val is not None:
                metrics[m["name"]] = {"value": val, "unit": m["unit"]}
        device["busy_s"] = tracereduce.busy_s(tr)
        device["window_s"] = tracereduce.window_s(tr)
        breakdown = {"device_ops": tracereduce.top_ops(tr),
                     "idle_gaps": tracereduce.idle_gaps(tr)}
    else:
        metrics = {m["name"]: {"value": e2e[m["name"]][0], "unit": m["unit"]}
                   for m in cell.end_to_end if m["name"] in e2e}
    out["metrics"] = metrics
    out["device"] = device
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["load"] = {
        "queries_submitted": len(queries), "answered_in_window": in_window,
        "rate_span_s": span, "returns_in_window": n_rets,
        "p50_ms": percentile(lat_ms, 50) if lat_ms else None,
        "lanes_admitted": dict(zip(LANES, served)),
        "updates": [{"late_ms": (start - due) * 1e3,
                     "advance_ms": (end - due) * 1e3, "epoch": ep,
                     "full_rebuild": bool(info.get("full_rebuild")),
                     "n_affected": int(info.get("n_affected", 0))}
                    for due, start, end, ep, info in update_log],
        "update_errors": writer.error if writer else [],
        "checked": n_check, "epochs_checked": sorted(epochs_checked),
        "check_s": check_s, "setup_parts_s": setup, "seed": seed,
        "preroll_returns_s": [r - t_loop for r in returns if r <= t_open],
        "compile_cache": cache_dir,
    }
    if control:
        out["control"] = {"mismatched_answers": control_mismatched}
    out["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    return out


def main(argv, t_start: float) -> int:
    import argparse

    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    root = HERE.parent
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    cell = load_cell(root, args.workload)
    err = lambda msg: print(msg, file=sys.stderr, flush=True)  # noqa: E731
    try:
        out = run(root, cell, args.seed, args.seconds, bool(args.trace),
                  t_start=t_start, log=err)
    except NoChip as e:
        err(f"perfbench: {e}; no result")
        return 2
    print(json.dumps(out), flush=True)
    return 0
