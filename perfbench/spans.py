"""Run one benchmark cell once under the profiler, with the program's own
spans, and print one JSON line.

    python3 perfbench/spans.py --workload youtube-r20.hub-anchored \
        --seed 7 --seconds 51

The run is the benchmark's (``harness.run``), whole: its end-to-end
metrics are measured while the profiler records and the collector hook
(``repro.tracing.gc_spans``) is in, so set beside an untraced run of the
same seed they give what tracing costs.  The line adds ``program``: the
host metrics that read the program's spans (``HOST_METRICS``), the
cell's per-layer metrics, and ``breakdown``: the device ops and idle
gaps, the gaps named by the innermost program span, the lane programs'
device time by scope, each program span's own time in the window
(``self_ms``: less the spans nested in it on its thread), and the
longest program spans of each name (start in s from the window's
opening, length in ms).

The benchmark's own ``--trace 1`` run reads the scopes; these host
metrics need the program's spans in its reduction (``PERF.md``, open
questions).
"""
import time

T_START = time.perf_counter()

import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from collections import Counter  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import harness  # noqa: E402
import programtrace  # noqa: E402
import tracereduce  # noqa: E402

# metric -> unit: the readers in metrics/ that need the program's spans
HOST_METRICS = {
    "router.host_us_per_query": "us",
    "planner.host_ms_per_chunk": "ms",
    "dispatch.host_ms_per_chunk": "ms",
    "fetch.host_ms_per_chunk": "ms",
    "resolve.host_ms_per_chunk": "ms",
    "gc.max_pause_ms": "ms",
    "host.device_wait_share": "%",
    "result_bytes_per_query": "bytes",
}
LANE_MODULES = ("jit_search_batch", "jit__symmetrize", "jit__landmark_onesided_lanes")


def scope_cover(tr, pt, module: str) -> dict:
    """``module``'s device ms in the window, the ms of its ops under any
    ``qbs.*`` scope, its ops with most time, each named
    ``<op>@<innermost qbs scope>``, and its unscoped ops with most time."""
    lo, hi = tr.window
    scoped, ops_ms, rest = [], Counter(), Counter()
    for ops, got in zip(tr.ops, pt.scopes):
        for (s, d, n), (mod, path) in zip(ops, got):
            a, b = max(s, lo), min(s + d, hi)
            if mod != module or b <= a:
                continue
            names = [p for p in path if p.startswith(programtrace.PREFIX)]
            op = tracereduce.op_label(n)
            if names:
                scoped.append((a, b))
                ops_ms[f"{op}@{names[-1]}"] += (b - a) / 1e6
            else:
                rest[op] += (b - a) / 1e6
    return {"module_ms": tracereduce.module_s(tr, [module]) * 1e3,
            "scoped_ms": sum(b - a for a, b in tracereduce._union(scoped)) / 1e6,
            "top_ms": ops_ms.most_common(10),
            "unscoped_top_ms": rest.most_common(5)}


def longest(tr, pt, k: int = 5) -> dict:
    lo, hi = tr.window
    out: dict = {}
    for s, d, n, _, _ in pt.spans:
        if lo <= s < hi:
            out.setdefault(n, []).append([(s - lo) / 1e9, d / 1e6])
    return {n: sorted(v, key=lambda x: -x[1])[:k] for n, v in sorted(out.items())}


def traced_run(root: Path, cell, seed: int, seconds: float, *, t_start: float,
               **run_kw) -> dict:
    """``harness.run`` under the profiler with the collector hook in; its
    result line with ``program`` and ``breakdown`` added."""
    import jax

    if str(root / "src") not in sys.path:
        sys.path.insert(0, str(root / "src"))
    from repro import tracing

    tracedir = tempfile.mkdtemp(prefix="perfbench-spans-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(tracedir, profiler_options=opts)
    try:
        with tracing.gc_spans():
            out = harness.run(root, cell, seed, seconds, False, t_start=t_start,
                              **run_kw)
    finally:
        jax.profiler.stop_trace()
    (path,) = list(Path(tracedir).rglob("*.xplane.pb"))
    tr = tracereduce.load_xplane(path)
    pt = programtrace.load_xplane(path, tr)
    shutil.rmtree(tracedir, ignore_errors=True)
    lanes = out["load"]["lanes_admitted"]
    index = cell.config["index"]
    obs = harness.Observed(tr, tuple(lanes[x] for x in harness.LANES), [], 0,
                           out["device"]["kind"], int(index["chunk"]),
                           int(index["n_landmarks"]))
    obs.program = pt
    units = dict(HOST_METRICS, **{m["name"]: m["unit"] for m in cell.per_layer})
    program = {}
    for name, unit in units.items():
        val = harness.load_reader(name)(obs)
        if val is not None:
            program[name] = {"value": val, "unit": unit}
    lo, hi = tr.window
    out["program"] = program
    out["breakdown"] = {
        "device_ops": tracereduce.top_ops(tr),
        "idle_gaps": tracereduce.idle_gaps(tr),
        "idle_gaps_program": programtrace.idle_gaps_program(tr, pt),
        "scope_cover": {m: scope_cover(tr, pt, m) for m in LANE_MODULES
                        if tracereduce.module_s(tr, [m]) > 0},
        "longest_spans": longest(tr, pt),
        "self_ms": {n: programtrace.self_s(tr, pt, n) * 1e3
                    for n in sorted({n for _, _, n, _, _ in pt.spans})},
        "span_counts": dict(Counter(n for s, _, n, _, _ in pt.spans if lo <= s < hi)),
    }
    return out


def main(argv) -> int:
    import argparse
    import os

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    err = lambda msg: print(msg, file=sys.stderr, flush=True)  # noqa: E731
    root = HERE.parent
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    cell = harness.load_cell(root, args.workload)
    try:
        harness.require_chips(cell.chips)
    except harness.NoChip as e:
        err(f"perfbench: {e}; no result")
        return 2
    out = traced_run(root, cell, args.seed, args.seconds, t_start=T_START, log=err)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
