"""Dynamic-graph maintenance bench: incremental label updates vs rebuild.

Replays an interleaved update+query trace against one ``QbSIndex``:
single-edge inserts and deletes alternate (each advancing the epoch
through ``QbSIndex.apply_update``), with a query batch resolved between
updates so the measured index is always serving-warm.  Every few updates
the same event is also applied through the forced full-rebuild branch
(``churn_threshold=0``) — the honest baseline, since it pays everything a
servable index needs (labelling BFS, landmark-distance table, repacking)
and produces a bit-identical ``PackedLabels``.

The acceptance metric is ``update_speedup`` = rebuild-median over
update-median; the bench gate holds it above an absolute floor
(``--update-speedup-floor``, default 5) rather than a relative threshold
— the ratio normalizes machine speed out, like ``roofline_frac``.

Warmup discipline matters here: the first update takes the CSR's first
edge-capacity step (build packs slots exactly), and the update programs
compile once per capacity — so the bench takes that step first, warms
the update path there (``QbSIndex.warm_update_path``), and only then
starts the clock.
"""
from __future__ import annotations

import json
import time
from pathlib import Path

import numpy as np

from repro.core import QbSIndex, barabasi_albert_graph
from repro.core.graph import edge_set

REPO = Path(__file__).resolve().parents[1]
BENCH_PATH = REPO / "BENCH.json"

N_UPDATES = 16        # timed single-edge updates (inserts/deletes alternate)
REBUILD_EVERY = 3     # every third event also times the full-rebuild branch
R_LANDMARKS = 64
CHURN = 0.5


def _block(index: QbSIndex) -> None:
    import jax

    jax.block_until_ready(index.packed.label_dist)


def run(scale: float = 1.0, **_) -> list[tuple]:
    v = max(3_000, int(48_000 * scale))
    r = min(R_LANDMARKS, max(8, v // 128))
    rng = np.random.default_rng(5)
    g = barabasi_albert_graph(v, 4, seed=17)
    index = QbSIndex.build(g, n_landmarks=r, chunk=16)

    def rand_absent(cur: QbSIndex) -> tuple[int, int]:
        present = {tuple(e) for e in edge_set(cur.graph)}
        while True:
            a, b = rng.integers(0, cur.graph.n_vertices, 2)
            if a != b and (min(a, b), max(a, b)) not in present:
                return (int(a), int(b))

    def rand_present(cur: QbSIndex) -> tuple[int, int]:
        es = edge_set(cur.graph)
        return tuple(int(x) for x in es[rng.integers(0, len(es))])

    # capacity-stabilizing update (the edge slots take one step, then
    # hold), then warm the update programs and both terminal branches
    cur = index.apply_update(inserts=[rand_absent(index)],
                             churn_threshold=CHURN)
    cur.warm_update_path(CHURN)
    _block(cur.apply_update(inserts=[rand_absent(cur)], churn_threshold=0.0))
    # Small query legs: random pairs at this V are SPG-expensive (the
    # (N, E) edge-mask recover path), so the trace interleaves a few
    # queries rather than a throughput batch — serving-rate benches own
    # that axis (serving_throughput / trace_replay).
    us, vs = (rng.integers(0, v, 8).astype(np.int32) for _ in range(2))
    cur.query_batch_arrays(us, vs)          # warm the serving program

    upd_t = {"insert": [], "delete": []}
    reb_t, affected, q_t = [], [], []
    for i in range(N_UPDATES):
        op = "insert" if i % 2 == 0 else "delete"
        edge = rand_absent(cur) if op == "insert" else rand_present(cur)
        ev = {f"{op}s": [edge]}
        t0 = time.perf_counter()
        nxt = cur.apply_update(**ev, churn_threshold=CHURN)
        _block(nxt)
        upd_t[op].append(time.perf_counter() - t0)
        affected.append(nxt.last_update_info["n_affected"])
        if i % REBUILD_EVERY == 0:
            t0 = time.perf_counter()
            reb = cur.apply_update(**ev, churn_threshold=0.0)
            _block(reb)
            reb_t.append(time.perf_counter() - t0)
        cur = nxt
        if i % 4 == 3:
            t0 = time.perf_counter()
            cur.query_batch_arrays(us, vs)  # epoch-fresh index keeps serving
            q_t.append(time.perf_counter() - t0)

    ins_med = float(np.median(upd_t["insert"]) * 1e6)
    del_med = float(np.median(upd_t["delete"]) * 1e6)
    upd_med = float(np.median(upd_t["insert"] + upd_t["delete"]) * 1e6)
    reb_med = float(np.median(reb_t) * 1e6)
    q_med = float(np.median(q_t) * 1e6)
    speedup = reb_med / max(upd_med, 1e-9)
    aff_med = float(np.median(affected))

    graph = "ba-hub"
    ident = {"graph": graph, "V": v, "R": r}
    rows_json = [
        {**ident, "op": "insert", "us_per_call": ins_med},
        {**ident, "op": "delete", "us_per_call": del_med},
        {**ident, "op": "rebuild", "us_per_call": reb_med},
        {**ident, "op": "query_between_updates", "us_per_call": q_med},
        {**ident, "op": "speedup", "update_speedup": float(speedup),
         "affected_med": aff_med},
    ]
    record = {"bench": "graph_updates", "ts": time.time(), "scale": scale,
              "rows": rows_json}
    with BENCH_PATH.open("a") as f:
        f.write(json.dumps(record) + "\n")

    derived = f"V={v};R={r};affected_med={aff_med:.0f}"
    return [
        (f"graph_updates/insert/{graph}", ins_med, derived),
        (f"graph_updates/delete/{graph}", del_med, derived),
        (f"graph_updates/rebuild/{graph}", reb_med, derived),
        (f"graph_updates/query/{graph}", q_med, f"epochs={cur.epoch}"),
        (f"graph_updates/speedup/{graph}", upd_med,
         f"update_speedup={speedup:.1f}x"),
    ]


if __name__ == "__main__":
    from .common import emit

    emit(run(scale=0.25))
