"""Smoke run of the QbS serving path on a TPU.

Builds a real index on the chip, answers queries in all four planner lanes
through the entry points a user calls (``QbSIndex.query_batch`` and a
one-replica ``ReplicaRouter`` of ``StreamingService``s, as
``repro.launch.serve`` does), advances one update epoch, and checks a
sample of every lane at both epochs bit-for-bit against the numpy oracle
(``tests/helpers/serving_oracle.py``).

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # only the multi-chip paths
    python chip_smoke.py --chips 4 --vertices 275000   # a smaller graph

The graph is ``barabasi_albert_graph(1_100_000, 3, seed=0)``: the size of
the ``youtube`` row of the paper's Table 1 (``configs/qbs_graphs.py``),
with R=20 landmarks (``--vertices`` changes its size).  ``--chips 4`` builds the vertex-sharded index over
four chips and the batch-sharded general lane, and checks both against
the replicated one-chip index built in the same process.

Where JAX finds no TPU the script exits non-zero and prints no result.
The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Every time printed before it is a measurement of this run on the device
that line names.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
N_VERTICES = 1_100_000
N_LANDMARKS = 20
CHUNK = 32


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check(ok, what) -> None:
    """A failed check ends the run (kept under ``python -O``)."""
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def _import_paths() -> None:
    for p in (ROOT, ROOT / "src"):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))


def pick_queries(is_landmark: np.ndarray, rng, *, n_general: int,
                 n_pair: int, n_onesided: int, n_trivial: int):
    """Query pairs for each planner lane: ``(us, vs, lanes)`` with lanes in
    ``serving.planner``'s numbering (0 trivial, 1 landmark pair, 2 one-sided,
    3 general)."""
    lms = np.flatnonzero(is_landmark)
    non = np.flatnonzero(~is_landmark)

    def distinct(pool, n):
        u = rng.choice(pool, n)
        v = rng.choice(pool, n)
        clash = u == v
        v[clash] = pool[(np.searchsorted(pool, v[clash]) + 1) % pool.size]
        return u, v

    gu, gv = distinct(non, n_general)
    pu, pv = distinct(lms, n_pair)
    ou, ov = rng.choice(non, n_onesided), rng.choice(lms, n_onesided)
    flip = rng.random(n_onesided) < 0.5
    ou, ov = np.where(flip, ov, ou), np.where(flip, ou, ov)
    t = rng.choice(non, n_trivial)
    us = np.concatenate([t, pu, ou, gu]).astype(np.int32)
    vs = np.concatenate([t, pv, ov, gv]).astype(np.int32)
    lanes = np.repeat(np.arange(4), [n_trivial, n_pair, n_onesided, n_general])
    return us, vs, lanes


def assert_same(got, want, what: str) -> None:
    """Bit-identity of two result lists on (u, v, dist, edge_ids)."""
    check(len(got) == len(want), what)
    for a, b in zip(got, want):
        check((a.u, a.v, a.dist) == (b.u, b.v, b.dist), (what, a.u, a.v))
        check(np.array_equal(a.edge_ids, b.edge_ids), (what, a.u, a.v))


def check_oracle(oracle, results, rows, epoch: int) -> int:
    """Check ``results[rows]`` against the oracle at ``epoch``."""
    for k in rows:
        r = results[k]
        d, eids = oracle.spg(r.u, r.v, epoch)
        check(r.dist == d, ("dist", epoch, r.u, r.v, r.dist, d))
        check(np.array_equal(r.edge_ids, eids), ("edges", epoch, r.u, r.v))
    return len(rows)


def sample_rows(lanes: np.ndarray, per_lane: int, rng) -> np.ndarray:
    return np.concatenate([
        rng.choice(np.flatnonzero(lanes == k), per_lane, replace=False)
        for k in range(4)])


def update_batch(graph, results, lanes, rng, *, n_edges: int, n_touch: int):
    """One update epoch: ``n_edges`` deletes of present edges and as many
    inserts of absent ones (so the edge-slot capacity, and with it every
    compiled program, is kept).  The first ``n_touch`` general queries get
    a shortcut u-v insert or lose one edge of their answer, so the epoch
    changes answers that are then checked.  Returns (inserts, deletes,
    touched rows)."""
    src = np.asarray(graph.src, np.int64)
    dst = np.asarray(graph.dst, np.int64)
    n = graph.n_vertices
    present = set((src[src < dst] * n + dst[src < dst]).tolist())
    key = lambda a, b: min(a, b) * n + max(a, b)  # noqa: E731
    ins, dels, touched = [], [], []
    for k in np.flatnonzero(lanes == 3)[:n_touch]:
        r = results[k]
        if len(touched) % 2 == 0 and r.dist > 1 and \
                key(r.u, r.v) not in present:
            ins.append(key(r.u, r.v))
        else:
            e = int(r.edge_ids[0])
            dels.append(key(int(src[e]), int(dst[e])))
        touched.append(k)
    real = np.flatnonzero(src < dst)
    while len(dels) < n_edges:
        e = int(rng.choice(real))
        k = key(int(src[e]), int(dst[e]))
        if k not in dels:
            dels.append(k)
    while len(ins) < n_edges:
        a, b = (int(x) for x in rng.integers(0, n, 2))
        k = key(a, b)
        if a != b and k not in present and k not in ins:
            ins.append(k)
    as_pairs = lambda ks: np.stack([np.asarray(ks) // n,  # noqa: E731
                                    np.asarray(ks) % n], axis=1)
    return as_pairs(ins), as_pairs(dels), np.asarray(touched)


def _block(x):
    import jax
    return jax.block_until_ready(x)


def run_one_chip(n: int = N_VERTICES, *, seed: int = 0, n_general: int = 32,
                 n_pair: int = 64, n_onesided: int = 64, n_trivial: int = 32,
                 per_lane: int = 2, n_hybrid: int = 16) -> dict:
    """The one-chip phases.  On a TPU they also require the compiled Pallas
    kernels (a ``tpu_custom_call`` in the general lane, Pallas in the
    hybrid relay); on any other backend the same phases run interpreted.

    A general-lane chunk is the costly step at this size, so each epoch
    sends one chunk of general queries (``n_general`` = ``CHUNK``) beside
    the cheaper landmark lanes, and the hybrid relay is driven through
    its labelling build and the landmark lanes only."""
    _import_paths()
    import jax

    from repro.core import QbSIndex, barabasi_albert_graph
    from repro.serving import LANE_NAMES, AdmissionPolicy, ReplicaRouter
    from tests.helpers.serving_oracle import EpochOracle

    on_chip = jax.default_backend() == "tpu"
    rng = np.random.default_rng(seed)
    report: dict = {}

    t = time.perf_counter()
    g = barabasi_albert_graph(n, 3, seed=seed)
    report["graph_s"] = time.perf_counter() - t
    log(f"graph ba V={g.n_vertices} E_slots={g.n_edges} in "
        f"{report['graph_s']:.3f}s (host)")

    t = time.perf_counter()
    idx = QbSIndex.build(g, n_landmarks=N_LANDMARKS, chunk=CHUNK)
    _block(idx.packed.label_dist)
    report["build_s"] = time.perf_counter() - t
    log(f"index built in {report['build_s']:.3f}s (compile included): "
        f"R={idx.scheme.n_landmarks} pack={idx.packed.label_dist.dtype} "
        f"backend={idx.backend} use_pallas={idx.use_pallas}")

    us, vs, lanes = pick_queries(idx._is_landmark_np, rng, n_general=n_general,
                                 n_pair=n_pair, n_onesided=n_onesided,
                                 n_trivial=n_trivial)
    # the general-lane program, compiled ahead of its first chunk (the
    # persistent cache then serves query_batch the same executable)
    t = time.perf_counter()
    text = idx._search_batch.lower(
        idx.ctx, idx.packed.label_dist, idx.packed.meta_w,
        idx.packed.meta_dist, us[:CHUNK], vs[:CHUNK]).compile().as_text()
    report["general_compile_s"] = time.perf_counter() - t
    has_kernel = "tpu_custom_call" in text
    log(f"general-lane program compiled in {report['general_compile_s']:.3f}s;"
        f" holds the Pallas kernel (tpu_custom_call): {has_kernel}")
    if on_chip:
        check(has_kernel, "general lane without the kernel")

    t = time.perf_counter()
    res = idx.query_batch(us, vs)
    report["query_batch_s"] = time.perf_counter() - t
    served = idx._default_service().lane_served
    log(f"query_batch: {len(us)} queries in {report['query_batch_s']:.3f}s "
        f"(other lanes' compiles included); per lane "
        f"{dict(zip(LANE_NAMES, np.bincount(lanes, minlength=4).tolist()))}; "
        f"service lane counters {dict(zip(LANE_NAMES, served))}")
    check(all(c > 0 for c in served), served)
    check(all(r.dist < (1 << 20) for r in res), "BA graph is connected")

    oracle = EpochOracle(g)
    rows0 = sample_rows(lanes, per_lane, rng)
    t = time.perf_counter()
    n0 = check_oracle(oracle, res, rows0, 0)
    log(f"epoch 0: {n0}/{n0} sampled answers match the oracle "
        f"({per_lane} per lane; oracle {time.perf_counter() - t:.3f}s host)")

    # the hybrid relay: the only path through bitmap_expand_packed, which
    # its labelling BFSs and the one-sided lane's full-graph BFS run
    t = time.perf_counter()
    idx_h = QbSIndex.build(g, landmarks=np.asarray(idx.scheme.landmarks),
                           chunk=CHUNK, backend="hybrid")
    hrows = np.concatenate([np.flatnonzero(lanes == k)[:n_hybrid]
                            for k in range(3)])
    res_h = idx_h.query_batch(us[hrows], vs[hrows])
    report["hybrid_s"] = time.perf_counter() - t
    if on_chip:
        check(idx_h._full_engine.use_pallas and idx_h.ctx.engine.use_pallas,
              "hybrid relay without the Pallas hub block")
    for name in ("label_dist", "meta_w", "meta_dist", "lm_dist"):
        check(np.array_equal(np.asarray(getattr(idx_h.packed, name)),
              np.asarray(getattr(idx.packed, name))), name)
    assert_same(res_h, [res[k] for k in hrows], "hybrid vs segment")
    log(f"hybrid backend (Pallas hub block={idx_h._full_engine.use_pallas}): "
        f"labels and {len(hrows)} landmark-lane answers bit-identical to "
        f"segment, {report['hybrid_s']:.3f}s build+compile+query")
    del idx_h, res_h

    # epoch 1 through a one-replica router of StreamingServices, as
    # repro.launch.serve runs it
    router = ReplicaRouter(idx, n_replicas=1,
                           policy=AdmissionPolicy(adaptive=False))
    ins, dels, touched = update_batch(g, res, lanes, rng, n_edges=24,
                                      n_touch=4)
    t = time.perf_counter()
    idx1 = router.apply_update(inserts=ins, deletes=dels)
    report["update_s"] = time.perf_counter() - t
    oracle.advance(idx1.graph, ins.tolist(), dels.tolist())
    t = time.perf_counter()
    futs = router.submit_batch(us, vs)
    router.drain()
    res1 = [f.result() for f in futs]
    report["router_s"] = time.perf_counter() - t
    check(all(f.epoch == 1 for f in futs), "answers not pinned to epoch 1")
    changed = sum(a.dist != b.dist for a, b in zip(res, res1))
    report["epoch1_changed"] = changed
    log(f"epoch 1: {len(ins)} inserts + {len(dels)} deletes applied in "
        f"{report['update_s']:.3f}s ({idx1.last_update_info.get('n_affected')}"
        f" landmarks relabelled, rebuild="
        f"{idx1.last_update_info.get('full_rebuild')}); ReplicaRouter"
        f"(1 replica) answered {len(us)} queries in {report['router_s']:.3f}s;"
        f" {changed} distances changed")
    rows1 = np.union1d(sample_rows(lanes, per_lane, rng), touched)
    n1 = check_oracle(oracle, res1, rows1, 1)
    log(f"epoch 1: {n1}/{n1} sampled answers match the oracle (incl. "
        f"{len(touched)} the update touched)")
    router.close()
    report["oracle_checked"] = n0 + n1
    return report


def run_four_chips(n: int = N_VERTICES, *, seed: int = 0, n_chips: int = 4,
                   n_general: int = 32, n_pair: int = 16, n_onesided: int = 32,
                   n_trivial: int = 8, per_lane: int = 2) -> dict:
    """The multi-chip phases: the vertex-sharded index and the batch-sharded
    general lane over ``n_chips`` devices, each bit-identical to the
    replicated one-chip index."""
    _import_paths()
    import jax

    from repro.core import QbSIndex, barabasi_albert_graph
    from tests.helpers.serving_oracle import EpochOracle

    rng = np.random.default_rng(seed)
    report: dict = {}
    check(len(jax.devices()) >= n_chips, jax.devices())

    t = time.perf_counter()
    g = barabasi_albert_graph(n, 3, seed=seed)
    log(f"graph ba V={g.n_vertices} E_slots={g.n_edges} in "
        f"{time.perf_counter() - t:.3f}s (host)")

    t = time.perf_counter()
    idx = QbSIndex.build(g, n_landmarks=N_LANDMARKS, chunk=CHUNK)
    _block(idx.packed.label_dist)
    log(f"replicated one-chip index built in {time.perf_counter() - t:.3f}s")
    landmarks = np.asarray(idx.scheme.landmarks)

    t = time.perf_counter()
    sh = QbSIndex.build(g, landmarks=landmarks, sharded=n_chips, chunk=CHUNK)
    _block(sh.labels.labels_sh)
    report["sharded_build_s"] = time.perf_counter() - t
    info = sh.sharded_size_bytes()
    log(f"sharded index built over {info['n_shards']} chips in "
        f"{report['sharded_build_s']:.3f}s; sharded_size_bytes={info}")
    for name, arr in (("labels", sh.labels.labels_sh),
                      ("lm_dist", sh.labels.lm_sh), ("src", sh._src_sh),
                      ("dst", sh._dst_sh)):
        devs = arr.sharding.device_set
        check(len(devs) == n_chips, (name, devs))
        shards = {s.device.id: s.data.shape for s in arr.addressable_shards}
        log(f"  {name}: {arr.shape} {arr.dtype} over devices {shards}")

    svc = idx.make_service(devices=n_chips)
    us, vs, lanes = pick_queries(idx._is_landmark_np, rng, n_general=n_general,
                                 n_pair=n_pair, n_onesided=n_onesided,
                                 n_trivial=n_trivial)
    gen = np.flatnonzero(lanes == 3)[:svc.chunk]
    mask, dist = _block(svc._sharded_general(us[gen], vs[gen]))
    for name, arr in (("edge_mask", mask), ("dist", dist)):
        devs = arr.sharding.device_set
        check(len(devs) == n_chips, (name, devs))
        shards = {s.device.id: s.data.shape for s in arr.addressable_shards}
        log(f"  batch-sharded general lane {name}: {arr.shape} over "
            f"devices {shards}")
    report["batch_sharded_result_bytes_per_device"] = \
        mask.addressable_shards[0].data.nbytes

    t = time.perf_counter()
    res = idx.query_batch(us, vs)
    report["replicated_s"] = time.perf_counter() - t
    t = time.perf_counter()
    res_sh = sh.query_batch(us, vs)
    report["sharded_s"] = time.perf_counter() - t
    t = time.perf_counter()
    res_b = svc.query_batch(us, vs)
    report["batch_sharded_s"] = time.perf_counter() - t
    assert_same(res_sh, res, "vertex-sharded vs replicated")
    assert_same(res_b, res, "batch-sharded vs replicated")
    log(f"{len(us)} queries (all four lanes): vertex-sharded and "
        f"batch-sharded answers bit-identical to the one-chip index "
        f"(replicated {report['replicated_s']:.3f}s, vertex-sharded "
        f"{report['sharded_s']:.3f}s, batch-sharded "
        f"{report['batch_sharded_s']:.3f}s, compile included)")

    oracle = EpochOracle(g)
    rows = sample_rows(lanes, per_lane, rng)
    report["oracle_checked"] = check_oracle(oracle, res, rows, 0)
    log(f"{len(rows)}/{len(rows)} sampled answers match the oracle")
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the multi-chip paths")
    ap.add_argument("--vertices", type=int, default=N_VERTICES,
                    help="graph size (default: the youtube-row deployment)")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (found {devices[0].platform}); nothing run",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} visible",
              file=sys.stderr)
        return 1
    _import_paths()
    from repro.launch.compile_cache import configure_compile_cache

    log(f"devices: {len(devices)} x {devices[0].device_kind}; compilation "
        f"cache {configure_compile_cache()}")
    t = time.perf_counter()
    if args.chips == 4:
        report = run_four_chips(args.vertices)
    else:
        report = run_one_chip(args.vertices)
    peaks = {d.id: (d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices[:args.chips]}
    log(f"peak_bytes_in_use per device: {peaks}")
    log(f"total {time.perf_counter() - t:.3f}s; report {report}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
