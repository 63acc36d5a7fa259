"""Dry-run of the QbS engine's distributed programs at the paper's Table 1
scales (``configs/qbs_graphs.py``).

Each cell lowers and compiles one program from ``ShapeDtypeStruct``
inputs, so no table is allocated, on the production mesh of 256 (single
pod) or 512 (multi pod) emulated host devices, and records its HLO cost,
memory and collective bytes (``hlo_stats.summarize_compiled``) under
``results/dryrun/``:

* ``qbs-label``: the edge-sharded labelling step (``--qbs-frontier``);
* ``qbs-serve``: the replicated-label general lane, queries sharded;
* ``qbs-sharded-serve``: ``ShardedIndex``'s general lane, labels, CSR
  and search state vertex-sharded over every mesh axis.

  PYTHONPATH=src python -m repro.launch.dryrun [--mesh single|multi|both]
"""
import argparse
import json
import math
import os
import time
import traceback
from pathlib import Path

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs.qbs_graphs import GRAPHS
from .hlo_stats import summarize_compiled
from .mesh import make_production_mesh

RESULTS = Path(__file__).resolve().parents[3] / "results" / "dryrun"


def _n_devices(mesh) -> int:
    return int(np.prod(list(mesh.shape.values())))


def _compile(lower) -> dict:
    """Lower (``lower()`` returns a ``jax.stages.Lowered``) and compile
    one program: its HLO summary plus the lower and compile wall times."""
    t0 = time.perf_counter()
    lowered = lower()
    t1 = time.perf_counter()
    compiled = lowered.compile()
    t2 = time.perf_counter()
    stats = summarize_compiled(lowered, compiled)
    stats.update({"lower_s": round(t1 - t0, 2), "compile_s": round(t2 - t1, 2)})
    return stats


def lower_qbs_labelling_cell(graph_name: str, mesh, *, frontier_mode="bitmap") -> dict:
    from ..core.distributed import make_labelling_step, make_labelling_step_pull

    g = GRAPHS[graph_name]
    n_shards = _n_devices(mesh)
    vloc = math.ceil(g.n_vertices / n_shards)
    emax = math.ceil(g.n_edge_slots / n_shards)
    i32 = jnp.int32
    edge_args = (
        jax.ShapeDtypeStruct((n_shards, emax), i32),
        jax.ShapeDtypeStruct((n_shards, emax), i32),
        jax.ShapeDtypeStruct((n_shards,), i32),
        jax.ShapeDtypeStruct((g.n_landmarks,), i32),
    )
    if frontier_mode == "pull":
        # plan sizes from the uniform-spread estimate: each shard's edge
        # sources distribute ~evenly over owners
        p_pad = (math.ceil(emax / n_shards) + 31) // 32 * 32
        step = make_labelling_step_pull(
            mesh, n_vertices=g.n_vertices, v_loc=vloc, e_max=emax,
            p_pad=p_pad, n_landmarks=g.n_landmarks, max_levels=64,
        )
        args = edge_args + (
            jax.ShapeDtypeStruct((n_shards, n_shards, p_pad), i32),
            jax.ShapeDtypeStruct((n_shards, emax), i32),
            jax.ShapeDtypeStruct((n_shards, emax), i32),
        )
    else:
        step = make_labelling_step(
            mesh, n_vertices=g.n_vertices, v_loc=vloc, e_max=emax,
            n_landmarks=g.n_landmarks, frontier_mode=frontier_mode,
            max_levels=64,
        )
        args = edge_args
    stats = _compile(lambda: step.lower(*args))
    stats.update({
        "n_devices": n_shards,
        "variant": {"frontier_mode": frontier_mode},
        "graph": {"V": g.n_vertices, "E_directed": g.n_edge_slots,
                  "R": g.n_landmarks},
    })
    return stats


def lower_qbs_serve_cell(graph_name: str, mesh, *, batch: int | None = None) -> dict:
    """Replicated-label batched serving (graphs that fit per-device);
    ``lower_qbs_sharded_serve_cell`` lowers the vertex-sharded lane."""
    from ..core.frontier import abstract_engine
    from ..core.search import Query, SearchContext, search_chunk
    from ..core.sketch import compute_sketch_batch

    g = GRAPHS[graph_name]
    v, e, r = g.n_vertices, g.n_edge_slots, g.n_landmarks
    if batch is None:  # one query per device, times query-parallel width
        batch = _n_devices(mesh)
    i32, b_ = jnp.int32, jnp.bool_
    ctx = SearchContext(
        src=jax.ShapeDtypeStruct((e,), i32),
        dst=jax.ShapeDtypeStruct((e,), i32),
        gminus_e=jax.ShapeDtypeStruct((e,), b_),
        is_landmark=jax.ShapeDtypeStruct((v,), b_),
        lid=jax.ShapeDtypeStruct((v,), i32),
        label_dist=jax.ShapeDtypeStruct((v, r), i32),
        meta_w=jax.ShapeDtypeStruct((r, r), i32),
        engine=abstract_engine(v, e, masked=True),
    )
    scheme_label = jax.ShapeDtypeStruct((v, r), i32)
    meta = jax.ShapeDtypeStruct((r, r), i32)

    def step(ctx, label_dist, meta_w, meta_dist, us, vs):
        lu = label_dist[us]
        lv = label_dist[vs]
        sk = compute_sketch_batch(lu, lv, meta_w, meta_dist)
        queries = Query(u=us, v=vs, d_top=sk.d_top, du_land=sk.du_land,
                        dv_land=sk.dv_land, meta_edge=sk.meta_edge,
                        d_star_u=sk.d_star_u, d_star_v=sk.d_star_v)
        res = search_chunk(ctx, queries, v, max_levels=32, max_chain=32)
        return res.edge_mask, res.dist

    rep = NamedSharding(mesh, P())
    bsp = NamedSharding(mesh, P(tuple(mesh.axis_names)))
    ctx_sh = jax.tree_util.tree_map(lambda _: rep, ctx)
    fn = jax.jit(step, in_shardings=(ctx_sh, rep, rep, rep, bsp, bsp),
                 out_shardings=(bsp, bsp))
    stats = _compile(lambda: fn.lower(
        ctx, scheme_label, meta, meta,
        jax.ShapeDtypeStruct((batch,), i32),
        jax.ShapeDtypeStruct((batch,), i32)))
    stats.update({
        "n_devices": _n_devices(mesh),
        "variant": {"mode": "replicated-labels", "batch": batch},
        "graph": {"V": v, "E_directed": e, "R": r},
    })
    return stats


def lower_qbs_sharded_serve_cell(graph_name: str, mesh, *, batch: int = 32) -> dict:
    """``ShardedIndex``'s general lane (``core.sharded``): uint8-packed
    labels, the CSR partition and the search state vertex-sharded; the
    edge mask comes back replicated, ``(batch, E)``."""
    from ..core.sharded import make_sharded_general_step

    g = GRAPHS[graph_name]
    n_shards = _n_devices(mesh)
    vloc = math.ceil(g.n_vertices / n_shards)
    emax = math.ceil(g.n_edge_slots / n_shards)
    r = g.n_landmarks
    i32, u8 = jnp.int32, jnp.uint8
    step = make_sharded_general_step(
        mesh, n_vertices=g.n_vertices, v_loc=vloc, e_max=emax,
        n_edges=g.n_edge_slots, n_landmarks=r)
    edge = jax.ShapeDtypeStruct((n_shards, emax), i32)
    per_shard = jax.ShapeDtypeStruct((n_shards,), i32)
    stats = _compile(lambda: step.lower(
        edge, edge, edge, edge, per_shard, per_shard,
        jax.ShapeDtypeStruct((n_shards, vloc, r), u8),
        jax.ShapeDtypeStruct((r,), i32),
        jax.ShapeDtypeStruct((r, r), u8),
        jax.ShapeDtypeStruct((r, r), u8),
        jax.ShapeDtypeStruct((batch,), i32),
        jax.ShapeDtypeStruct((batch,), i32)))
    stats.update({
        "n_devices": n_shards,
        "variant": {"mode": "vertex-sharded", "batch": batch},
        "graph": {"V": g.n_vertices, "E_directed": g.n_edge_slots, "R": r},
    })
    return stats


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

CELLS = {
    "qbs-label": lower_qbs_labelling_cell,
    "qbs-serve": lower_qbs_serve_cell,
    "qbs-sharded-serve": lower_qbs_sharded_serve_cell,
}
QBS_LABELLING_GRAPHS = ["youtube", "livejournal", "orkut", "twitter",
                        "friendster", "uk2007", "clueweb09"]
# the sharded lane addresses global edge slots as int32, so twitter and
# larger (over 2^31 slots) are out of its reach
QBS_SERVE_GRAPHS = ["youtube", "livejournal", "orkut"]


def run_cell(kind: str, key: str, mesh_name: str, *, force=False,
             variant_tag: str = "", **kw) -> tuple[str, dict]:
    name = f"{kind}__{key}__{mesh_name}" + (f"__{variant_tag}" if variant_tag else "")
    out = RESULTS / f"{name}.json"
    if out.exists() and not force:
        prior = json.loads(out.read_text())
        if "error" not in prior:  # re-attempt recorded failures
            return name, prior
    mesh = make_production_mesh(multi_pod=(mesh_name == "multi"))
    try:
        with mesh:
            stats = CELLS[kind](key, mesh, **kw)
    except Exception as e:  # noqa: BLE001 — record failures, they are bugs
        stats = {"error": repr(e), "traceback": traceback.format_exc()[-4000:]}
    RESULTS.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(stats, indent=1))
    status = "FAIL" if "error" in stats else "ok"
    print(f"[dryrun] {name}: {status} "
          f"(compile {stats.get('compile_s', '-')}s)", flush=True)
    return name, stats


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--qbs-frontier", default="", choices=["", "bool", "bitmap", "pull"])
    args = ap.parse_args()
    # the emulated device count is fixed when jax first touches its
    # backend, which nothing has done before this line
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

    meshes = {"single": ["single"], "multi": ["multi"],
              "both": ["single", "multi"]}[args.mesh]
    label_kw = {}
    if args.qbs_frontier:
        label_kw = {"frontier_mode": args.qbs_frontier,
                    "variant_tag": args.qbs_frontier}
    runs = [("qbs-label", g, label_kw) for g in QBS_LABELLING_GRAPHS]
    runs += [(kind, g, {}) for kind in ("qbs-serve", "qbs-sharded-serve")
             for g in QBS_SERVE_GRAPHS]
    failures = 0
    for mesh_name in meshes:
        for kind, gname, kw in runs:
            _, stats = run_cell(kind, gname, mesh_name, force=args.force, **kw)
            failures += 1 if "error" in stats else 0
    print(f"[dryrun] done; failures={failures}", flush=True)
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
