import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# ^ MUST precede any jax import: jax locks the device count at first init.
# Smoke tests / benches never import this module, so they see 1 device.

import argparse
import json
import math
import time
import traceback
from pathlib import Path

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs import ARCHS, get_config
from ..configs.qbs_graphs import GRAPHS
from ..models import (
    SHAPES,
    batch_pspecs,
    build_model,
    cache_pspecs,
    cell_applicable,
    input_specs,
    param_pspecs,
)
from ..training import adamw, make_train_step, warmup_cosine
from ..serving import make_decode_step, make_prefill_step
from .hlo_stats import summarize_compiled
from .mesh import dp_axes, make_production_mesh

RESULTS = Path(__file__).resolve().parents[3] / "results" / "dryrun"


def _ns(mesh, spec):
    return NamedSharding(mesh, spec)


def _shard_tree(mesh, spec_tree):
    return jax.tree_util.tree_map(lambda s: _ns(mesh, s), spec_tree,
                                  is_leaf=lambda x: isinstance(x, P))


def _sds_tree(tree):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


# ---------------------------------------------------------------------------
# LM cells
# ---------------------------------------------------------------------------

def lower_lm_cell(arch: str, shape_name: str, mesh, *, remat: bool = False,
                  kv_quant: bool = False, zero1: bool = False,  # noqa: doc
                  moe_sort: bool = False, moe_group: bool = False,
                  flash: bool = False, seq_shard: str = "",
                  microbatches: int = 1, kv_layout: str = "hd",
                  depth_probe: bool = True) -> dict:
    from dataclasses import replace as _replace

    cfg = get_config(arch)
    if moe_sort:
        cfg = _replace(cfg, moe_dispatch="sort", moe_ep_anchor=True)
    if moe_group:
        cfg = _replace(cfg, moe_group_size=1024)
    if flash:
        cfg = _replace(cfg, attn_impl="chunked")
    if remat:
        cfg = _replace(cfg, remat_policy="layer")
        remat = False  # cfg-level per-layer remat, not whole-loss remat
    if microbatches > 1:
        pass  # threaded below
    if seq_shard == "dp":      # anchor activations to DP-only sharding
        cfg = _replace(cfg, act_spec=(tuple(dp_axes(mesh)), None, None))
    elif seq_shard == "sp":    # Megatron-SP: sequence sharded over model
        cfg = _replace(cfg, act_spec=(tuple(dp_axes(mesh)), "model", None))
    shape = SHAPES[shape_name]
    ok, why = cell_applicable(cfg, shape)
    if not ok:
        return {"skipped": why}

    stats = _lower_lm_once(cfg, shape, mesh, remat=remat, kv_quant=kv_quant,
                           microbatches=microbatches, zero1=zero1,
                           kv_layout=kv_layout)

    if depth_probe:
        # XLA's HloCostAnalysis visits a scan body ONCE, so flops/collective
        # bytes are undercounted by ~n_layers.  Lower two shallow variants
        # and extrapolate linearly in depth (exact for scan-linear programs).
        # Memory/compile proof above still comes from the real-depth program.
        from dataclasses import replace
        per = cfg.hybrid_period or 1
        l1, l2 = per, 2 * per
        if cfg.n_layers > l2:
            s1 = _lower_lm_once(replace(cfg, n_layers=l1, scan_unroll=True),
                                shape, mesh, remat=remat, kv_quant=kv_quant,
                                microbatches=microbatches, zero1=zero1,
                                kv_layout=kv_layout)
            s2 = _lower_lm_once(replace(cfg, n_layers=l2, scan_unroll=True),
                                shape, mesh, remat=remat, kv_quant=kv_quant,
                                microbatches=microbatches, zero1=zero1,
                                kv_layout=kv_layout)
            stats["depth_extrapolated"] = _extrapolate_depth(
                s1, s2, l1, l2, cfg.n_layers)
    return stats


def _extrapolate_depth(s1: dict, s2: dict, l1: int, l2: int, l: int) -> dict:
    def lin(a, b):
        slope = (b - a) / (l2 - l1)
        return a + slope * (l - l1)

    out = {
        "flops": lin(s1["flops"], s2["flops"]),
        "bytes_accessed": lin(s1["bytes_accessed"], s2["bytes_accessed"]),
        "transcendentals": lin(s1["transcendentals"], s2["transcendentals"]),
        "collectives": {},
        "probe_layers": [l1, l2],
    }
    kinds = (set(s1["collectives"]) | set(s2["collectives"])) - {"_counts"}
    for k in kinds:
        out["collectives"][k] = lin(s1["collectives"].get(k, 0),
                                    s2["collectives"].get(k, 0))
    return out


def _lower_lm_once(cfg, shape, mesh, *, remat: bool = False,
                   kv_quant: bool = False, microbatches: int = 1,
                   zero1: bool = False, kv_layout: str = "hd") -> dict:
    model = build_model(cfg)
    dpx = dp_axes(mesh)
    dp_total = int(np.prod([mesh.shape[a] for a in dpx]))
    axis_sizes = dict(mesh.shape)

    from ..models import sanitize_pspecs

    params_shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    pspec = sanitize_pspecs(param_pspecs(cfg, params_shapes), params_shapes,
                            axis_sizes)
    p_sh = _shard_tree(mesh, pspec)

    specs = input_specs(cfg, shape, kv_quant=kv_quant)
    t0 = time.perf_counter()

    if shape.kind == "train":
        opt = adamw(warmup_cosine(3e-4, 2000, 100_000))
        opt_shapes = jax.eval_shape(opt.init, params_shapes)
        mom_spec = pspec
        if zero1:
            # ZeRO-1: shard optimizer moments over the DP axes on the first
            # dimension they divide (params stay TP-sharded + DP-replicated)
            def z1(spec, leaf):
                dims = list(spec)
                for i, d in enumerate(dims):
                    if d is None and leaf.shape[i] % dp_total == 0:
                        dims[i] = dpx
                        return P(*dims)
                return spec
            mom_spec = jax.tree_util.tree_map(
                z1, pspec, params_shapes, is_leaf=lambda x: isinstance(x, P))
        opt_spec = {"mu": mom_spec, "nu": mom_spec, "step": P()}
        o_sh = _shard_tree(mesh, opt_spec)
        b_spec = sanitize_pspecs(
            batch_pspecs(cfg, specs["batch"], dpx), specs["batch"], axis_sizes)
        b_sh = _shard_tree(mesh, b_spec)
        step = make_train_step(model, opt, remat=remat,
                               microbatches=microbatches)
        fn = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh),
                     out_shardings=(p_sh, o_sh, None), donate_argnums=(0, 1))
        lowered = fn.lower(params_shapes, opt_shapes, specs["batch"])
    elif shape.kind == "prefill":
        b_spec = sanitize_pspecs(
            batch_pspecs(cfg, specs["batch"], dpx), specs["batch"], axis_sizes)
        b_sh = _shard_tree(mesh, b_spec)
        fn = jax.jit(make_prefill_step(model), in_shardings=(p_sh, b_sh))
        lowered = fn.lower(params_shapes, specs["batch"])
    else:  # decode
        b = shape.global_batch
        if b % dp_total == 0:
            c_spec = cache_pspecs(cfg, specs["cache"], dpx)
            if kv_layout != "hd":
                # KV layout study (§Perf decode): "seq" shards cache S over
                # the otherwise-idle model axis; "rep" replicates over model
                def relayout(path, spec, leaf):
                    dims = list(spec)
                    if leaf.ndim >= 4 and "model" in [d for d in dims if isinstance(d, str)]:
                        nd = leaf.ndim
                        if kv_layout == "seq":
                            return P(*([None] * (nd - 4) + [dpx, "model", None, None]))
                        return P(*([None] * (nd - 4) + [dpx, None, None, None]))
                    return spec
                c_spec = jax.tree_util.tree_map_with_path(
                    relayout, c_spec, specs["cache"],
                    is_leaf=lambda x: isinstance(x, P))
            t_spec = P(dpx, None)
        else:
            # SP fallback (long_500k, B=1): replicate batch, shard the cache
            # sequence dim over the DP axes
            c_spec = _sp_cache_pspecs(cfg, specs["cache"], dpx)
            t_spec = P(None, None)
        c_spec = sanitize_pspecs(c_spec, specs["cache"], axis_sizes)
        c_sh = _shard_tree(mesh, c_spec)
        fn = jax.jit(
            make_decode_step(model),
            in_shardings=(p_sh, c_sh, _ns(mesh, P()), _ns(mesh, t_spec)),
            donate_argnums=(1,),
        )
        lowered = fn.lower(params_shapes, specs["cache"],
                           jax.ShapeDtypeStruct((), jnp.int32),
                           specs["tokens"])

    t1 = time.perf_counter()
    compiled = lowered.compile()
    t2 = time.perf_counter()
    stats = summarize_compiled(lowered, compiled)
    stats.update({
        "lower_s": round(t1 - t0, 2),
        "compile_s": round(t2 - t1, 2),
        "n_devices": int(np.prod(list(mesh.shape.values()))),
        "variant": {"remat": remat, "kv_quant": kv_quant},
    })
    return stats


def _sp_cache_pspecs(cfg, cache, dpx):
    """Sequence-parallel cache specs for batch-1 long-context decode."""

    def rule(path, leaf):
        keys = [str(e.key) for e in path if hasattr(e, "key")]
        nd = leaf.ndim
        name = keys[-1] if keys else ""
        if name in {"shift", "cm", "conv"}:
            return P(*([None] * (nd - 3) + [None, None, "model"]))
        if nd >= 4 and name in {"wkv", "ssm"}:
            return P(*([None] * (nd - 4) + [None, "model", None, None]))
        if nd >= 4 and name == "scale":
            return P(*([None] * (nd - 4) + [None, dpx, None, None]))
        if nd >= 4:  # KV (B, S, Hkv, hd): shard S over DP axes
            return P(*([None] * (nd - 4) + [None, dpx, None, "model"]))
        return P(*([None] * nd))

    return jax.tree_util.tree_map_with_path(rule, cache)


# ---------------------------------------------------------------------------
# QbS engine cells (paper-scale labelling + serving)
# ---------------------------------------------------------------------------

def lower_qbs_labelling_cell(graph_name: str, mesh, *, frontier_mode="bitmap") -> dict:
    from ..core.distributed import make_labelling_step, make_labelling_step_pull

    g = GRAPHS[graph_name]
    axis_names = tuple(mesh.axis_names)
    n_shards = int(np.prod([mesh.shape[a] for a in axis_names]))
    vloc = math.ceil(g.n_vertices / n_shards)
    emax = math.ceil(g.n_edge_slots / n_shards)
    i32 = jnp.int32
    t0 = time.perf_counter()
    if frontier_mode == "pull":
        # plan sizes from the uniform-spread estimate: each shard's edge
        # sources distribute ~evenly over owners
        p_pad = (math.ceil(emax / n_shards) + 31) // 32 * 32
        step = make_labelling_step_pull(
            mesh, n_vertices=g.n_vertices, v_loc=vloc, e_max=emax,
            p_pad=p_pad, n_landmarks=g.n_landmarks, max_levels=64,
        )
        lowered = step.lower(
            jax.ShapeDtypeStruct((n_shards, emax), i32),
            jax.ShapeDtypeStruct((n_shards, emax), i32),
            jax.ShapeDtypeStruct((n_shards,), i32),
            jax.ShapeDtypeStruct((g.n_landmarks,), i32),
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
        lowered = step.lower(
            jax.ShapeDtypeStruct((n_shards, emax), i32),
            jax.ShapeDtypeStruct((n_shards, emax), i32),
            jax.ShapeDtypeStruct((n_shards,), i32),
            jax.ShapeDtypeStruct((g.n_landmarks,), i32),
        )
    t1 = time.perf_counter()
    compiled = lowered.compile()
    t2 = time.perf_counter()
    stats = summarize_compiled(lowered, compiled)
    stats.update({
        "lower_s": round(t1 - t0, 2), "compile_s": round(t2 - t1, 2),
        "n_devices": n_shards,
        "variant": {"frontier_mode": frontier_mode},
        "graph": {"V": g.n_vertices, "E_directed": g.n_edge_slots,
                  "R": g.n_landmarks},
    })
    return stats


def lower_qbs_serve_cell(graph_name: str, mesh, *, batch: int | None = None,
                         avg_degree_slots: int | None = None) -> dict:
    """Replicated-label batched serving (graphs that fit per-device); the
    vertex-sharded variant for billion-scale graphs lives in
    core.scale_serve and is lowered by lower_qbs_scale_serve_cell."""
    from ..core.frontier import abstract_engine
    from ..core.search import SearchContext

    g = GRAPHS[graph_name]
    v, e, r = g.n_vertices, g.n_edge_slots, g.n_landmarks
    if batch is None:  # one query per device, times query-parallel width
        batch = int(np.prod(list(mesh.shape.values())))
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

    axis_names = tuple(mesh.axis_names)

    from ..core.search import Query, search_chunk
    from ..core.sketch import compute_sketch_batch

    def step(ctx, label_dist, meta_w, meta_dist, us, vs):
        lu = label_dist[us]
        lv = label_dist[vs]
        sk = compute_sketch_batch(lu, lv, meta_w, meta_dist)
        queries = Query(u=us, v=vs, d_top=sk.d_top, du_land=sk.du_land,
                        dv_land=sk.dv_land, meta_edge=sk.meta_edge,
                        d_star_u=sk.d_star_u, d_star_v=sk.d_star_v)
        res = search_chunk(ctx, queries, v, max_levels=32, max_chain=32)
        return res.edge_mask, res.dist

    rep = _ns(mesh, P())
    bsp = _ns(mesh, P(axis_names))
    ctx_sh = jax.tree_util.tree_map(lambda _: rep, ctx)
    fn = jax.jit(step, in_shardings=(ctx_sh, rep, rep, rep, bsp, bsp),
                 out_shardings=(bsp, bsp))
    t0 = time.perf_counter()
    lowered = fn.lower(ctx, scheme_label, meta, meta,
                       jax.ShapeDtypeStruct((batch,), i32),
                       jax.ShapeDtypeStruct((batch,), i32))
    t1 = time.perf_counter()
    compiled = lowered.compile()
    t2 = time.perf_counter()
    stats = summarize_compiled(lowered, compiled)
    stats.update({
        "lower_s": round(t1 - t0, 2), "compile_s": round(t2 - t1, 2),
        "n_devices": int(np.prod(list(mesh.shape.values()))),
        "variant": {"mode": "replicated-labels", "batch": batch},
        "graph": {"V": v, "E_directed": e, "R": r},
    })
    return stats



def lower_qbs_scale_serve_cell(graph_name: str, mesh, *, batch: int = 32) -> dict:
    """Vertex-sharded serving (labels + state sharded): the layout that
    actually scales to ClueWeb09 (labels alone are 68GB — unreplicable)."""
    from ..core.scale_serve import make_scale_serve_step

    g = GRAPHS[graph_name]
    axis_names = tuple(mesh.axis_names)
    n_shards = int(np.prod([mesh.shape[a] for a in axis_names]))
    vloc = math.ceil(g.n_vertices / n_shards)
    emax = math.ceil(g.n_edge_slots / n_shards)
    r = g.n_landmarks
    i32, i16 = jnp.int32, jnp.int16
    t0 = time.perf_counter()
    step = make_scale_serve_step(
        mesh, n_vertices=g.n_vertices, v_loc=vloc, e_max=emax,
        n_landmarks=r, batch=batch, max_levels=16, max_chain=4)
    lowered = step.lower(
        jax.ShapeDtypeStruct((n_shards, emax), i32),
        jax.ShapeDtypeStruct((n_shards, emax), i32),
        jax.ShapeDtypeStruct((n_shards,), i32),
        jax.ShapeDtypeStruct((n_shards, vloc, r), i16),
        jax.ShapeDtypeStruct((n_shards, emax, r), i16),
        jax.ShapeDtypeStruct((r,), i32),
        jax.ShapeDtypeStruct((r, r), i32),
        jax.ShapeDtypeStruct((r, r), i32),
        jax.ShapeDtypeStruct((batch,), i32),
        jax.ShapeDtypeStruct((batch,), i32),
    )
    t1 = time.perf_counter()
    compiled = lowered.compile()
    t2 = time.perf_counter()
    stats = summarize_compiled(lowered, compiled)
    stats.update({
        "lower_s": round(t1 - t0, 2), "compile_s": round(t2 - t1, 2),
        "n_devices": n_shards,
        "variant": {"mode": "vertex-sharded", "batch": batch},
        "graph": {"V": g.n_vertices, "E_directed": g.n_edge_slots, "R": r},
    })
    return stats


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

QBS_LABELLING_GRAPHS = ["youtube", "livejournal", "orkut", "twitter",
                        "friendster", "uk2007", "clueweb09"]
QBS_SERVE_GRAPHS = ["youtube", "livejournal", "orkut"]


def run_cell(kind: str, key: str, shape: str, mesh_name: str, *,
             force=False, **kw) -> tuple[str, dict]:
    variant = kw.pop("variant_tag", "")
    name = f"{kind}__{key}__{shape}__{mesh_name}" + (f"__{variant}" if variant else "")
    out = RESULTS / f"{name}.json"
    if out.exists() and not force:
        prior = json.loads(out.read_text())
        if "error" not in prior:  # re-attempt recorded failures
            return name, prior
    mesh = make_production_mesh(multi_pod=(mesh_name == "multi"))
    try:
        with mesh:
            if kind == "lm":
                stats = lower_lm_cell(key, shape, mesh, **kw)
            elif kind == "qbs-label":
                stats = lower_qbs_labelling_cell(key, mesh, **kw)
            elif kind == "qbs-serve":
                stats = lower_qbs_serve_cell(key, mesh, **kw)
            elif kind == "qbs-scale-serve":
                stats = lower_qbs_scale_serve_cell(key, mesh, **kw)
            else:
                raise ValueError(kind)
    except Exception as e:  # noqa: BLE001 — record failures, they are bugs
        stats = {"error": repr(e), "traceback": traceback.format_exc()[-4000:]}
    RESULTS.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(stats, indent=1))
    status = "SKIP" if "skipped" in stats else ("FAIL" if "error" in stats else "ok")
    print(f"[dryrun] {name}: {status} "
          f"(compile {stats.get('compile_s', '-')}s)", flush=True)
    return name, stats


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cells", default="all", choices=["all", "lm", "qbs"])
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--remat", action="store_true")
    ap.add_argument("--kv-quant", action="store_true")
    ap.add_argument("--moe-sort", action="store_true")
    ap.add_argument("--moe-group", action="store_true")
    ap.add_argument("--flash", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--qbs-frontier", default="", choices=["", "bool", "bitmap", "pull"])
    ap.add_argument("--zero1", action="store_true")
    ap.add_argument("--kv-layout", default="hd", choices=["hd", "seq", "rep"])
    ap.add_argument("--seq-shard", default="", choices=["", "dp", "sp"])
    args = ap.parse_args()

    meshes = {"single": ["single"], "multi": ["multi"],
              "both": ["single", "multi"]}[args.mesh]
    variant_tag = ""
    kw: dict = {}
    if args.remat:
        kw["remat"] = True
        variant_tag += "remat"
    if args.kv_quant:
        kw["kv_quant"] = True
        variant_tag += "kvq"
    if args.moe_sort:
        kw["moe_sort"] = True
        variant_tag += "moesort"
    if args.moe_group:
        kw["moe_group"] = True
        variant_tag += "moegroup"
    if args.flash:
        kw["flash"] = True
        variant_tag += "flash"
    if args.microbatches > 1:
        kw["microbatches"] = args.microbatches
        variant_tag += f"mb{args.microbatches}"
    if args.seq_shard:
        kw["seq_shard"] = args.seq_shard
        variant_tag += f"act{args.seq_shard}"
    if args.zero1:
        kw["zero1"] = True
        variant_tag += "zero1"
    if args.kv_layout != "hd":
        kw["kv_layout"] = args.kv_layout
        variant_tag += f"kv{args.kv_layout}"

    failures = 0
    if args.cells in ("all", "lm"):
        archs = [args.arch] if args.arch else sorted(ARCHS)
        shapes = [args.shape] if args.shape else list(SHAPES)
        for mesh_name in meshes:
            for arch in archs:
                for shape in shapes:
                    _, stats = run_cell("lm", arch, shape, mesh_name,
                                        force=args.force,
                                        variant_tag=variant_tag, **kw)
                    failures += 1 if "error" in stats else 0
    if args.cells in ("all", "qbs"):
        qkw = {}
        qtag = ""
        if args.qbs_frontier:
            qkw["frontier_mode"] = args.qbs_frontier
            qtag = args.qbs_frontier
        for mesh_name in meshes:
            for gname in QBS_LABELLING_GRAPHS:
                _, stats = run_cell("qbs-label", gname, "label", mesh_name,
                                    force=args.force, variant_tag=qtag, **qkw)
                failures += 1 if "error" in stats else 0
            for gname in QBS_SERVE_GRAPHS:
                _, stats = run_cell("qbs-serve", gname, "serve", mesh_name,
                                    force=args.force)
                failures += 1 if "error" in stats else 0
            for gname in ("twitter", "clueweb09"):
                _, stats = run_cell("qbs-scale-serve", gname, "serve", mesh_name,
                                    force=args.force)
                failures += 1 if "error" in stats else 0
    print(f"[dryrun] done; failures={failures}", flush=True)
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
