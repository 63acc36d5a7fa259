"""Vertex-sharded distributed SPG serving for graphs too large to replicate
(labels + search state sharded over the mesh; ClueWeb09: V=1.7B).

Layout (per device, under shard_map; S shards over all mesh axes):
  vertices     contiguous block [vstart, vstart+vloc), +1 garbage row
  edges        dst-owned (same ``EdgePartition`` as distributed labelling)
  labels       labels_loc (vloc, R) int16 + *edge-aligned* source-label
               copies label_src (E_loc, R) int16 — the classic edge-attribute
               trade that makes every recover-search certificate edge-local
  queries      (B,) replicated; all per-query scalars replicated via psum

Phases (mirrors core.search, see DESIGN.md §2 for the certificates):
  A  label-row extraction for (u, v): owned-else-INF + global min-reduce
  B  sketch (replicated compute, O(B R^2))
  C  sketch-bounded bidirectional BFS: per-level packed-bitmap all_gather of
     the chosen side's frontier, edge relay into local depth
  D  reverse sweep per side: one (on & depth==l) bitmap exchange per level
  E  recover: per-landmark pointwise certificates + fixed-K chain closure
     (one bitmap exchange per iteration); Delta edges fully local via the
     edge-aligned labels (min-plus over the sketch's meta edges, looped
     over queries to bound per-device temporaries)

Exact vs the replicated-label ``QbSIndex`` path (tests/test_scale_serve.py);
the dry-run lowers it at paper scale.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from .frontier import segment_or
from .graph import INF, Graph
from .labelling import LabellingScheme
from .distributed import _pack_bits, partition_edges
from .sketch import compute_sketch_batch

INF16 = np.int16(30_000)


def make_scale_serve_step(
    mesh: Mesh,
    *,
    n_vertices: int,
    v_loc: int,
    e_max: int,
    n_landmarks: int,
    batch: int,
    axis_names: tuple[str, ...] | None = None,
    max_levels: int = 32,
    max_chain: int = 8,
):
    axis_names = axis_names or tuple(mesh.axis_names)
    n_shards = int(np.prod([mesh.shape[a] for a in axis_names]))
    v, r, vloc, b = n_vertices, n_landmarks, v_loc, batch
    wloc = (vloc + 31) // 32
    spec_e = P(axis_names)
    rep = P()

    def body(src_sh, dst_sh, vstart_sh, labels_sh, lsrc_sh,
             landmarks_j, meta_w, meta_dist, us, vs):
        src_l = src_sh[0]                    # (E,) global ids
        dst_l = dst_sh[0]                    # (E,) local dst (pad = vloc)
        vst = vstart_sh[0]
        labels_loc = labels_sh[0]            # (vloc, R) int16
        label_src = lsrc_sh[0]               # (E, R) int16

        vstart_all = jax.lax.all_gather(vstart_sh, axis_names, tiled=True)

        def to_gathered(ids):
            shard = jnp.clip(
                jnp.searchsorted(vstart_all, ids, side="right") - 1,
                0, n_shards - 1)
            return shard, ids - vstart_all[shard]

        src_shard, src_off = to_gathered(src_l)
        src_word = src_shard * wloc + src_off // 32
        src_bit = (src_off % 32).astype(jnp.uint32)

        dst_glob = jnp.where(dst_l < vloc, vst + dst_l, v)  # pad -> out of range
        is_lm_src = (src_l[:, None] == landmarks_j[None, :])
        is_lm_dst = (dst_glob[:, None] == landmarks_j[None, :])
        src_lid = jnp.where(is_lm_src.any(1), jnp.argmax(is_lm_src, axis=1), -1)
        dst_lid = jnp.where(is_lm_dst.any(1), jnp.argmax(is_lm_dst, axis=1), -1)
        gm_e = (~is_lm_src.any(1)) & (~is_lm_dst.any(1)) & (dst_l < vloc)

        label_dst = jnp.concatenate(
            [labels_loc, jnp.full((1, r), INF16, jnp.int16)], axis=0
        )[dst_l].astype(jnp.int32)                         # (E, R)
        label_dst = jnp.where(label_dst >= INF16, INF, label_dst)
        label_src32 = jnp.where(label_src >= INF16, INF,
                                label_src.astype(jnp.int32))

        # ---- A: label rows -------------------------------------------------
        def fetch_rows(qs):
            loc = qs - vst
            owned = (qs >= vst) & (loc < vloc)
            rows = labels_loc[jnp.clip(loc, 0, vloc - 1)].astype(jnp.int32)
            rows = jnp.where(owned[:, None] & (rows < INF16), rows, INF)
            return jax.lax.pmin(rows, axis_names)

        lu = fetch_rows(us)                                 # (B, R)
        lv = fetch_rows(vs)

        # ---- B: sketch (replicated) ---------------------------------------
        sk = compute_sketch_batch(lu, lv, meta_w, meta_dist)
        d_top = sk.d_top

        # ---- C: bounded bidirectional BFS ----------------------------------
        def owned_depth0(qs):
            loc = qs - vst
            owned = (qs >= vst) & (loc < vloc)
            d0 = jnp.full((b, vloc + 1), INF, jnp.int32)
            idx = jnp.where(owned, loc, vloc)
            return d0.at[jnp.arange(b), idx].min(jnp.where(owned, 0, INF))

        def exchange_bits(mask_loc):
            """(B, vloc) bool -> per-edge per-query bits (B, E)."""
            packed = _pack_bits(mask_loc)                    # (B, wloc)
            full = jax.lax.all_gather(packed, axis_names, tiled=False)
            flat = jnp.moveaxis(full, 0, 1).reshape(b, n_shards * wloc)
            words = flat[:, src_word]
            return ((words >> src_bit[None, :]) & jnp.uint32(1)) > 0

        def relay(bits_be, extra_e_mask=None):
            """(B, E) bool -> (B, vloc+1) bool via the shared frontier
            primitive (dst-keyed segment-OR over the local edge shard)."""
            m = bits_be
            if extra_e_mask is not None:
                m = m & extra_e_mask[None, :]
            return segment_or(m, dst_l, vloc + 1, acc_dtype=jnp.int8)

        def psum_i(x):
            return jax.lax.psum(x, axis_names)

        depth_u0 = owned_depth0(us)
        depth_v0 = owned_depth0(vs)

        def ball_size(depth):
            return psum_i(jnp.sum(depth[:, :vloc] < INF, axis=1))

        def cond(c):
            depth_u, depth_v, du, dv, au, av, met, it = c
            active = (~met) & (du + dv < jnp.minimum(d_top, max_levels)) & (au | av)
            return psum_i(active.any().astype(jnp.int32)) > 0

        def step(c):
            depth_u, depth_v, du, dv, au, av, met, it = c
            active = (~met) & (du + dv < jnp.minimum(d_top, max_levels)) & (au | av)
            want_u = sk.d_star_u > du
            want_v = sk.d_star_v > dv
            su = ball_size(depth_u)
            sv = ball_size(depth_v)
            pick_u = jnp.where(want_u != want_v, want_u, su <= sv)
            pick_u = jnp.where(au & av, pick_u, au)

            fr_u = (depth_u[:, :vloc] == du[:, None]) & (active & pick_u)[:, None]
            fr_v = (depth_v[:, :vloc] == dv[:, None]) & (active & ~pick_u)[:, None]
            bits = exchange_bits(fr_u | fr_v)
            msg = relay(bits, gm_e)
            grow_u = (active & pick_u)[:, None]
            grow_v = (active & ~pick_u)[:, None]
            new_u = msg & (depth_u == INF) & grow_u
            new_v = msg & (depth_v == INF) & grow_v
            depth_u = jnp.where(new_u, du[:, None] + 1, depth_u)
            depth_v = jnp.where(new_v, dv[:, None] + 1, depth_v)
            any_u = psum_i(new_u[:, :vloc].any(1).astype(jnp.int32)) > 0
            any_v = psum_i(new_v[:, :vloc].any(1).astype(jnp.int32)) > 0
            au = jnp.where(active & pick_u, any_u, au)
            av = jnp.where(active & ~pick_u, any_v, av)
            du = jnp.where(active & pick_u, du + 1, du)
            dv = jnp.where(active & ~pick_u, dv + 1, dv)
            common = (depth_u[:, :vloc] < INF) & (depth_v[:, :vloc] < INF)
            met = psum_i(common.any(1).astype(jnp.int32)) > 0
            return depth_u, depth_v, du, dv, au, av, met, it + 1

        zero_b = us * 0
        true_b = us == us
        state = (depth_u0, depth_v0, zero_b, zero_b, true_b, true_b,
                 ~true_b, jnp.int32(0) + (vst * 0))
        depth_u, depth_v, du, dv, au, av, met, _ = jax.lax.while_loop(
            cond, step, state)

        common = (depth_u[:, :vloc] < INF) & (depth_v[:, :vloc] < INF)
        sums = jnp.where(common, depth_u[:, :vloc] + depth_v[:, :vloc], INF)
        d_minus = jax.lax.pmin(jnp.min(sums, axis=1), axis_names)
        dist = jnp.minimum(d_minus, d_top)
        reverse_on = met & (d_minus <= d_top)
        recover_on = (d_top < INF) & (d_top <= d_minus)
        trivial = us == vs

        w_set = common & (sums == d_minus[:, None])

        # ---- D: reverse sweeps ---------------------------------------------
        false_e = jnp.broadcast_to((gm_e & ~gm_e)[None, :],
                                   (b, src_l.shape[0]))  # varying-typed False

        def sweep(depth, d_side):
            on = jnp.concatenate([w_set, jnp.zeros((b, 1), bool)], axis=1)
            emask = false_e

            def sbody(i, carry):
                on, emask = carry
                lvl = d_side - i                       # (B,)
                send = on[:, :vloc] & (depth[:, :vloc] == lvl[:, None])
                bits = exchange_bits(send)
                cert = bits & gm_e[None, :] & (
                    depth[:, dst_l] == (lvl - 1)[:, None]) & (lvl > 0)[:, None]
                on = on | relay(cert)
                return on, emask | cert

            steps = int(max_levels)
            on, emask = jax.lax.fori_loop(0, steps, sbody, (on, emask))
            return emask

        rev_edges = sweep(depth_u, du) | sweep(depth_v, dv)

        # ---- E1: per-landmark side attachments ------------------------------
        rec_edges = false_e
        for ri in range(r):
            lcol = jnp.where(labels_loc[:, ri] >= INF16, INF,
                             labels_loc[:, ri].astype(jnp.int32))
            lcol = jnp.concatenate([lcol, jnp.full((1,), INF, jnp.int32)])
            ls_e = label_src32[:, ri]
            ld_e = label_dst[:, ri]
            for side_depth, side_land in ((depth_u, sk.du_land[:, ri]),
                                          (depth_v, sk.dv_land[:, ri])):
                sigma = side_land                        # (B,)
                on = (side_depth < INF) & (lcol[None, :] < INF) & (
                    side_depth + lcol[None, :] == sigma[:, None]) & (
                    sigma < INF)[:, None]

                def chain(i, on):
                    bits = exchange_bits(on[:, :vloc])
                    grow = bits & gm_e[None] & (ld_e == ls_e - 1)[None] & (
                        ld_e < INF)[None]
                    return on | relay(grow)

                on = jax.lax.fori_loop(0, max_chain, chain, on)
                bits = exchange_bits(on[:, :vloc])
                interior = bits & on[:, dst_l] & gm_e[None] & (
                    ld_e == ls_e - 1)[None]
                # final hops both orientations
                hop_in = bits & (dst_lid == ri)[None] & (ls_e == 1)[None]
                hop_out = (src_lid == ri)[None] & on[:, dst_l] & (ld_e == 1)[None]
                rec_edges = rec_edges | interior | hop_in | hop_out

        # ---- E2: Delta edges (fully local) ----------------------------------
        w32 = jnp.where(meta_w < INF, meta_w, INF)

        def delta_b(bi, acc):
            me = sk.meta_edge[bi]                        # (R, R)
            fin = me & (meta_w < INF)
            m2 = jnp.where(fin, -w32, INF).T.astype(jnp.int32)   # (j, i)
            t1 = jnp.min(label_dst[:, :, None] + m2[None], axis=1)  # (E, i)
            minval = jnp.min(label_src32 + t1, axis=1)
            interior = gm_e & (minval == -1)
            g1 = jnp.where(fin, w32 - 1, -1)             # (i, j)
            hop1 = (src_lid >= 0) & (
                label_dst == g1[jnp.clip(src_lid, 0)]).any(1)
            hop2 = (dst_lid >= 0) & (
                label_src32 == g1.T[jnp.clip(dst_lid, 0)]).any(1)
            direct = (src_lid >= 0) & (dst_lid >= 0) & fin[
                jnp.clip(src_lid, 0), jnp.clip(dst_lid, 0)] & (
                w32[jnp.clip(src_lid, 0), jnp.clip(dst_lid, 0)] == 1)
            return acc.at[bi].set(interior | hop1 | hop2 | direct)

        delta_edges = jax.lax.fori_loop(0, b, delta_b, false_e)

        edge_mask = ((rev_edges & reverse_on[:, None])
                     | ((rec_edges | delta_edges) & recover_on[:, None]))
        edge_mask = edge_mask & (~trivial)[:, None] & (dst_l < vloc)[None, :]
        dist = jnp.where(trivial, 0, dist)
        return edge_mask[None], dist

    return jax.jit(
        shard_map(
            body,
            mesh=mesh,
            in_specs=(spec_e, spec_e, spec_e, spec_e, spec_e,
                      rep, rep, rep, rep, rep),
            out_specs=(spec_e, rep),
        )
    )


def build_scale_inputs(graph: Graph, scheme: LabellingScheme, n_shards: int):
    """Host-side: partition edges and build vertex-sharded + edge-aligned
    label arrays for the scale-serve program."""
    part = partition_edges(graph, n_shards)
    labels = np.asarray(scheme.label_dist)
    labels16 = np.where(labels >= INF, INF16, labels).astype(np.int16)
    v = graph.n_vertices
    r = labels.shape[1]
    vloc = part.v_loc
    vend = np.concatenate([part.vstart[1:], [v]])
    labels_sh = np.full((n_shards, vloc, r), INF16, np.int16)
    for s in range(n_shards):
        n_loc = vend[s] - part.vstart[s]
        labels_sh[s, :n_loc] = labels16[part.vstart[s]:vend[s]]
    lsrc = labels16[np.clip(part.src, 0, v - 1)]   # (S, E, R)
    return part, labels_sh, lsrc


def scale_serve(graph: Graph, scheme: LabellingScheme, mesh: Mesh, us, vs,
                **kw):
    """Run the vertex-sharded serving step on a real graph (test path).
    Returns (set of undirected edge pairs per query, dist array)."""
    axis_names = tuple(mesh.axis_names)
    n_shards = int(np.prod([mesh.shape[a] for a in axis_names]))
    part, labels_sh, lsrc = build_scale_inputs(graph, scheme, n_shards)
    us = np.asarray(us, np.int32)
    step = make_scale_serve_step(
        mesh, n_vertices=graph.n_vertices, v_loc=part.v_loc,
        e_max=part.e_max, n_landmarks=scheme.n_landmarks,
        batch=us.shape[0], **kw)
    mask_sh, dist = step(
        jnp.asarray(part.src), jnp.asarray(part.dst_local),
        jnp.asarray(part.vstart), jnp.asarray(labels_sh), jnp.asarray(lsrc),
        scheme.landmarks, scheme.meta_w, scheme.meta_dist,
        jnp.asarray(us), jnp.asarray(vs, jnp.int32))
    mask_np = np.asarray(mask_sh)      # (S, B, E)
    dist = np.asarray(dist)
    vend = np.concatenate([part.vstart[1:], [graph.n_vertices]])
    pairs = [set() for _ in range(us.shape[0])]
    for s in range(n_shards):
        dst_glob = part.dst_local[s] + part.vstart[s]
        valid = part.dst_local[s] < part.v_loc
        for b in range(us.shape[0]):
            sel = mask_np[s, b] & valid
            for a_, c_ in zip(part.src[s][sel], dst_glob[sel]):
                pairs[b].add((int(min(a_, c_)), int(max(a_, c_))))
    return pairs, dist
