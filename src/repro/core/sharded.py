"""Vertex-sharded QbS index: every serving lane answered from the
born-sharded tables (DESIGN.md §11).

``distributed_build_sharded`` leaves the packed label table, the (R, V)
landmark-distance table and the CSR edge partition resident one vertex
block per device (``jax.sharding.NamedSharding``); ``ShardedIndex`` is
the ``QbSIndex``-shaped facade that serves from them without ever
materializing a full table:

* **General lane** (``make_sharded_general_step``): the vertex-sharded
  twin of ``core.scale_serve`` fed by *packed* labels.  Sketch rows for
  (u, v) come from the owning shard (owned-else-INF + ``pmin``); the
  sketch itself is a replicated O(B R^2) compute; the sketch-bounded
  Bi-BFS / reverse sweeps / recover chains run the ``segment_or`` relay
  on each device's local dst-owned edges with one packed-bitmap
  ``all_gather`` frontier exchange per level (the halo exchange — words
  stay packed across the wire).  Edge-source label columns are read from
  a *transient* in-program gather of the packed table, so the resident
  footprint stays one block per device (no edge-aligned label copies).
* **Landmark lanes** (``make_sharded_landmark_pair_step`` /
  ``make_sharded_onesided_step``): gather exactly the ``B`` packed rows
  of the landmark-distance table each chunk needs (one row per query
  side — never the table), then certify per local edge; the one-sided
  lane adds the same distance-bounded BFS as the replicated lane,
  sharded level-synchronously like the general lane.

Every lane ends in the same **scatter-symmetrize**: each shard writes
its locally-certified edges into the canonical ``(B, n_edges)`` mask at
their global slot *and* its reverse slot (``EdgePartition.eid`` + the
host-built reverse map), then one ``psum`` replicates the union.  Each
directed edge is dst-owned by exactly one shard, so this equals the
replicated path's ``mask | mask[:, rev_edge]`` bit-for-bit — pinned by
tests/test_sharded_index.py against the replicated oracle on emulated
8-device meshes.

Exactness caveat (same as ``core.scale_serve``): ``max_levels`` /
``max_chain`` must exceed the graph's diameter / longest recover chain;
the defaults suit the test graphs, paper-scale runs size them from the
measured diameter.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .distributed import (
    EdgePartition,
    ShardedLabels,
    _pack_bits,
    distributed_build_sharded,
)
from .frontier import segment_or
from .graph import INF, Graph, select_landmarks
from .packing import widen_dist
from .qbs import SPGResult, _reverse_edge_map
from .sketch import compute_sketch_batch


def _scatter_symmetrize(cert, eid_l, rev_l, n_edges, axis_names):
    """Per-shard certified local edges -> replicated symmetrized global
    mask.  ``cert`` is (B, E_loc) bool over this shard's dst-owned edge
    slots; each True scatters into its global slot *and* the reverse
    slot (pad slots target the dropped column ``n_edges``).  Because a
    directed edge is owned by exactly one shard, the int8 ``psum`` union
    (contribution <= 2 per shard: safe to 63 shards) reproduces the
    replicated ``mask | mask[:, rev_edge]`` exactly."""
    b = cert.shape[0]
    m8 = cert.astype(jnp.int8)
    acc = jnp.zeros((b, n_edges + 1), jnp.int8)
    acc = acc.at[:, eid_l].max(m8).at[:, rev_l].max(m8)
    acc = jax.lax.psum(acc, axis_names)
    return acc[:, :n_edges] > 0


def make_sharded_general_step(
    mesh: Mesh,
    *,
    n_vertices: int,
    v_loc: int,
    e_max: int,
    n_edges: int,
    n_landmarks: int,
    axis_names: tuple[str, ...] | None = None,
    max_levels: int = 32,
    max_chain: int = 8,
):
    """General lane from vertex-sharded packed tables.  The phase
    structure mirrors ``core.scale_serve`` (A label rows, B sketch,
    C bounded Bi-BFS, D reverse sweeps, E recover) — see that module for
    the certificate derivations; the differences here are packed-label
    widening (``widen_dist`` in-program), the transient edge-source
    label gather, and the scatter-symmetrized replicated output.

    Inputs: sharded (src, dst_local, eid, rev_eid, vstart, nloc,
    labels_sh) + replicated (landmarks, packed meta_w/meta_dist, us, vs).
    Outputs: replicated (edge_mask (B, n_edges) bool, dist (B,) int32).
    """
    axis_names = axis_names or tuple(mesh.axis_names)
    n_shards = int(np.prod([mesh.shape[a] for a in axis_names]))
    v, r, vloc = n_vertices, n_landmarks, v_loc
    wloc = (vloc + 31) // 32
    spec_e = P(axis_names)
    rep = P()

    def body(src_sh, dst_sh, eid_sh, rev_sh, vstart_sh, nloc_sh,
             labels_sh, landmarks_j, meta_w_p, meta_dist_p, us, vs):
        src_l = src_sh[0]                    # (E,) global ids
        dst_l = dst_sh[0]                    # (E,) local dst (pad = vloc)
        eid_l = eid_sh[0]                    # (E,) global slots (pad = n_edges)
        rev_l = rev_sh[0]
        vst = vstart_sh[0]
        n_loc = nloc_sh[0]
        labels_p = labels_sh[0]              # (vloc, R) packed
        labels_loc = widen_dist(labels_p)    # (vloc, R) int32, pad rows = INF
        b = us.shape[0]

        vstart_all = jax.lax.all_gather(vstart_sh, axis_names, tiled=True)

        def to_gathered(ids):
            shard = jnp.clip(
                jnp.searchsorted(vstart_all, ids, side="right") - 1,
                0, n_shards - 1)
            return shard, ids - vstart_all[shard]

        src_shard, src_off = to_gathered(src_l)
        src_g = src_shard * vloc + src_off
        src_word = src_shard * wloc + src_off // 32
        src_bit = (src_off % 32).astype(jnp.uint32)

        dst_glob = jnp.where(dst_l < vloc, vst + dst_l, v)
        is_lm_src = src_l[:, None] == landmarks_j[None, :]
        is_lm_dst = dst_glob[:, None] == landmarks_j[None, :]
        src_lid = jnp.where(is_lm_src.any(1), jnp.argmax(is_lm_src, axis=1), -1)
        dst_lid = jnp.where(is_lm_dst.any(1), jnp.argmax(is_lm_dst, axis=1), -1)
        gm_e = (~is_lm_src.any(1)) & (~is_lm_dst.any(1)) & (dst_l < vloc)

        label_dst = jnp.concatenate(
            [labels_loc, jnp.full((1, r), INF, jnp.int32)], axis=0)[dst_l]
        # transient gather of the packed table for edge-*source* columns:
        # crosses the wire packed, widens in registers, never resident
        full_p = jax.lax.all_gather(labels_p, axis_names, tiled=False)
        label_src32 = widen_dist(full_p.reshape(n_shards * vloc, r)[src_g])

        # ---- A: endpoint label rows from the owning shard ------------------
        def fetch_rows(qs):
            loc = qs - vst
            owned = (qs >= vst) & (qs < vst + n_loc)
            rows = labels_loc[jnp.clip(loc, 0, vloc - 1)]
            rows = jnp.where(owned[:, None], rows, INF)
            return jax.lax.pmin(rows, axis_names)

        lu = fetch_rows(us)
        lv = fetch_rows(vs)

        # ---- B: sketch (replicated compute; packed meta widens inside) -----
        sk = compute_sketch_batch(lu, lv, meta_w_p, meta_dist_p,
                                  use_pallas=False)
        d_top = sk.d_top

        # ---- C: sketch-bounded bidirectional BFS ---------------------------
        def owned_depth0(qs):
            loc = qs - vst
            owned = (qs >= vst) & (qs < vst + n_loc)
            d0 = jnp.full((b, vloc + 1), INF, jnp.int32)
            idx = jnp.where(owned, loc, vloc)
            return d0.at[jnp.arange(b), idx].min(jnp.where(owned, 0, INF))

        def exchange_bits(mask_loc):
            packed = _pack_bits(mask_loc)                    # (B, wloc)
            full = jax.lax.all_gather(packed, axis_names, tiled=False)
            flat = jnp.moveaxis(full, 0, 1).reshape(b, n_shards * wloc)
            words = flat[:, src_word]
            return ((words >> src_bit[None, :]) & jnp.uint32(1)) > 0

        def relay(bits_be, extra_e_mask=None):
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
                lvl = d_side - i
                send = on[:, :vloc] & (depth[:, :vloc] == lvl[:, None])
                bits = exchange_bits(send)
                cert = bits & gm_e[None, :] & (
                    depth[:, dst_l] == (lvl - 1)[:, None]) & (lvl > 0)[:, None]
                on = on | relay(cert)
                return on, emask | cert

            on, emask = jax.lax.fori_loop(0, int(max_levels), sbody,
                                          (on, emask))
            return emask

        rev_edges = sweep(depth_u, du) | sweep(depth_v, dv)

        # ---- E1: per-landmark side attachments ------------------------------
        rec_edges = false_e
        for ri in range(r):
            lcol = jnp.concatenate(
                [labels_loc[:, ri], jnp.full((1,), INF, jnp.int32)])
            ls_e = label_src32[:, ri]
            ld_e = label_dst[:, ri]
            for side_depth, side_land in ((depth_u, sk.du_land[:, ri]),
                                          (depth_v, sk.dv_land[:, ri])):
                sigma = side_land
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
                hop_in = bits & (dst_lid == ri)[None] & (ls_e == 1)[None]
                hop_out = (src_lid == ri)[None] & on[:, dst_l] & (ld_e == 1)[None]
                rec_edges = rec_edges | interior | hop_in | hop_out

        # ---- E2: Delta edges (fully local), one landmark i at a time -------
        # min_{i,j} ld[x,i] + ld[y,j] - w[i,j] == -1 through the per-vertex
        # t_i[y] = min_j ld[y,j] + (-w[i,j] | INF), as search.recover_chunk's
        # meta-row jobs take it: the (E, R, R) per-query form does not fit a chip
        # at real size
        meta_w32 = widen_dist(meta_w_p)
        w32 = jnp.where(meta_w32 < INF, meta_w32, INF)
        fin = sk.meta_edge & (meta_w32 < INF)[None]              # (B, R, R)
        m2 = jnp.where(fin, -w32[None], INF).astype(jnp.int32)   # (B, i, j)
        g1 = jnp.where(fin, w32[None] - 1, -1)                   # (B, i, j)
        sl = jnp.clip(src_lid, 0)
        dl = jnp.clip(dst_lid, 0)
        labels_pad = jnp.concatenate(
            [labels_loc, jnp.full((1, r), INF, jnp.int32)], axis=0)
        big = jnp.int32(1 << 30) + (vst * 0)   # above any sum; shard-varying

        def delta_i(i, c):
            minval, hop_a, hop_b = c

            def t_step(j, t):
                return jnp.minimum(
                    t, labels_pad[:, j][None, :] + m2[:, i, j][:, None])

            t_i = jax.lax.fori_loop(0, r, t_step,
                                    jnp.full((b, vloc + 1), big))
            minval = jnp.minimum(minval, label_src32[:, i][None, :]
                                 + t_i[:, dst_l])
            hop_a = hop_a | (label_dst[:, i][None, :] == g1[:, sl, i])
            hop_b = hop_b | (label_src32[:, i][None, :] == g1[:, i, dl])
            return minval, hop_a, hop_b

        minval, hop_a, hop_b = jax.lax.fori_loop(
            0, r, delta_i, (jnp.full(false_e.shape, big), false_e, false_e))
        delta_edges = ((gm_e[None] & (minval == -1))
                       | ((src_lid >= 0)[None] & hop_a)
                       | ((dst_lid >= 0)[None] & hop_b)
                       | ((src_lid >= 0) & (dst_lid >= 0))[None]
                       & fin[:, sl, dl] & (w32[sl, dl] == 1)[None])

        edge_mask = ((rev_edges & reverse_on[:, None])
                     | ((rec_edges | delta_edges) & recover_on[:, None]))
        edge_mask = edge_mask & (~trivial)[:, None] & (dst_l < vloc)[None, :]
        dist = jnp.where(trivial, 0, dist)
        mask = _scatter_symmetrize(edge_mask, eid_l, rev_l, n_edges,
                                   axis_names)
        return mask, dist

    return jax.jit(
        shard_map(
            body,
            mesh=mesh,
            in_specs=(spec_e, spec_e, spec_e, spec_e, spec_e, spec_e,
                      spec_e, rep, rep, rep, rep, rep),
            out_specs=(rep, rep),
        )
    )


def make_sharded_landmark_pair_step(
    mesh: Mesh,
    *,
    n_vertices: int,
    v_loc: int,
    n_edges: int,
    n_landmarks: int,
    axis_names: tuple[str, ...] | None = None,
):
    """Landmark-landmark lane from shards: distance is a replicated
    ``meta_dist`` lookup; the SPG certifies per dst-owned edge from the
    two gathered (B, V) landmark-distance rows — each chunk moves exactly
    2B packed rows across the mesh, never the table.  Bit-identical to
    ``qbs._landmark_pair_lanes`` (same formula per directed slot, then
    the shared scatter-symmetrize)."""
    axis_names = axis_names or tuple(mesh.axis_names)
    n_shards = int(np.prod([mesh.shape[a] for a in axis_names]))
    vloc = v_loc
    spec_e = P(axis_names)
    rep = P()

    def body(src_sh, dst_sh, eid_sh, rev_sh, vstart_sh, lm_sh,
             meta_dist_p, ru, rv):
        src_l = src_sh[0]
        dst_l = dst_sh[0]
        eid_l = eid_sh[0]
        rev_l = rev_sh[0]
        lm_loc = lm_sh[0]                    # (R, vloc) packed
        b = ru.shape[0]

        vstart_all = jax.lax.all_gather(vstart_sh, axis_names, tiled=True)
        shard = jnp.clip(
            jnp.searchsorted(vstart_all, src_l, side="right") - 1,
            0, n_shards - 1)
        src_g = shard * vloc + (src_l - vstart_all[shard])

        def rows_at_src(r_idx):
            sel = lm_loc[r_idx]                              # (B, vloc) packed
            full = jax.lax.all_gather(sel, axis_names, tiled=False)
            flat = jnp.moveaxis(full, 0, 1).reshape(b, n_shards * vloc)
            return widen_dist(flat[:, src_g])                # (B, E)

        def rows_at_dst(r_idx):
            sel = widen_dist(lm_loc[r_idx])                  # (B, vloc)
            sel = jnp.concatenate(
                [sel, jnp.full((b, 1), INF, jnp.int32)], axis=1)
            return sel[:, dst_l]                             # (B, E)

        d = jnp.minimum(widen_dist(meta_dist_p[ru, rv]), INF).astype(jnp.int32)
        cert = (rows_at_src(ru) + 1 + rows_at_dst(rv)) == d[:, None]
        cert = cert & (d < INF)[:, None] & (dst_l < vloc)[None, :]
        mask = _scatter_symmetrize(cert, eid_l, rev_l, n_edges, axis_names)
        return mask, d

    return jax.jit(
        shard_map(
            body,
            mesh=mesh,
            in_specs=(spec_e, spec_e, spec_e, spec_e, spec_e, spec_e,
                      rep, rep, rep),
            out_specs=(rep, rep),
        )
    )


def make_sharded_onesided_step(
    mesh: Mesh,
    *,
    n_vertices: int,
    v_loc: int,
    n_edges: int,
    n_landmarks: int,
    axis_names: tuple[str, ...] | None = None,
    max_levels: int = 32,
):
    """One-sided landmark lane from shards: d(root, landmark) reads one
    gathered packed row; the distance-bounded *full-graph* BFS from the
    root runs level-synchronously on local edges with the packed-bitmap
    halo exchange, mirroring ``frontier.bfs_depths_batch`` state-for-state
    (act/alive/bounds semantics — bit-identical depths), then certifies
    per dst-owned edge exactly like ``qbs._landmark_onesided_lanes``."""
    axis_names = axis_names or tuple(mesh.axis_names)
    n_shards = int(np.prod([mesh.shape[a] for a in axis_names]))
    v, vloc = n_vertices, v_loc
    wloc = (vloc + 31) // 32
    spec_e = P(axis_names)
    rep = P()

    def body(src_sh, dst_sh, eid_sh, rev_sh, vstart_sh, nloc_sh, lm_sh,
             roots, r_idx):
        src_l = src_sh[0]
        dst_l = dst_sh[0]
        eid_l = eid_sh[0]
        rev_l = rev_sh[0]
        vst = vstart_sh[0]
        n_loc = nloc_sh[0]
        lm_loc = lm_sh[0]                    # (R, vloc) packed
        b = roots.shape[0]

        vstart_all = jax.lax.all_gather(vstart_sh, axis_names, tiled=True)

        def to_gathered(ids):
            shard = jnp.clip(
                jnp.searchsorted(vstart_all, ids, side="right") - 1,
                0, n_shards - 1)
            return shard, ids - vstart_all[shard]

        src_shard, src_off = to_gathered(src_l)
        src_g = src_shard * vloc + src_off
        src_word = src_shard * wloc + src_off // 32
        src_bit = (src_off % 32).astype(jnp.uint32)

        # the B needed landmark-distance rows, gathered packed
        sel = lm_loc[r_idx]                                  # (B, vloc)
        full = jax.lax.all_gather(sel, axis_names, tiled=False)
        flat = jnp.moveaxis(full, 0, 1).reshape(b, n_shards * vloc)
        to_lm_src = widen_dist(flat[:, src_g])               # (B, E)
        root_sh, root_off = to_gathered(roots)
        d = widen_dist(flat[jnp.arange(b), root_sh * vloc + root_off])
        bounds = jnp.where(d < INF, d - 1, 0)

        # bounded batched BFS, sharded (mirrors bfs_depths_batch exactly)
        loc = roots - vst
        owned = (roots >= vst) & (roots < vst + n_loc)
        depth0 = jnp.full((b, vloc + 1), INF, jnp.int32)
        idx = jnp.where(owned, loc, vloc)
        depth0 = depth0.at[jnp.arange(b), idx].min(
            jnp.where(owned, 0, INF))

        def exchange_bits(mask_loc):
            packed = _pack_bits(mask_loc)
            full_b = jax.lax.all_gather(packed, axis_names, tiled=False)
            flat_b = jnp.moveaxis(full_b, 0, 1).reshape(b, n_shards * wloc)
            words = flat_b[:, src_word]
            return ((words >> src_bit[None, :]) & jnp.uint32(1)) > 0

        def active_rows(level, alive):
            return alive & (level < max_levels) & (level < bounds)

        def cond(c):
            _, level, alive = c
            return jax.lax.psum(
                active_rows(level, alive).any().astype(jnp.int32),
                axis_names) > 0

        def step(c):
            depth, level, alive = c
            act = active_rows(level, alive)
            frontier = (depth[:, :vloc] == level) & act[:, None]
            bits = exchange_bits(frontier)
            msg = segment_or(bits, dst_l, vloc + 1, acc_dtype=jnp.int8)
            new = msg & (depth == INF)
            row_new = jax.lax.psum(
                new[:, :vloc].any(axis=1).astype(jnp.int32), axis_names) > 0
            alive = jnp.where(act, row_new, alive)
            return jnp.where(new, level + 1, depth), level + 1, alive

        # the level and row-liveness carries vary per shard (they mix with
        # shard-local depths), so they start varying too
        zero, alive0 = jax.lax.pcast(
            (jnp.int32(0), roots == roots), axis_names, to="varying")
        depth, _, _ = jax.lax.while_loop(
            cond, step, (depth0, zero, alive0))

        cert = (to_lm_src + 1 + depth[:, dst_l]) == d[:, None]
        cert = cert & (d < INF)[:, None] & (dst_l < vloc)[None, :]
        mask = _scatter_symmetrize(cert, eid_l, rev_l, n_edges, axis_names)
        # d is equal on every shard, but read from an all_gather, whose
        # result the vma check types as varying: pmax (exact on equal
        # values) types it replicated for the out_spec
        return mask, jax.lax.pmax(d, axis_names)

    return jax.jit(
        shard_map(
            body,
            mesh=mesh,
            in_specs=(spec_e, spec_e, spec_e, spec_e, spec_e, spec_e,
                      spec_e, rep, rep),
            out_specs=(rep, rep),
        )
    )


class ShardedIndex:
    """``QbSIndex``-shaped serving facade over born-sharded tables.

    Exposes the same per-lane device steps and query delegates as
    ``QbSIndex`` (the planner/service layers run unchanged on top — the
    streaming admission seam of DESIGN.md §5 never sees the sharding),
    but every step answers from the vertex-sharded label + CSR blocks.
    ``ServingService(mesh=...)`` batch-sharding is rejected: the index
    is already mesh-resident (``is_sharded``).
    """

    is_sharded = True
    epoch = 0   # sharded tables are build-once; dynamic updates (§13) are
                # a replicated-index feature — the epoch never advances here

    def __init__(self, graph: Graph, labels: ShardedLabels,
                 part: EdgePartition, mesh: Mesh, *,
                 max_levels: int = 32, max_chain: int = 8, chunk: int = 32,
                 axis_names: tuple[str, ...] | None = None):
        self.graph = graph
        self.labels = labels
        self.part = part
        self.mesh = mesh
        self.max_levels = max_levels
        self.max_chain = max_chain
        self.chunk = chunk
        axis_names = axis_names or tuple(mesh.axis_names)
        self.axis_names = axis_names
        v = graph.n_vertices
        r = labels.n_landmarks

        lm_np = np.asarray(labels.landmarks)
        self._is_landmark_np = np.zeros((v,), bool)
        self._is_landmark_np[lm_np] = True
        self._lid_np = np.full((v,), -1, np.int32)
        self._lid_np[lm_np] = np.arange(r, dtype=np.int32)
        self._service = None

        # global slot ids + reverse slots, edge-partition-aligned (pads
        # target the dropped column n_edges)
        rev = _reverse_edge_map(np.asarray(graph.src), np.asarray(graph.dst),
                                v)
        rev_full = np.concatenate(
            [rev, np.asarray([graph.n_edges], np.int32)])
        rev_eid = rev_full[part.eid].astype(np.int32)

        shard = NamedSharding(mesh, P(axis_names))
        put = partial(jax.device_put, device=shard)
        self._src_sh = put(part.src)
        self._dst_sh = put(part.dst_local)
        self._eid_sh = put(part.eid)
        self._rev_eid_sh = put(rev_eid)
        self._vstart_sh = put(part.vstart)
        self._nloc_sh = put(labels.nloc)

        common = dict(n_vertices=v, v_loc=part.v_loc, n_edges=graph.n_edges,
                      n_landmarks=r, axis_names=axis_names)
        self._general = make_sharded_general_step(
            mesh, e_max=part.e_max, max_levels=max_levels,
            max_chain=max_chain, **common)
        self._lm_pair = make_sharded_landmark_pair_step(mesh, **common)
        self._onesided = make_sharded_onesided_step(
            mesh, max_levels=max_levels, **common)

    # -- per-lane device steps (QbSIndex contract) ---------------------------

    def serve_step(self, us, vs):
        """General lane: (B,) pairs -> replicated device ``(dist (B,),
        edge_mask (B, E))`` — already symmetrized (the scatter does it)."""
        mask, dist = self._general(
            self._src_sh, self._dst_sh, self._eid_sh, self._rev_eid_sh,
            self._vstart_sh, self._nloc_sh, self.labels.labels_sh,
            self.labels.landmarks, self.labels.meta_w, self.labels.meta_dist,
            jnp.asarray(us, jnp.int32), jnp.asarray(vs, jnp.int32))
        return dist, mask

    def landmark_pair_step(self, ru, rv):
        mask, dist = self._lm_pair(
            self._src_sh, self._dst_sh, self._eid_sh, self._rev_eid_sh,
            self._vstart_sh, self.labels.lm_sh, self.labels.meta_dist,
            jnp.asarray(ru, jnp.int32), jnp.asarray(rv, jnp.int32))
        return dist, mask

    def landmark_onesided_step(self, roots, r_idx):
        mask, dist = self._onesided(
            self._src_sh, self._dst_sh, self._eid_sh, self._rev_eid_sh,
            self._vstart_sh, self._nloc_sh, self.labels.lm_sh,
            jnp.asarray(roots, jnp.int32), jnp.asarray(r_idx, jnp.int32))
        return dist, mask

    # -- memory accounting ---------------------------------------------------

    def sharded_size_bytes(self) -> dict:
        """Per-device resident bytes vs the replicated layout the index
        replaces — the acceptance metric of the sharding work
        (benchmarks/sharded_memory.py commits ``per_device_frac`` rows;
        the gate holds them under a linear-scaling ceiling)."""
        item = self.labels.pack_dtype.itemsize
        v, r = self.labels.n_vertices, self.labels.n_landmarks
        e = self.graph.n_edges
        per_device_label = self.labels.per_device_label_bytes()
        # src + dst_local + eid + rev_eid, one edge shard each
        per_device_csr = 4 * self.part.e_max * 4
        replicated_label = (2 * v * r + 2 * r * r) * item
        replicated_csr = 3 * e * 4          # src + dst + rev_edge
        per_device = per_device_label + per_device_csr
        replicated = replicated_label + replicated_csr
        return {
            "n_shards": int(np.prod(
                [self.mesh.shape[a] for a in self.axis_names])),
            "per_device_label_bytes": per_device_label,
            "per_device_csr_bytes": per_device_csr,
            "per_device_bytes": per_device,
            "replicated_label_bytes": replicated_label,
            "replicated_csr_bytes": replicated_csr,
            "replicated_bytes": replicated,
            "per_device_frac": per_device / max(replicated, 1),
        }

    # -- construction --------------------------------------------------------

    @classmethod
    def build(cls, graph: Graph, n_landmarks: int = 20,
              landmarks: np.ndarray | None = None,
              mesh: Mesh | int | None = None,
              frontier_mode: str = "bitmap", build_max_levels: int = 64,
              **kw) -> "ShardedIndex":
        """Build labels distributed (born sharded) and wrap them for
        serving.  ``mesh`` is a ``jax.sharding.Mesh`` or a device count
        (1-D mesh over the first N local devices, axis ``"shards"``);
        default: every local device."""
        if mesh is None or isinstance(mesh, int):
            n = len(jax.devices()) if mesh is None else int(mesh)
            avail = jax.devices()
            if len(avail) < n:
                raise ValueError(
                    f"mesh={n} devices requested, {len(avail)} visible")
            mesh = Mesh(np.array(avail[:n]), ("shards",))
        if landmarks is None:
            landmarks = select_landmarks(graph, n_landmarks)
        labels, part = distributed_build_sharded(
            graph, np.asarray(landmarks), mesh,
            frontier_mode=frontier_mode, max_levels=build_max_levels)
        return cls(graph, labels, part, mesh, **kw)

    # -- queries (thin delegates over the planner/service) -------------------

    def make_service(self, **kw):
        from ..serving.service import ServingService
        return ServingService(self, **kw)

    def make_stream(self, *, policy=None, **kw):
        from ..serving.stream import StreamingService
        return StreamingService(self, policy=policy, **kw)

    def _default_service(self):
        if self._service is None:
            self._service = self.make_service()
        return self._service

    def query_batch(self, us, vs) -> list[SPGResult]:
        return self._default_service().query_batch(us, vs)

    def query_batch_arrays(self, us, vs):
        return self._default_service().query_arrays(us, vs)

    def query(self, u: int, v: int) -> SPGResult:
        return self.query_batch([u], [v])[0]
