"""Distributed QbS: edge-sharded labelling and batch-sharded query serving.

Mapping of the paper onto a TPU mesh (DESIGN.md §2, §7):

* **Labelling** (offline): the |R| BFSs are one batched frontier program.
  Edges are sharded across devices *by destination-vertex block* (blocks cut
  at balanced edge counts, so hub-heavy blocks stay narrow); ``depth`` /
  ``reach_L`` live vertex-sharded next to the edges that write them.  Each
  level every device relays its local edges and the new frontier is
  exchanged with one ``all_gather``.  Lemma 5.2 (order-independence) is what
  makes the device-local relays commute — the merge is an exact OR/min.

  Two exchange formats:
    - ``frontier_mode="bool"``   : gather (2, R, V_loc) bool   (paper-faithful
                                   straightforward port; 2 bytes/vertex/root)
    - ``frontier_mode="bitmap"`` : gather (2, R, V_loc/32) uint32 packed
                                   (beyond-paper: 16x fewer collective bytes)

* **Serving** (online): queries are embarrassingly parallel — the batch is
  sharded across the mesh, labels and the sparsified graph are replicated
  within a pod (``make_serve_step``).  Billion-vertex variants keep the
  labels *vertex-sharded*: ``distributed_build_sharded`` finishes the
  labelling on-device so the packed tables are born sharded
  (``ShardedLabels``, one ``jax.sharding.NamedSharding`` block per
  device, never gathered to host), and ``core.sharded.ShardedIndex``
  serves every lane from those shards (DESIGN.md §11).
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from .frontier import segment_or
from .graph import INF, Graph
from .labelling import LabellingScheme, meta_apsp
# Bit-packed word layout shared with the hybrid frontier's hub block; the
# canonical definitions live in core.packing (DESIGN.md §10).
from .packing import PackedLabels, choose_pack_dtype, pack_dist, sentinel_of
from .packing import pack_bits as _pack_bits
from .packing import unpack_bits as _unpack_bits
from .search import Query, SearchContext, search_chunk
from .sketch import compute_sketch_batch


class EdgePartition(NamedTuple):
    """Host-side edge partition into S destination-contiguous shards."""

    src: np.ndarray        # (S, E_max) int32, global src ids (pad: 0)
    dst_local: np.ndarray  # (S, E_max) int32, dst - vstart (pad: V_loc_max)
    vstart: np.ndarray     # (S,) int32 first vertex of each shard's block
    v_loc: int             # max local block size (padded)
    e_max: int
    eid: np.ndarray | None = None  # (S, E_max) int32 global edge-slot ids
    #                                (pad: n_edges) — lets sharded serving
    #                                scatter local certificates back into
    #                                the canonical (B, E) edge mask


def partition_edges(graph: Graph, n_shards: int) -> EdgePartition:
    """Cut vertices into contiguous blocks with ~equal *edge* counts (not
    vertex counts) so degree skew doesn't create straggler shards, then
    assign each directed edge to its destination's block."""
    src = np.asarray(graph.src)
    dst = np.asarray(graph.dst)
    v = graph.n_vertices
    order = np.argsort(dst, kind="stable")
    dsorted = dst[order]
    ssorted = src[order]
    e = dst.shape[0]
    # block boundaries at ~equal edge quantiles, snapped to vertex borders
    cuts = [0]
    for s in range(1, n_shards):
        target = (e * s) // n_shards
        vtx = dsorted[min(target, e - 1)]
        cuts.append(int(vtx))
    cuts.append(v)
    vstart = np.maximum.accumulate(np.asarray(cuts[:-1], np.int64))
    vend = np.concatenate([vstart[1:], [v]])
    v_loc = int((vend - vstart).max()) if n_shards > 0 else v

    starts = np.searchsorted(dsorted, vstart)
    ends = np.searchsorted(dsorted, vend - 1, side="right")
    # guard empty blocks
    ends = np.maximum(ends, starts)
    e_max = int((ends - starts).max())
    e_max = max(e_max, 1)
    src_sh = np.zeros((n_shards, e_max), np.int32)
    dst_sh = np.full((n_shards, e_max), v_loc, np.int32)  # pad row = dropped
    eid_sh = np.full((n_shards, e_max), e, np.int32)      # pad -> dropped col
    for s in range(n_shards):
        a, b = starts[s], ends[s]
        src_sh[s, : b - a] = ssorted[a:b]
        dst_sh[s, : b - a] = dsorted[a:b] - vstart[s]
        eid_sh[s, : b - a] = order[a:b]
    return EdgePartition(src_sh, dst_sh, vstart.astype(np.int32), v_loc,
                         e_max, eid_sh)




def make_labelling_step(
    mesh: Mesh,
    *,
    n_vertices: int,
    v_loc: int,
    e_max: int,
    n_landmarks: int,
    axis_names: tuple[str, ...] | None = None,
    frontier_mode: str = "bitmap",
    max_levels: int = 64,
):
    """Build the jitted edge-sharded labelling program.

    Closes over *static* sizes only, so the dry-run can ``.lower()`` it from
    ShapeDtypeStructs at paper scale (ClueWeb09: V=1.7e9, E=15.6e9 directed)
    without allocating anything.  Landmark-ness is computed on the fly from
    the (R,) landmark-id vector — no (V,)-sized auxiliary arrays exist.

    Inputs: src_sh (S, E_max) int32, dst_local_sh (S, E_max) int32,
            vstart_sh (S,) int32, landmarks (R,) int32
    Outputs: depth (S, R, v_loc) int32, reach_L (S, R, v_loc) bool
    """
    axis_names = axis_names or tuple(mesh.axis_names)
    n_shards = int(np.prod([mesh.shape[a] for a in axis_names]))
    v = n_vertices
    r = n_landmarks
    vloc = v_loc
    spec_e = P(axis_names)
    rep = P()

    def shard_body(src_sh, dst_sh, vstart_sh, landmarks_j):
        # local shapes: src/dst (1, E_max) -> squeeze; vstart (1,)
        src_l = src_sh[0]
        dst_l = dst_sh[0]
        vst = vstart_sh[0]

        # local state (padded local block + 1 garbage row at index vloc)
        depth = jnp.full((r, vloc + 1), INF, jnp.int32)
        reach = jnp.zeros((r, vloc + 1), bool)
        lm_local = landmarks_j - vst
        own = (landmarks_j >= vst) & (lm_local < vloc)
        lm_idx = jnp.where(own, lm_local, vloc)
        depth = depth.at[jnp.arange(r), lm_idx].min(0)
        reach = reach.at[jnp.arange(r), lm_idx].set(own)

        local_ids = vst + jnp.arange(vloc, dtype=jnp.int32)
        local_ids = jnp.clip(local_ids, 0, v - 1)
        # landmark-ness on the fly: (R, vloc) root mask and its any-reduction
        is_root_loc = local_ids[None, :] == landmarks_j[:, None]
        is_lm_loc = is_root_loc.any(axis=0)
        prop_ok = (~is_lm_loc)[None, :] | is_root_loc

        # map global vertex id -> gathered layout index (shard, local)
        vstart_all = jax.lax.all_gather(vstart_sh, axis_names, tiled=True)  # (S,)

        def to_gathered(ids):
            shard = jnp.clip(
                jnp.searchsorted(vstart_all, ids, side="right") - 1, 0, n_shards - 1
            )
            return shard * vloc + (ids - vstart_all[shard])

        src_g = to_gathered(src_l)

        def exchange_and_read(fr_loc, pl_loc):
            """All-gather the frontier and read it at local edge sources.

            bitmap mode gathers uint32-packed words (16x fewer collective
            bytes than bool x2 flags) and extracts per-edge bits directly —
            the full boolean frontier is never materialized."""
            both = jnp.stack([fr_loc, pl_loc])  # (2, R, vloc)
            if frontier_mode == "bitmap":
                packed = _pack_bits(both)                       # (2, R, Wloc)
                wloc = packed.shape[-1]
                full = jax.lax.all_gather(packed, axis_names, tiled=False)
                full = jnp.moveaxis(full, 0, 2).reshape(2, r, n_shards * wloc)
                sh_i = src_g // vloc
                loc_i = src_g % vloc
                w_idx = sh_i * wloc + loc_i // 32
                bit = (loc_i % 32).astype(jnp.uint32)
                words = full[:, :, w_idx]                       # (2, R, E)
                vals = ((words >> bit[None, None, :]) & jnp.uint32(1)) > 0
                return vals[0], vals[1]
            full = jax.lax.all_gather(both, axis_names, tiled=False)
            full = jnp.moveaxis(full, 0, 2).reshape(2, r, n_shards * vloc)
            return full[0][:, src_g], full[1][:, src_g]

        def cond(c):
            _, _, level, alive = c
            return alive & (level < max_levels)

        def body(c):
            depth, reach, level, _ = c
            fr_loc = depth[:, :vloc] == level
            pl_loc = fr_loc & reach[:, :vloc] & prop_ok
            fr_src, pl_src = exchange_and_read(fr_loc, pl_loc)

            # local edge relay = the shared frontier primitive (int8
            # accumulator: smaller on-device temporaries, same booleans)
            msg_v = segment_or(fr_src, dst_l, vloc + 1, acc_dtype=jnp.int8)
            msg_l = segment_or(pl_src, dst_l, vloc + 1, acc_dtype=jnp.int8)
            new = msg_v & (depth == INF)
            depth2 = jnp.where(new, level + 1, depth)
            reach2 = reach | (new & msg_l)
            # psum makes the flag globally agreed (required: the all_gather
            # in the body must run the same trip count on every device);
            # OR with a varying-false keeps the carry type device-varying.
            alive = jax.lax.psum(new[:, :vloc].any().astype(jnp.int32), axis_names) > 0
            alive = alive | (vst < 0)
            return depth2, reach2, level + 1, alive

        depth, reach, _, _ = jax.lax.while_loop(
            cond, body, (depth, reach, vst * 0, vst == vst)
        )
        return depth[None, :, :vloc], reach[None, :, :vloc]

    return jax.jit(
        shard_map(
            shard_body,
            mesh=mesh,
            in_specs=(spec_e, spec_e, spec_e, rep),
            out_specs=(spec_e, spec_e),
        )
    )


class PullPlan(NamedTuple):
    """Static routing plan for demand-driven frontier exchange.

    The all-gather exchange moves 2*R*V/8 bytes/device/level, but a device
    only ever reads the frontier bits of *its local edges' sources* —
    typically ~E_loc of V vertices (50x less at ClueWeb09 scale).  The plan
    precomputes, per (sender i, receiver j), the sorted list of i-owned
    vertices that j needs; the exchange is then one all_to_all of packed
    bit-buffers and per-edge reads become static word/bit lookups.
    """

    send_idx: np.ndarray   # (S, S, P) int32: [i][j] = local idx of vertices i sends j
    edge_word: np.ndarray  # (S, E_max) int32: per-edge word into flat recv buffer
    edge_bit: np.ndarray   # (S, E_max) int32: per-edge bit position
    p_pad: int             # padded per-pair list length (multiple of 32)


def build_pull_plan(part: EdgePartition, n_shards: int) -> PullPlan:
    vstart = part.vstart.astype(np.int64)
    s_cnt = n_shards
    lists: list[list[np.ndarray]] = [[None] * s_cnt for _ in range(s_cnt)]  # type: ignore
    p_max = 1
    for j in range(s_cnt):
        valid = part.dst_local[j] < part.v_loc
        srcs = np.unique(part.src[j][valid])
        owner = np.clip(np.searchsorted(vstart, srcs, side="right") - 1, 0, s_cnt - 1)
        for i in range(s_cnt):
            li = srcs[owner == i]
            lists[i][j] = li
            p_max = max(p_max, li.size)
    p_pad = ((p_max + 31) // 32) * 32
    pw = p_pad // 32

    send_idx = np.zeros((s_cnt, s_cnt, p_pad), np.int32)
    for i in range(s_cnt):
        for j in range(s_cnt):
            li = lists[i][j]
            send_idx[i, j, : li.size] = (li - vstart[i]).astype(np.int32)

    edge_word = np.zeros((s_cnt, part.e_max), np.int32)
    edge_bit = np.zeros((s_cnt, part.e_max), np.int32)
    for j in range(s_cnt):
        valid = part.dst_local[j] < part.v_loc
        srcs = part.src[j]
        owner = np.clip(np.searchsorted(vstart, srcs, side="right") - 1, 0, s_cnt - 1)
        pos = np.zeros(srcs.shape, np.int64)
        for i in range(s_cnt):
            sel = (owner == i) & valid
            pos[sel] = np.searchsorted(lists[i][j], srcs[sel])
        edge_word[j] = (owner * pw + pos // 32).astype(np.int32)
        edge_bit[j] = (pos % 32).astype(np.int32)
    return PullPlan(send_idx, edge_word, edge_bit, p_pad)


def make_labelling_step_pull(
    mesh: Mesh,
    *,
    n_vertices: int,
    v_loc: int,
    e_max: int,
    p_pad: int,
    n_landmarks: int,
    axis_names: tuple[str, ...] | None = None,
    max_levels: int = 64,
):
    """Labelling program with demand-driven (pull) frontier exchange."""
    axis_names = axis_names or tuple(mesh.axis_names)
    n_shards = int(np.prod([mesh.shape[a] for a in axis_names]))
    v, r, vloc = n_vertices, n_landmarks, v_loc
    pw = p_pad // 32
    spec_e = P(axis_names)
    rep = P()

    def shard_body(src_sh, dst_sh, vstart_sh, landmarks_j,
                   send_idx_sh, edge_word_sh, edge_bit_sh):
        dst_l = dst_sh[0]
        vst = vstart_sh[0]
        send_idx = send_idx_sh[0]          # (S, P)
        edge_word = edge_word_sh[0]        # (E,)
        edge_bit = edge_bit_sh[0].astype(jnp.uint32)

        depth = jnp.full((r, vloc + 1), INF, jnp.int32)
        reach = jnp.zeros((r, vloc + 1), bool)
        lm_local = landmarks_j - vst
        own = (landmarks_j >= vst) & (lm_local < vloc)
        lm_idx = jnp.where(own, lm_local, vloc)
        depth = depth.at[jnp.arange(r), lm_idx].min(0)
        reach = reach.at[jnp.arange(r), lm_idx].set(own)

        local_ids = jnp.clip(vst + jnp.arange(vloc, dtype=jnp.int32), 0, v - 1)
        is_root_loc = local_ids[None, :] == landmarks_j[:, None]
        is_lm_loc = is_root_loc.any(axis=0)
        prop_ok = (~is_lm_loc)[None, :] | is_root_loc

        def exchange_and_read(fr_loc, pl_loc):
            both = jnp.concatenate([fr_loc, pl_loc], axis=0)   # (2R, vloc)
            vals = both[:, send_idx]                            # (2R, S, P)
            packed = _pack_bits(vals)                           # (2R, S, Pw)
            buf = jnp.moveaxis(packed, 1, 0)                    # (S, 2R, Pw)
            recv = jax.lax.all_to_all(
                buf, axis_names, split_axis=0, concat_axis=0, tiled=True)
            flat = jnp.moveaxis(recv, 0, 1).reshape(2 * r, n_shards * pw)
            words = flat[:, edge_word]                          # (2R, E)
            bits = (words >> edge_bit[None, :]) & jnp.uint32(1)
            on = bits > 0
            return on[:r], on[r:]

        def cond(c):
            _, _, level, alive = c
            return alive & (level < max_levels)

        def body(c):
            depth, reach, level, _ = c
            fr_loc = depth[:, :vloc] == level
            pl_loc = fr_loc & reach[:, :vloc] & prop_ok
            fr_src, pl_src = exchange_and_read(fr_loc, pl_loc)
            msg_v = segment_or(fr_src, dst_l, vloc + 1, acc_dtype=jnp.int8)
            msg_l = segment_or(pl_src, dst_l, vloc + 1, acc_dtype=jnp.int8)
            new = msg_v & (depth == INF)
            depth2 = jnp.where(new, level + 1, depth)
            reach2 = reach | (new & msg_l)
            alive = jax.lax.psum(new[:, :vloc].any().astype(jnp.int32), axis_names) > 0
            alive = alive | (vst < 0)
            return depth2, reach2, level + 1, alive

        depth, reach, _, _ = jax.lax.while_loop(
            cond, body, (depth, reach, vst * 0, vst == vst)
        )
        return depth[None, :, :vloc], reach[None, :, :vloc]

    return jax.jit(
        shard_map(
            shard_body,
            mesh=mesh,
            in_specs=(spec_e, spec_e, spec_e, rep, spec_e, spec_e, spec_e),
            out_specs=(spec_e, spec_e),
        )
    )


def distributed_build_labelling(  # qbslint: host-boundary
    graph: Graph,
    landmarks: np.ndarray,
    mesh: Mesh,
    *,
    axis_names: tuple[str, ...] | None = None,
    frontier_mode: str = "bitmap",
    max_levels: int = 64,
) -> LabellingScheme:
    """Edge-sharded Algorithm 2 over a device mesh.  Exact (== the
    single-device labelling) for any shard count.  frontier_mode: "bool"
    (paper-faithful port), "bitmap" (packed exchange), "pull" (demand-driven
    all_to_all exchange)."""
    axis_names = axis_names or tuple(mesh.axis_names)
    n_shards = int(np.prod([mesh.shape[a] for a in axis_names]))
    part = partition_edges(graph, n_shards)
    v = graph.n_vertices
    r = int(np.asarray(landmarks).shape[0])
    landmarks_j = jnp.asarray(landmarks, jnp.int32)
    is_landmark = jnp.zeros((v,), bool).at[landmarks_j].set(True)
    lid = jnp.full((v,), -1, jnp.int32).at[landmarks_j].set(
        jnp.arange(r, dtype=jnp.int32)
    )

    if frontier_mode == "pull":
        plan = build_pull_plan(part, n_shards)
        step = make_labelling_step_pull(
            mesh, n_vertices=v, v_loc=part.v_loc, e_max=part.e_max,
            p_pad=plan.p_pad, n_landmarks=r, axis_names=axis_names,
            max_levels=max_levels,
        )
        depth_sh, reach_sh = step(
            jnp.asarray(part.src), jnp.asarray(part.dst_local),
            jnp.asarray(part.vstart), landmarks_j,
            jnp.asarray(plan.send_idx), jnp.asarray(plan.edge_word),
            jnp.asarray(plan.edge_bit),
        )
    else:
        step = make_labelling_step(
            mesh, n_vertices=v, v_loc=part.v_loc, e_max=part.e_max,
            n_landmarks=r, axis_names=axis_names, frontier_mode=frontier_mode,
            max_levels=max_levels,
        )
        depth_sh, reach_sh = step(
            jnp.asarray(part.src), jnp.asarray(part.dst_local),
            jnp.asarray(part.vstart), landmarks_j,
        )

    # host re-assembly into the canonical dense labelling
    depth_np = np.asarray(depth_sh)   # (S, R, vloc)
    reach_np = np.asarray(reach_sh)
    depth_full = np.full((r, v), INF, np.int64)
    reach_full = np.zeros((r, v), bool)
    vstart = part.vstart
    vend = np.concatenate([vstart[1:], [v]])
    for s in range(depth_np.shape[0]):
        n_loc = vend[s] - vstart[s]
        depth_full[:, vstart[s]:vend[s]] = depth_np[s, :, :n_loc]
        reach_full[:, vstart[s]:vend[s]] = reach_np[s, :, :n_loc]

    is_lm_np = np.zeros((v,), bool)
    is_lm_np[np.asarray(landmarks)] = True
    valid = reach_full & ~is_lm_np[None, :]
    label_dist = np.where(valid, depth_full, INF).T.astype(np.int32)
    at_land = depth_full[:, np.asarray(landmarks)]
    l_at_land = reach_full[:, np.asarray(landmarks)]
    meta_w = np.where(l_at_land, at_land, INF)
    np.fill_diagonal(meta_w, INF)
    meta_w = np.minimum(meta_w, meta_w.T).astype(np.int32)

    return LabellingScheme(
        landmarks=landmarks_j,
        lid=lid,
        is_landmark=is_landmark,
        label_dist=jnp.asarray(label_dist),
        meta_w=jnp.asarray(meta_w),
        meta_dist=meta_apsp(jnp.asarray(meta_w)),
    )


# ---------------------------------------------------------------------------
# Born-sharded labelling: packed tables that never leave the mesh
# ---------------------------------------------------------------------------


class ShardedLabels(NamedTuple):
    """Packed label tables of one index, vertex-sharded over a mesh.

    Device fields carry a ``jax.sharding.NamedSharding``: one contiguous
    vertex block per device along the leading S axis.  The (R, R) meta
    tables and the landmark list are replicated — they are the sketch's
    landmark-landmark block, tiny by design (DESIGN.md §11).  Host fields
    hold partition *geometry* only, never table contents: the full (V, R)
    table is never materialized anywhere.
    """

    labels_sh: jax.Array   # (S, v_loc, R) packed, vertex-sharded
    lm_sh: jax.Array       # (S, R, v_loc) packed, vertex-sharded
    meta_w: jax.Array      # (R, R) packed, replicated
    meta_dist: jax.Array   # (R, R) packed, replicated (APSP closure)
    landmarks: jax.Array   # (R,) int32, replicated
    vstart: np.ndarray     # (S,) int32 first vertex of each block
    nloc: np.ndarray       # (S,) int32 real (un-padded) block sizes
    v_loc: int             # padded block size (labels_sh.shape[1])
    n_vertices: int

    @property
    def n_landmarks(self) -> int:
        return int(self.labels_sh.shape[-1])

    @property
    def pack_dtype(self) -> np.dtype:
        return np.dtype(self.labels_sh.dtype)

    @property
    def sentinel(self) -> int:
        return sentinel_of(self.labels_sh.dtype)

    def per_device_label_bytes(self) -> int:
        """Packed label bytes resident on ONE device: its (v_loc, R) label
        block + (R, v_loc) landmark-distance block + the replicated meta
        pair.  The sharding acceptance gate (benchmarks/sharded_memory.py)
        compares this against ``PackedLabels.nbytes``."""
        item = self.pack_dtype.itemsize
        r = self.n_landmarks
        return 2 * self.v_loc * r * item + 2 * r * r * item


def make_sharded_finalize(
    mesh: Mesh,
    *,
    n_vertices: int,
    v_loc: int,
    n_landmarks: int,
    axis_names: tuple[str, ...] | None = None,
):
    """Device program A of the born-sharded build: raw labelling state
    (depth, reach_L) -> int32 label blocks plus the replicated
    landmark-landmark readouts, all still on the mesh.

    Mirrors ``distributed_build_labelling``'s host re-assembly formulas
    exactly, per shard: ``label32 = where(reach & ~is_lm & real, depth,
    INF).T`` (pad rows forced INF), and the (R, R) ``at_land`` /
    ``l_at_land`` blocks read from each landmark's *owning* shard
    (owned-else-neutral + pmin/pmax, so the outputs are replicated).
    """
    axis_names = axis_names or tuple(mesh.axis_names)
    vloc = v_loc
    spec_e = P(axis_names)
    rep = P()

    def body(depth_sh, reach_sh, vstart_sh, nloc_sh, landmarks_j):
        depth = depth_sh[0]          # (R, vloc) int32
        reach = reach_sh[0]          # (R, vloc) bool
        vst = vstart_sh[0]
        n_loc = nloc_sh[0]
        local_ids = vst + jnp.arange(vloc, dtype=jnp.int32)
        real = jnp.arange(vloc, dtype=jnp.int32) < n_loc
        is_lm_loc = (local_ids[:, None] == landmarks_j[None, :]).any(axis=1)
        valid = reach & (~is_lm_loc & real)[None, :]
        label32 = jnp.where(valid, depth, INF).T       # (vloc, R)

        # landmark-landmark readout from the exact owner (each landmark is
        # claimed by exactly one shard, so owned-else-neutral + pmin/pmax
        # reconstructs depth_full[:, landmarks] bit-for-bit)
        lm_local = landmarks_j - vst
        own = (landmarks_j >= vst) & (landmarks_j < vst + n_loc)
        idx = jnp.clip(lm_local, 0, vloc - 1)
        at_land = jnp.where(own[None, :], depth[:, idx], INF)        # (R, R)
        at_land = jax.lax.pmin(at_land, axis_names)
        l_at_land = jnp.where(own[None, :], reach[:, idx], False)
        l_at_land = jax.lax.pmax(
            l_at_land.astype(jnp.int32), axis_names) > 0
        return label32[None], at_land, l_at_land

    return jax.jit(
        shard_map(
            body,
            mesh=mesh,
            in_specs=(spec_e, spec_e, spec_e, spec_e, rep),
            out_specs=(spec_e, rep, rep),
        )
    )


def make_sharded_lm_table(
    mesh: Mesh,
    *,
    n_vertices: int,
    v_loc: int,
    n_landmarks: int,
    axis_names: tuple[str, ...] | None = None,
):
    """Device program B: per-shard (R, v_loc) exact vertex-to-landmark
    distances from the local int32 label block + the replicated meta APSP
    — the vertex-sharded twin of ``qbs._dists_to_landmark_batch``,
    bit-identical on real rows (pad rows are INF).  Also emits the global
    max finite entry across label + lm tables (pmax-replicated scalar) so
    the host can run the same pack-dtype ladder as ``choose_pack_dtype``
    without ever gathering a table.
    """
    axis_names = axis_names or tuple(mesh.axis_names)
    vloc = v_loc
    spec_e = P(axis_names)
    rep = P()

    def body(label_sh, vstart_sh, nloc_sh, landmarks_j, meta_dist32):
        lab = label_sh[0]            # (vloc, R) int32
        vst = vstart_sh[0]
        n_loc = nloc_sh[0]
        # base[x, r] = min_i lab[x, i] + meta_dist[i, r]  (non-landmark rows)
        base = jnp.min(lab[:, :, None] + meta_dist32[None, :, :], axis=1)
        local_ids = vst + jnp.arange(vloc, dtype=jnp.int32)
        eqs = local_ids[:, None] == landmarks_j[None, :]
        is_lm = eqs.any(axis=1)
        lid_loc = jnp.argmax(eqs, axis=1)              # 0 where not landmark
        at_lm = meta_dist32[lid_loc]                   # (vloc, R); unused rows
        lm = jnp.minimum(jnp.where(is_lm[:, None], at_lm, base), INF)
        real = (jnp.arange(vloc, dtype=jnp.int32) < n_loc)[:, None]
        lm = jnp.where(real, lm, INF).astype(jnp.int32)
        mx = jnp.maximum(
            jnp.max(jnp.where(lab < INF, lab, -1)),
            jnp.max(jnp.where(lm < INF, lm, -1)),
        )
        mx = jax.lax.pmax(mx, axis_names)
        return lm.T[None], mx

    return jax.jit(
        shard_map(
            body,
            mesh=mesh,
            in_specs=(spec_e, spec_e, spec_e, rep, rep),
            out_specs=(spec_e, rep),
        )
    )


@partial(jax.jit, static_argnames=("sentinel", "dtype"))
def _pack_dist_device(a, *, sentinel: int, dtype: str):
    """Elementwise sentinel-encode on device; jitted so XLA carries the
    input's NamedSharding onto the output — the packed table is *born*
    sharded, never staged through host (``pack_dist`` is its host twin)."""
    return jnp.where(a >= INF, sentinel, a).astype(dtype)


def distributed_build_sharded(  # qbslint: host-boundary
    graph: Graph,
    landmarks: np.ndarray,
    mesh: Mesh,
    *,
    axis_names: tuple[str, ...] | None = None,
    frontier_mode: str = "bitmap",
    max_levels: int = 64,
) -> tuple[ShardedLabels, EdgePartition]:
    """Edge-sharded Algorithm 2 whose packed tables are *born*
    vertex-sharded: the labelling finishes on-device (finalize + lm-table
    shard_map programs) and only the (R, R) landmark-landmark block ever
    crosses to host — to run ``meta_apsp`` and the pack-dtype ladder.
    Exact: packs the same values ``distributed_build_labelling`` +
    ``pack_labelling`` would, per block (the bit-identity is pinned by
    tests/test_sharded_index.py).  Returns ``(ShardedLabels,
    EdgePartition)`` — the partition doubles as the serving CSR layout
    (``core.sharded.ShardedIndex``)."""
    axis_names = axis_names or tuple(mesh.axis_names)
    n_shards = int(np.prod([mesh.shape[a] for a in axis_names]))
    part = partition_edges(graph, n_shards)
    v = graph.n_vertices
    r = int(np.asarray(landmarks).shape[0])
    landmarks_j = jnp.asarray(landmarks, jnp.int32)
    vend = np.concatenate([part.vstart[1:], [v]])
    nloc = (vend - part.vstart).astype(np.int32)

    step = make_labelling_step(
        mesh, n_vertices=v, v_loc=part.v_loc, e_max=part.e_max,
        n_landmarks=r, axis_names=axis_names, frontier_mode=frontier_mode,
        max_levels=max_levels,
    )
    depth_sh, reach_sh = step(
        jnp.asarray(part.src), jnp.asarray(part.dst_local),
        jnp.asarray(part.vstart), landmarks_j,
    )

    finalize = make_sharded_finalize(
        mesh, n_vertices=v, v_loc=part.v_loc, n_landmarks=r,
        axis_names=axis_names,
    )
    vstart_j = jnp.asarray(part.vstart)
    nloc_j = jnp.asarray(nloc)
    label32_sh, at_land, l_at_land = finalize(
        depth_sh, reach_sh, vstart_j, nloc_j, landmarks_j)

    # Host boundary: the (R, R) landmark block is the one sanctioned
    # replicated readout (R^2 ints — bytes, not tables).
    at_np = np.asarray(at_land)
    l_np = np.asarray(l_at_land)
    meta_w_np = np.where(l_np, at_np, INF)
    np.fill_diagonal(meta_w_np, INF)
    meta_w_np = np.minimum(meta_w_np, meta_w_np.T).astype(np.int32)
    meta_dist32 = meta_apsp(jnp.asarray(meta_w_np))

    lm_step = make_sharded_lm_table(
        mesh, n_vertices=v, v_loc=part.v_loc, n_landmarks=r,
        axis_names=axis_names,
    )
    lm32_sh, mx = lm_step(label32_sh, vstart_j, nloc_j, landmarks_j,
                          meta_dist32)

    # Same dtype ladder as choose_pack_dtype, fed by the pmax scalar
    # instead of a gathered table.
    md_np = np.asarray(meta_dist32)
    dtype = choose_pack_dtype(
        np.asarray([max(int(mx), 0)]), meta_w_np, md_np)
    sent = sentinel_of(dtype)
    labels_sh = _pack_dist_device(
        label32_sh, sentinel=sent, dtype=np.dtype(dtype).name)
    lm_sh = _pack_dist_device(
        lm32_sh, sentinel=sent, dtype=np.dtype(dtype).name)
    return ShardedLabels(
        labels_sh=labels_sh,
        lm_sh=lm_sh,
        meta_w=pack_dist(meta_w_np, dtype),
        meta_dist=pack_dist(md_np, dtype),
        landmarks=landmarks_j,
        vstart=part.vstart,
        nloc=nloc,
        v_loc=part.v_loc,
        n_vertices=v,
    ), part


# ---------------------------------------------------------------------------
# Sharded batch serving
# ---------------------------------------------------------------------------


def make_serve_step(
    ctx: SearchContext,
    scheme: LabellingScheme,
    mesh: Mesh,
    *,
    n_vertices: int,
    axis_names: tuple[str, ...] | None = None,
    max_levels: int = 64,
    max_chain: int = 64,
    use_pallas: bool = False,
    packed: PackedLabels | None = None,
):
    """Return a jitted serve step: (us, vs) batch -> (edge_mask, dist),
    batch-sharded across the mesh, graph/labels replicated.  ``use_pallas``
    selects the sketch kernel like ``QbSIndex(use_pallas=...)`` does for
    the single-device pipeline (the serving service threads the index's
    setting through).  ``packed=`` replicates the index's packed label
    tables instead of the int32 scheme arrays (~4x fewer replicated label
    bytes per device; ``compute_sketch_batch`` widens in registers) — the
    two are bit-identical."""
    axis_names = axis_names or tuple(mesh.axis_names)

    def step(ctx, label_dist, meta_w, meta_dist, us, vs):
        lu = label_dist[us]
        lv = label_dist[vs]
        sk = compute_sketch_batch(lu, lv, meta_w, meta_dist,
                                  use_pallas=use_pallas)
        queries = Query(
            u=us, v=vs, d_top=sk.d_top, du_land=sk.du_land, dv_land=sk.dv_land,
            meta_edge=sk.meta_edge, d_star_u=sk.d_star_u, d_star_v=sk.d_star_v,
        )
        res = search_chunk(ctx, queries, n_vertices, max_levels, max_chain)
        return res.edge_mask, res.dist

    batch_spec = P(axis_names)
    rep = P()
    # per-leaf replication spec (ctx.engine is a nested pytree, so the spec
    # tree is built by tree_map rather than positional construction)
    ctx_specs = jax.tree_util.tree_map(lambda _: rep, ctx)
    step_sharded = shard_map(
        step,
        mesh=mesh,
        in_specs=(ctx_specs, rep, rep, rep, batch_spec, batch_spec),
        out_specs=(batch_spec, batch_spec),
    )
    fn = jax.jit(step_sharded)
    labels = scheme if packed is None else packed
    return partial(fn, ctx, labels.label_dist, labels.meta_w, labels.meta_dist)
