"""Algorithm 4 (guided searching): sketch-bounded bidirectional BFS on the
sparsified graph G- = G[V \\ R], then a reverse search (extract the SPG edges
avoiding landmarks) and a recover search (re-attach shortest paths through
landmarks from the labelling).

TPU adaptation notes (see DESIGN.md §2):

* Queues -> level-synchronous frontier masks; every step is an edge-parallel
  relay through the pluggable ``core.frontier`` engine (a pull over the
  sorted CSR rows by default, CSR-blocked or hybrid hub/tail via
  ``backend=``), so hub vertices never serialize a lane.
* The paper's recover search walks pointers from anchor set Z.  Here the
  labels act as *global* distance certificates, which turns most of the walk
  into a single pointwise test:  a vertex x lies on a landmark-free shortest
  u->r path iff  depth_u[x] + delta_xr == sigma_S(u, r)  (compose the G- BFS
  prefix with the label suffix).  Only the part of a path *beyond* the
  explored ball needs the paper's anchor chain, which we run as a masked
  OR-closure over label levels (``while_loop``, trip count <= diameter).
* Landmark-to-landmark segments (the paper's precomputed Delta) need no
  search at all: both endpoints of an edge carry label certificates, so
  Delta is one min-plus contraction over the sketch's meta edges.

Everything is fixed-shape.  BFS and reverse are per query, vmapped over
the chunk; recover runs once per chunk over the (query, landmark) jobs
the sketches name (``recover_chunk``).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .frontier import FrontierEngine, make_relay
from .graph import INF, Graph
from .packing import PackedLabels, pack_dist, pack_labelling, widen_dist
from ..tracing import scope


class SearchContext(NamedTuple):
    """Per-graph constants shared by every query."""

    src: jax.Array          # (E,) int32
    dst: jax.Array          # (E,) int32
    gminus_e: jax.Array     # (E,) bool: both endpoints are non-landmarks
    is_landmark: jax.Array  # (V,) bool
    lid: jax.Array          # (V,) int32: vertex -> landmark index, -1 otherwise
    label_dist: jax.Array   # (V, R) packed uint8/uint16 (sentinel = INF)
    meta_w: jax.Array       # (R, R) packed direct meta edge weights
    engine: FrontierEngine  # G- relay (gminus_e baked in as the edge mask)


def make_search_context(
    graph: Graph,
    scheme=None,
    *,
    backend: str = "segment",
    engine: FrontierEngine | None = None,
    packed: PackedLabels | None = None,
    **engine_kw,
) -> SearchContext:
    """Build the per-graph search context (single construction point for the
    replicated-label path: ``QbSIndex``, the Bi-BFS baseline, the sharded
    serve step).  ``scheme=None`` means an empty landmark set, which is
    exactly the Bi-BFS degeneration.  ``engine`` overrides the built one
    (tests); otherwise the relay backend is chosen by ``backend=``.

    The label tables enter the context *packed* (``core.packing``): pass
    ``packed=`` to share the caller's ``PackedLabels`` buffers (as
    ``QbSIndex`` does, so HBM holds one packed copy for sketch + recover),
    otherwise the scheme is packed here.  ``widen_dist`` at the use sites
    restores exact int32/INF semantics inside the jit programs."""
    v, e = graph.n_vertices, graph.n_edges
    if scheme is None:
        gminus_e = jnp.ones((e,), bool)
        is_landmark = jnp.zeros((v,), bool)
        lid = jnp.full((v,), -1, jnp.int32)
        label_dist = pack_dist(np.full((v, 1), INF, np.int32), np.uint8)
        meta_w = pack_dist(np.full((1, 1), INF, np.int32), np.uint8)
    else:
        is_landmark = scheme.is_landmark
        gminus_e = (~is_landmark[graph.src]) & (~is_landmark[graph.dst])
        lid = scheme.lid
        if packed is None:
            packed = pack_labelling(scheme)
        label_dist = packed.label_dist
        meta_w = packed.meta_w
    if engine is None:
        engine = make_relay(graph, backend=backend, edge_mask=gminus_e,
                            **engine_kw)
    return SearchContext(
        src=graph.src, dst=graph.dst, gminus_e=gminus_e,
        is_landmark=is_landmark, lid=lid, label_dist=label_dist,
        meta_w=meta_w, engine=engine,
    )


class Query(NamedTuple):
    """One query + its sketch (leading axis = batch under vmap)."""

    u: jax.Array          # () int32
    v: jax.Array          # () int32
    d_top: jax.Array      # () int32
    du_land: jax.Array    # (R,) int32 sigma_S(u, r)
    dv_land: jax.Array    # (R,) int32 sigma_S(v, r')
    meta_edge: jax.Array  # (R, R) bool
    d_star_u: jax.Array   # () int32
    d_star_v: jax.Array   # () int32


class SearchResult(NamedTuple):
    """A chunk's answers (leading axis = the chunk's queries)."""

    edge_mask: jax.Array     # (B, E) bool, path-direction orientation marks
    dist: jax.Array          # (B,) int32, INF if disconnected
    d_minus: jax.Array       # (B,) int32 d_{G-}(u, v), INF if balls never met
    d_u: jax.Array           # (B,) int32 explored radius, u side
    d_v: jax.Array           # (B,) int32 explored radius, v side
    recover_jobs: jax.Array  # () int32 side + meta-row jobs recover ran


# ---------------------------------------------------------------------------
# Stage 1: sketch-bounded bidirectional BFS on G-  (Alg. 4 lines 1-15)
# ---------------------------------------------------------------------------

def bidirectional_bfs(ctx: SearchContext, q: Query, n_vertices: int, max_levels: int):
    V = n_vertices
    depth_u = jnp.full((V,), INF, jnp.int32).at[q.u].set(0)
    depth_v = jnp.full((V,), INF, jnp.int32).at[q.v].set(0)

    def cond(c):
        depth_u, depth_v, d_u, d_v, alive_u, alive_v, met = c
        more = (d_u + d_v < q.d_top) & (d_u + d_v < max_levels)
        return more & (~met) & (alive_u | alive_v)

    def body(c):
        depth_u, depth_v, d_u, d_v, alive_u, alive_v, met = c
        # pick_search: prefer the side whose sketch budget d* is unmet; on a
        # tie use the smaller explored ball (paper's |P_u| vs |P_v| rule).
        want_u = q.d_star_u > d_u
        want_v = q.d_star_v > d_v
        size_u = jnp.sum(depth_u < INF)
        size_v = jnp.sum(depth_v < INF)
        pick_u = jnp.where(
            want_u != want_v, want_u, size_u <= size_v
        )
        pick_u = jnp.where(alive_u & alive_v, pick_u, alive_u)

        def expand(depth, d):
            frontier = depth == d
            msg = ctx.engine.relay(frontier)
            new = msg & (depth == INF)
            return jnp.where(new, d + 1, depth), d + 1, new.any()

        du2, dcu, au2 = expand(depth_u, d_u)
        dv2, dcv, av2 = expand(depth_v, d_v)
        depth_u = jnp.where(pick_u, du2, depth_u)
        depth_v = jnp.where(pick_u, depth_v, dv2)
        d_u = jnp.where(pick_u, dcu, d_u)
        d_v = jnp.where(pick_u, d_v, dcv)
        alive_u = jnp.where(pick_u, au2, alive_u)
        alive_v = jnp.where(pick_u, alive_v, av2)
        met = jnp.any((depth_u < INF) & (depth_v < INF))
        return depth_u, depth_v, d_u, d_v, alive_u, alive_v, met

    # Carry scalars are derived from query data (not literals) so their
    # varying-manual-axes type matches the loop outputs under shard_map.
    true_ = q.u == q.u
    zero = q.u * 0
    init = (depth_u, depth_v, zero, zero, true_, true_, ~true_)
    return jax.lax.while_loop(cond, body, init)


# ---------------------------------------------------------------------------
# Stage 2: reverse search  (Alg. 4 lines 16-17)
# ---------------------------------------------------------------------------

def reverse_search(ctx: SearchContext, depth_u, depth_v, d_minus, n_vertices: int):
    """Extract the SPG edges of shortest u-v paths inside G-.

    Pointwise certification with *partial* balls only covers the two levels
    adjacent to the meeting cut, so we chain backward from the meeting set
    W = {x : depth_u[x] + depth_v[x] == d_minus} on each side.  Certified
    edges are oriented along the u->v path direction.

    The per-vertex chaining is one engine relay per level: a vertex x at
    depth l-1 joins the on-path set iff some on-path depth-l neighbour
    reaches it through G-.  For the u side the seed scattered the oriented
    certificates by *source*; on the symmetrized edge list (edge set and
    G- mask both symmetric) that equals the canonical dst-keyed relay, so
    both sides share one relay form.  The oriented per-edge certificate
    masks themselves stay explicit per-edge expressions (pure gathers).
    """
    common = (depth_u < INF) & (depth_v < INF)
    w_set = common & (depth_u + depth_v == d_minus)

    def sweep(depth, toward_u: bool):
        # walk from W back to the endpoint, level by level
        start_level = jnp.max(jnp.where(w_set, depth, 0))

        def cond(c):
            _, _, l = c
            return l >= 1

        def body(c):
            on, emask, l = c
            if toward_u:
                # certify (x -> y) with depth[x] == l-1, depth[y] == l, y on-path
                cert = (
                    ctx.gminus_e
                    & on[ctx.dst]
                    & (depth[ctx.dst] == l)
                    & (depth[ctx.src] == l - 1)
                )
            else:
                # certify (x -> y) with depth_v[x] == l, depth_v[y] == l-1
                cert = (
                    ctx.gminus_e
                    & on[ctx.src]
                    & (depth[ctx.src] == l)
                    & (depth[ctx.dst] == l - 1)
                )
            on = on | ((depth == l - 1) & ctx.engine.relay(on & (depth == l)))
            return on, emask | cert, l - 1

        on0 = w_set
        emask0 = w_set[ctx.src] & ~w_set[ctx.src]  # all-False, varying-typed
        _, emask, _ = jax.lax.while_loop(cond, body, (on0, emask0, start_level))
        return emask

    return sweep(depth_u, True) | sweep(depth_v, False)


# ---------------------------------------------------------------------------
# Stage 3: recover search  (Alg. 4 lines 18-24)
# ---------------------------------------------------------------------------
#
# Every certificate below is a test of one landmark OR-ed over the
# landmarks, and most (query, landmark) pairs cannot contribute: a side
# attaches only through a finite sketch edge, and the (iii) terms only
# through the sketch's meta edges.  So recover runs once per chunk over a
# compacted list of the jobs the sketch names, B at a time (B = the
# chunk's width), and keeps (J, V) / (J, E) state per block of J = B
# jobs.  The (V, R) / (E, R) forms of the same tests do not fit a chip at
# deployment size: over a 32-query chunk, one (E, R) int32 temporary of a
# 6.6M-slot graph is 17 GB before tiling.


def _label_col(ctx: SearchContext, k):
    """(V,) int32 labels of landmark ``k`` (traced), widened from the packed
    column."""
    return widen_dist(
        jax.lax.dynamic_index_in_dim(ctx.label_dist, k, axis=1, keepdims=False))


def _side_attach(ctx: SearchContext, depth, sigma, ld, dec, inc, k,
                 max_chain: int):
    """Component (i)/(ii) for landmark ``k``: edges of landmark-free shortest
    t->r_k paths for the sketch edge (r_k, t) of weight ``sigma``.  ``ld``
    is the (V,) label column of r_k, ``dec`` the (E,) G- edges along which
    it decrements (``ld[dst] == ld[src] - 1``) and ``inc`` their reverses
    (``ld[src] == ld[dst] - 1``)."""
    # Pointwise certificate: G- BFS prefix + label suffix == sigma.
    on = (ld < INF) & (depth < INF) & (sigma < INF) & (depth + ld == sigma)

    # Anchor-chain closure for path segments beyond the explored ball
    # (paper's Z-walk): extend along label-decrement edges in G-.  The
    # label-decrement coupling ties src and dst, so this is a per-edge
    # condition, not a vertex-value relay.  w joins when a decrement edge
    # (x, w) leaves an on-path x; by symmetry that edge's reverse (w, x)
    # lies in w's own row and satisfies ``inc``, so the step is a row pull.
    def cond(c):
        _, changed, it = c
        return changed & (it < max_chain)

    def body(c):
        on, _, it = c
        new_on = on | ctx.engine.pull(on, inc)
        return new_on, jnp.any(new_on & ~on), it + 1

    t = jnp.any(on)
    on, _, _ = jax.lax.while_loop(cond, body, (on, t | ~t, t.astype(jnp.int32) * 0))

    # Interior edges (both endpoints certified, label decrements) and the
    # final hops into r_k, in both orientations of the same edge.
    interior = dec & on[ctx.src] & on[ctx.dst]
    hop_in = (ctx.lid[ctx.dst] == k) & on[ctx.src] & (ld[ctx.src] == 1)
    hop_out = (ctx.lid[ctx.src] == k) & on[ctx.dst] & (ld[ctx.dst] == 1)
    return interior | hop_in | hop_out


def _label_cols(ctx: SearchContext, ks):
    """(J, V) int32 labels of landmarks ``ks`` (J,), widened from the
    packed columns."""
    return widen_dist(jnp.take(ctx.label_dist, ks, axis=1).T)


def _compact(active):
    """Flat positions of ``active``'s True entries first, in their order
    (a stable sort, no scatter), and how many there are."""
    order = jnp.argsort(~active, stable=True).astype(jnp.int32)
    return order, jnp.sum(active, dtype=jnp.int32)


def _or_by_query(masks, rows, valid, n_rows: int):
    """OR the jobs' (J, E) ``masks`` into their queries' rows: (n_rows, E).
    A one-hot (n_rows, J) x (J, E) contraction on the MXU, exact in bf16
    (0/1 in, counts <= J accumulated in f32); a scatter by row would
    serialize on the chip."""
    onehot = (rows[None, :] == jnp.arange(n_rows)[:, None]) & valid[None, :]
    hits = jnp.einsum("bj,je->be", onehot.astype(jnp.bfloat16),
                      masks.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)
    return hits > 0


def _for_blocks(block, order, n, width: int, acc):
    """OR ``block(jobs, valid)`` into ``acc`` for the compacted job list
    ``order[:n]``, ``width`` jobs at a time: ceil(n / width) trips.  The
    last block's tail holds inactive positions, flagged by ``valid``."""
    lane = jnp.arange(width)

    def cond(c):
        return c[0] < n

    def body(c):
        start, acc = c
        jobs = jax.lax.dynamic_slice_in_dim(order, start, width)
        return start + width, acc | block(jobs, start + lane < n)

    return jax.lax.while_loop(cond, body, (n * 0, acc))[1]


def recover_chunk(ctx: SearchContext, queries: Query, depth_u, depth_v,
                  recover_on, max_chain: int):
    """Recover (components (i)/(ii) on both sides and (iii)) for a whole
    chunk: ``(edge_mask (B, E), jobs ())``.  ``queries`` and the (B, V)
    BFS depths carry the chunk's batch axis; only rows with
    ``recover_on`` get edges.

    *Side jobs* ``(b, k, side)``: ``recover_on[b]`` and sigma_S(u_b, r_k)
    (or sigma_S(v_b, r_k)) finite.  Each attaches the landmark-free
    shortest paths of that sketch edge (``_side_attach``); every other
    pair's certificate is empty, since it needs ``sigma < INF``.

    *Meta-row jobs* ``(b, i)``: ``recover_on[b]`` and row i of the
    query's meta edges non-empty.  (iii) marks the edges on landmark-free
    shortest r_i - r_j paths of every sketch meta edge (i, j) (the
    paper's precomputed Delta), from labels alone.  For a G- edge (x, y)
    it holds iff some meta edge has  ld[x,i] + 1 + ld[y,j] == w[i,j].
    By the triangle inequality ld[x,i] + ld[y,j] - w[i,j] >= -1, so for
    row i the test is  ld[x,i] + t_i[y] == -1  with
        t_i[y] = min_j ( ld[y, j] - w[i, j]  over the row's meta edges ),
    and the OR of the rows' tests equals the min over all meta edges
    being -1.  The boundary hops r_i -> y and x -> r_j and the direct
    r_i - r_j edges of weight 1 complete the row.

    Each list is compacted on the device and run in blocks of B jobs; the
    bound is the dense form's pass count (2R side and R meta blocks when
    every entry is finite).  Block masks OR into their queries' rows by
    ``_or_by_query``.  ``jobs`` counts both lists."""
    b, r = queries.du_land.shape
    w = widen_dist(ctx.meta_w)
    fin = (w < INF)[None] & queries.meta_edge                 # (B, R, R)
    land = jnp.stack([queries.du_land, queries.dv_land], axis=-1)
    side_order, n_side = _compact(
        (recover_on[:, None, None] & (land < INF)).reshape(-1))
    meta_order, n_meta = _compact(
        (recover_on[:, None] & fin.any(axis=2)).reshape(-1))
    land = land.reshape(-1)
    # loop carries start from query data so that, under a shard_map, their
    # varying-axes type matches the per-query values they accumulate
    big = jnp.int32(1 << 30) + n_side * 0   # above any finite or INF sum
    lid_src = ctx.lid[ctx.src]
    lid_dst = jnp.clip(ctx.lid[ctx.dst], 0, None)
    lm_src, lm_dst = ctx.is_landmark[ctx.src], ctx.is_landmark[ctx.dst]

    def side_block(jobs, valid):
        qb, k, on_v = jobs // (2 * r), (jobs // 2) % r, jobs % 2 == 1
        depth = jnp.where(on_v[:, None], depth_v[qb], depth_u[qb])
        sigma = jnp.where(valid, land[jobs], INF)
        ld = _label_cols(ctx, k)
        ls, ldd = ld[:, ctx.src], ld[:, ctx.dst]
        dec = ctx.gminus_e & (ldd < INF) & (ldd == ls - 1)
        inc = ctx.gminus_e & (ls < INF) & (ls == ldd - 1)
        edges = jax.vmap(
            lambda *a: _side_attach(ctx, *a, max_chain=max_chain)
        )(depth, sigma, ld, dec, inc, k)
        return _or_by_query(edges, qb, valid, b)

    def meta_block(jobs, valid):
        qb, i = jobs // r, jobs % r
        row = fin[qb, i]                                      # (J, R)
        w_row = w[i]

        def column(j, c):
            t, near = c
            ld_j = _label_col(ctx, j)[None]
            on = row[:, j, None]
            w_j = w_row[:, j, None]
            return (jnp.minimum(t, jnp.where(on, ld_j - w_j, big)),
                    near | (on & (ld_j == w_j - 1)))

        t0 = jnp.full((jobs.shape[0], ctx.is_landmark.shape[0]), big)
        t, near = jax.lax.fori_loop(0, r, column, (t0, t0 < 0))
        ls = _label_cols(ctx, i)[:, ctx.src]
        from_ri = lid_src[None] == i[:, None]
        interior = ctx.gminus_e & (ls + t[:, ctx.dst] == -1)
        # boundary hops r_i -> y (ld[y, j] == w[i,j] - 1) and x -> r_j
        # (ld[x, i] == w[i,j] - 1), then the direct r_i - r_j edges
        hop_a = from_ri & ~lm_dst & near[:, ctx.dst]
        hop_b = lm_dst & ~lm_src & (
            ls == jnp.where(row, w_row - 1, -1)[:, lid_dst])
        direct = from_ri & lm_dst & (row & (w_row == 1))[:, lid_dst]
        edges = interior | hop_a | hop_b | direct
        return _or_by_query(edges, qb, valid, b)

    edges = recover_on[:, None] & (ctx.src < 0)[None]       # all False
    edges = _for_blocks(side_block, side_order, n_side, b, edges)
    edges = _for_blocks(meta_block, meta_order, n_meta, b, edges)
    return edges, n_side + n_meta


# ---------------------------------------------------------------------------
# Guided search of one query chunk
# ---------------------------------------------------------------------------

def search_chunk(ctx: SearchContext, queries: Query, n_vertices: int,
                 max_levels: int = 64, max_chain: int = 64) -> SearchResult:
    """Guided search for a chunk of queries (``queries`` carries the batch
    axis B): BFS and reverse per query, vmapped; recover once for the
    chunk over the jobs its sketches name (``recover_chunk``)."""

    def per_query(q):
        with scope("qbs.bfs"):
            depth_u, depth_v, d_u, d_v, _, _, met = bidirectional_bfs(
                ctx, q, n_vertices, max_levels
            )
        common = (depth_u < INF) & (depth_v < INF)
        sums = jnp.where(common, depth_u + depth_v, INF)
        d_minus = jnp.min(sums)
        with scope("qbs.reverse"):
            e_rev = reverse_search(ctx, depth_u, depth_v, d_minus, n_vertices)
        e_rev = e_rev & met & (d_minus <= q.d_top)
        return depth_u, depth_v, e_rev, d_minus, d_u, d_v

    depth_u, depth_v, e_rev, d_minus, d_u, d_v = jax.vmap(per_query)(queries)
    recover_on = (queries.d_top < INF) & (queries.d_top <= d_minus)
    with scope("qbs.recover"):
        e_rec, jobs = recover_chunk(ctx, queries, depth_u, depth_v,
                                    recover_on, max_chain)

    trivial = queries.u == queries.v
    edge_mask = (e_rev | e_rec) & ~trivial[:, None]
    dist = jnp.where(trivial, 0, jnp.minimum(d_minus, queries.d_top))
    return SearchResult(edge_mask=edge_mask, dist=dist.astype(jnp.int32),
                        d_minus=d_minus.astype(jnp.int32), d_u=d_u, d_v=d_v,
                        recover_jobs=jobs)
