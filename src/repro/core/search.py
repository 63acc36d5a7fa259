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

Everything is fixed-shape and vmap-able over a query batch.
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
    edge_mask: jax.Array  # (E,) bool, path-direction orientation marks
    dist: jax.Array       # () int32, INF if disconnected
    d_minus: jax.Array    # () int32 d_{G-}(u, v), INF if balls never met
    d_u: jax.Array        # () int32 explored radius, u side
    d_v: jax.Array        # () int32 explored radius, v side


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
# Every certificate below is a per-landmark test OR-ed (or min-ed) over the
# landmarks, so recover walks the landmarks one at a time and keeps only
# (V,) / (E,) state per query.  The (V, R) / (E, R) forms of the same tests
# do not fit a chip at deployment size: vmapped over a 32-query chunk, one
# (E, R) int32 temporary of a 6.6M-slot graph is 17 GB before tiling.


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


def recover_search(ctx: SearchContext, q: Query, depth_u, depth_v,
                   max_chain: int):
    """Components (i)/(ii) on both sides plus (iii): edges on landmark-free
    shortest r_i - r_j paths for every meta edge in the sketch (the
    paper's precomputed Delta), derived from labels alone.

    For a G- edge (x, y), (iii) holds iff some sketch meta edge (i, j) has
        ld[x,i] + 1 + ld[y,j] == w[i,j]
    By the triangle inequality ld[x,i] + ld[y,j] - w[i,j] >= -1, so the
    existential test is  min_{i,j} masked(ld[x,i] + ld[y,j] - w[i,j]) == -1,
    taken one landmark i at a time through
        t_i[y] = min_j ( ld[y, j] + (-w[i, j] | INF) ).
    """
    r = ctx.label_dist.shape[1]
    w = widen_dist(ctx.meta_w)
    fin = (w < INF) & q.meta_edge
    m2 = jnp.where(fin, -w, INF).astype(jnp.int32)   # (i, j)
    g1 = jnp.where(fin, w - 1, -1)                   # (i, j)
    lid_src = jnp.clip(ctx.lid[ctx.src], 0, None)
    lid_dst = jnp.clip(ctx.lid[ctx.dst], 0, None)
    # loop carries start from query data so that, under a shard_map, their
    # varying-axes type matches the per-query values they accumulate
    zero = q.u * 0
    big = jnp.int32(1 << 30) + zero   # above any finite or INF-saturated sum

    def per_landmark(k, c):
        edges, minval, hop_a, hop_b = c
        ld = _label_col(ctx, k)
        ls, ldd = ld[ctx.src], ld[ctx.dst]
        dec = ctx.gminus_e & (ldd < INF) & (ldd == ls - 1)
        inc = ctx.gminus_e & (ls < INF) & (ls == ldd - 1)
        edges = edges | _side_attach(ctx, depth_u, q.du_land[k], ld, dec, inc,
                                     k, max_chain)
        edges = edges | _side_attach(ctx, depth_v, q.dv_land[k], ld, dec, inc,
                                     k, max_chain)

        # (iii) interior, landmark i = k
        def t_step(j, t):
            return jnp.minimum(t, _label_col(ctx, j) + m2[k, j])

        t_k = jax.lax.fori_loop(0, r, t_step, jnp.full(ld.shape, big))
        minval = jnp.minimum(minval, ls + t_k[ctx.dst])
        # (iii) boundary hops r_i -> y (ld[y, j] == w[i,j] - 1) and
        # x -> r_j, column j = k
        hop_a = hop_a | (ldd == g1[lid_src, k])
        hop_b = hop_b | (ls == g1[k, lid_dst])
        return edges, minval, hop_a, hop_b

    false_e = ctx.gminus_e & (zero != 0)
    init = (false_e, jnp.full(ctx.src.shape, big), false_e, false_e)
    edges, minval, hop_a, hop_b = jax.lax.fori_loop(0, r, per_landmark, init)

    lm_src = ctx.is_landmark[ctx.src]
    lm_dst = ctx.is_landmark[ctx.dst]
    interior = ctx.gminus_e & (minval == -1)
    hops = (lm_src & ~lm_dst & hop_a) | (lm_dst & ~lm_src & hop_b)
    # Direct landmark-landmark sketch edges of weight 1.
    direct = lm_src & lm_dst & q.meta_edge[lid_src, lid_dst] & (
        w[lid_src, lid_dst] == 1)
    return edges | interior | hops | direct


# ---------------------------------------------------------------------------
# Full guided search for one query
# ---------------------------------------------------------------------------

def guided_search(ctx: SearchContext, q: Query, n_vertices: int,
                  max_levels: int = 64, max_chain: int = 64) -> SearchResult:
    with scope("qbs.bfs"):
        depth_u, depth_v, d_u, d_v, _, _, met = bidirectional_bfs(
            ctx, q, n_vertices, max_levels
        )

    common = (depth_u < INF) & (depth_v < INF)
    sums = jnp.where(common, depth_u + depth_v, INF)
    d_minus = jnp.min(sums)

    dist = jnp.minimum(d_minus, q.d_top)
    reverse_on = met & (d_minus <= q.d_top)
    recover_on = (q.d_top < INF) & (q.d_top <= d_minus)

    with scope("qbs.reverse"):
        e_rev = reverse_search(ctx, depth_u, depth_v, d_minus, n_vertices)
    with scope("qbs.recover"):
        e_rec = recover_search(ctx, q, depth_u, depth_v, max_chain)

    trivial = q.u == q.v
    edge_mask = ((e_rev & reverse_on) | (e_rec & recover_on)) & ~trivial
    dist = jnp.where(trivial, 0, dist)
    return SearchResult(edge_mask=edge_mask, dist=dist.astype(jnp.int32),
                        d_minus=d_minus.astype(jnp.int32), d_u=d_u, d_v=d_v)
