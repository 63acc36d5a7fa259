"""Recover over the jobs the sketch names (``search.recover_chunk``)
against the per-landmark recover it replaced, kept here as the
reference: for every query of a chunk it walks all R landmarks, running
both side attachments and the (iii) terms whether or not the sketch
names them.  The two must give bit-identical edge masks, and the job
count must be what the sketch names and ``/metrics`` exports."""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import INF, QbSIndex, barabasi_albert_graph, grid_graph
from repro.core.packing import widen_dist
from repro.core.search import (
    Query,
    _label_col,
    _side_attach,
    bidirectional_bfs,
    make_search_context,
    recover_chunk,
    reverse_search,
    search_chunk,
)
from repro.core.sketch import compute_sketch_batch
from repro.serving import ManualClock, MetricsRegistry, StreamingService

MAX_LEVELS = MAX_CHAIN = 64


def recover_reference(ctx, q, depth_u, depth_v, max_chain: int):
    """One query's recover, landmark by landmark (components (i)/(ii) on
    both sides plus (iii)), before ``recover_on`` gates it."""
    r = ctx.label_dist.shape[1]
    w = widen_dist(ctx.meta_w)
    fin = (w < INF) & q.meta_edge
    m2 = jnp.where(fin, -w, INF).astype(jnp.int32)   # (i, j)
    g1 = jnp.where(fin, w - 1, -1)                   # (i, j)
    lid_src = jnp.clip(ctx.lid[ctx.src], 0, None)
    lid_dst = jnp.clip(ctx.lid[ctx.dst], 0, None)
    zero = q.u * 0
    big = jnp.int32(1 << 30) + zero

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

        def t_step(j, t):
            return jnp.minimum(t, _label_col(ctx, j) + m2[k, j])

        t_k = jax.lax.fori_loop(0, r, t_step, jnp.full(ld.shape, big))
        minval = jnp.minimum(minval, ls + t_k[ctx.dst])
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
    direct = lm_src & lm_dst & q.meta_edge[lid_src, lid_dst] & (
        w[lid_src, lid_dst] == 1)
    return edges | interior | hops | direct


@partial(jax.jit, static_argnames="n_vertices")
def reference_chunk(ctx, queries, n_vertices: int):
    """The guided search as it ran per query before: ``(dist, edge_mask,
    recover mask gated by recover_on, depth_u, depth_v, recover_on)``."""

    def one(q):
        depth_u, depth_v, _, _, _, _, met = bidirectional_bfs(
            ctx, q, n_vertices, MAX_LEVELS)
        common = (depth_u < INF) & (depth_v < INF)
        d_minus = jnp.min(jnp.where(common, depth_u + depth_v, INF))
        reverse_on = met & (d_minus <= q.d_top)
        recover_on = (q.d_top < INF) & (q.d_top <= d_minus)
        e_rev = reverse_search(ctx, depth_u, depth_v, d_minus, n_vertices)
        e_rec = recover_reference(ctx, q, depth_u, depth_v, MAX_CHAIN)
        e_rec = e_rec & recover_on
        trivial = q.u == q.v
        mask = ((e_rev & reverse_on) | e_rec) & ~trivial
        dist = jnp.where(trivial, 0, jnp.minimum(d_minus, q.d_top))
        return dist, mask, e_rec, depth_u, depth_v, recover_on

    return jax.vmap(one)(queries)


_recover = jax.jit(partial(recover_chunk, max_chain=MAX_CHAIN))


def _sketched(idx, us, vs) -> Query:
    sk = compute_sketch_batch(idx.packed.label_dist[us],
                              idx.packed.label_dist[vs],
                              idx.packed.meta_w, idx.packed.meta_dist)
    return Query(u=jnp.asarray(us), v=jnp.asarray(vs), d_top=sk.d_top,
                 du_land=sk.du_land, dv_land=sk.dv_land,
                 meta_edge=sk.meta_edge, d_star_u=sk.d_star_u,
                 d_star_v=sk.d_star_v)


def _pairs(idx, b, seed):
    rng = np.random.default_rng(seed)
    cand = np.flatnonzero(~idx._is_landmark_np)
    return (rng.choice(cand, b).astype(np.int32),
            rng.choice(cand, b).astype(np.int32))


def _expected_jobs(ctx, queries, recover_on) -> int:
    """Finite side entries plus non-empty meta rows of recover_on queries."""
    on = np.asarray(recover_on)
    w = np.asarray(widen_dist(ctx.meta_w))
    side = ((np.asarray(queries.du_land) < INF).sum(1)
            + (np.asarray(queries.dv_land) < INF).sum(1))
    rows = ((w < INF)[None] & np.asarray(queries.meta_edge)).any(2).sum(1)
    return int((side + rows)[on].sum())


@pytest.fixture(scope="module")
def pa_index():
    return QbSIndex.build(barabasi_albert_graph(300, 3, seed=1),
                          n_landmarks=6, chunk=8)


@pytest.fixture(scope="module")
def grid_index():
    return QbSIndex.build(grid_graph(8, 8), n_landmarks=4, chunk=8)


def _real(idx, seed=0):
    us, vs = _pairs(idx, idx.chunk, seed)
    return idx.ctx, _sketched(idx, us, vs), None


def _all_active(idx):
    """Every sketch entry finite and every meta edge present: all 2R side
    blocks and every meta-row block run."""
    ctx, q, _ = _real(idx, seed=1)
    w = widen_dist(ctx.meta_w)
    q = q._replace(du_land=jnp.where(q.du_land < INF, q.du_land, 3),
                   dv_land=jnp.where(q.dv_land < INF, q.dv_land, 2),
                   meta_edge=jnp.broadcast_to(w < INF, q.meta_edge.shape))
    return ctx, q, jnp.ones((idx.chunk,), bool)


def _no_jobs(idx):
    """The Bi-BFS degeneration: no landmark, every d_top INF."""
    ctx = make_search_context(idx.graph, None)
    us, vs = _pairs(idx, idx.chunk, 2)
    b = us.size
    inf = jnp.full((b,), INF, jnp.int32)
    q = Query(u=jnp.asarray(us), v=jnp.asarray(vs), d_top=inf,
              du_land=inf[:, None], dv_land=inf[:, None],
              meta_edge=jnp.zeros((b, 1, 1), bool),
              d_star_u=inf * 0, d_star_v=inf * 0)
    return ctx, q, None


def _partial_block(idx):
    """B + 3 side jobs and 5 meta-row jobs: each list's last block is
    only part full."""
    ctx, q, _ = _all_active(idx)
    b, r = q.du_land.shape
    rng = np.random.default_rng(4)
    side = np.zeros(2 * r * b, bool)
    side[rng.choice(side.size, b + 3, replace=False)] = True
    side = side.reshape(b, r, 2)
    rows = np.zeros(b * r, bool)
    rows[rng.choice(rows.size, 5, replace=False)] = True
    rows = rows.reshape(b, r)
    q = q._replace(du_land=jnp.where(side[..., 0], q.du_land, INF),
                   dv_land=jnp.where(side[..., 1], q.dv_land, INF),
                   meta_edge=q.meta_edge & rows[:, :, None])
    return ctx, q, jnp.ones((b,), bool)


def _recover_off(idx):
    """Every entry finite, but half the queries have recover_on false:
    their jobs are skipped and their rows stay empty."""
    ctx, q, on = _all_active(idx)
    return ctx, q, on & (jnp.arange(on.size) % 2 == 0)


CASES = {
    "preferential-attachment": ("pa", _real),
    "grid-ties": ("grid", _real),
    "all-active": ("pa", _all_active),
    "no-jobs": ("pa", _no_jobs),
    "partial-block": ("pa", _partial_block),
    "recover-off": ("grid", _recover_off),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_recover_jobs_match_the_per_landmark_recover(case, request):
    which, make = CASES[case]
    idx = request.getfixturevalue(f"{which}_index")
    ctx, q, forced_on = make(idx)
    n_v = idx.graph.n_vertices
    dist, mask, want, depth_u, depth_v, recover_on = reference_chunk(
        ctx, q, n_v)
    if forced_on is not None:
        recover_on = forced_on
        want = jax.vmap(partial(recover_reference, max_chain=MAX_CHAIN),
                        in_axes=(None, 0, 0, 0))(ctx, q, depth_u, depth_v)
        want = want & recover_on[:, None]
    got, jobs = _recover(ctx, q, depth_u, depth_v, recover_on)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    n = _expected_jobs(ctx, q, recover_on)
    assert int(jobs) == n
    b, r = q.du_land.shape
    on = np.asarray(recover_on)
    if case == "all-active":
        assert n > (2 * r - 1) * b                   # every side block runs
    elif case == "no-jobs":
        assert n == 0 and not np.asarray(got).any()
    elif case == "partial-block":
        assert n == b + 3 + 5
    elif case == "recover-off":
        assert not np.asarray(got)[~on].any() and np.asarray(got)[on].any()
    else:                                             # real sketches
        assert on.any() and np.asarray(got).any()
    if forced_on is None:
        res = jax.jit(partial(search_chunk, n_vertices=n_v,
                              max_levels=MAX_LEVELS, max_chain=MAX_CHAIN))(
            ctx, q)
        assert np.array_equal(np.asarray(res.dist), np.asarray(dist))
        assert np.array_equal(np.asarray(res.edge_mask), np.asarray(mask))
        assert int(res.recover_jobs) == n


@pytest.mark.parametrize("which", ["pa", "grid"])
def test_recover_jobs_are_counted_and_exported(which, request):
    """One full chunk of general pairs through a streaming service: the
    general program's job count reaches ``stats["recover_jobs"]`` and
    ``/metrics`` as ``qbs_recover_jobs_total``."""
    idx = request.getfixturevalue(f"{which}_index")
    rng = np.random.default_rng(7)
    non = rng.permutation(np.flatnonzero(~idx._is_landmark_np))
    us, vs = non[:idx.chunk], non[idx.chunk:2 * idx.chunk]
    svc = StreamingService(idx, clock=ManualClock())
    svc.query_batch(us, vs)
    assert svc.stats["chunks"] == 1 and svc.stats["padded_rows"] == 0
    q = _sketched(idx, us.astype(np.int32), vs.astype(np.int32))
    *_, recover_on = reference_chunk(idx.ctx, q, idx.graph.n_vertices)
    want = _expected_jobs(idx.ctx, q, recover_on)
    assert want > 0
    assert svc.stats["recover_jobs"] == want
    reg = MetricsRegistry()
    reg.register("r0", svc)
    assert f'qbs_recover_jobs_total{{service="r0"}} {want}' in reg.render_text()
