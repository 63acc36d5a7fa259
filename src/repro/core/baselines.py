"""Baselines from the paper (§3, §6.1) plus the exact oracle.

* ``bfs_spg``      — textbook oracle: two full BFSs; an edge (x, y) lies on a
                     shortest u-v path iff d_u(x) + 1 + d_v(y) == d(u, v).
* ``bibfs_spg``    — the paper's search baseline (Bi-BFS): implemented as a
                     degenerate guided search with an empty landmark set,
                     which is exactly what QbS reduces to without a sketch.
* ``PPL``          — pruned path labelling (Algorithm 1): PLL with the
                     equal-distance pruning removed so 2-hop *path* cover
                     holds; recursive query answering.
* ``ParentPPL``    — PPL labels + per-label parent sets; parents accelerate
                     edge emission, the recursion guarantees exactness.

PPL/ParentPPL are host-side (numpy): they are comparison baselines whose
role in the paper is to demonstrate non-scalability (Tables 2-3); the
level-synchronous inner BFS is vectorized, the landmark loop is inherently
sequential because pruning depends on all previous labels.
"""
from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np

from .frontier import bfs_depths, make_relay
from .graph import INF, Graph
from .qbs import SPGResult, _reverse_edge_map
from .search import Query, make_search_context, search_chunk

# ---------------------------------------------------------------------------
# Oracle
# ---------------------------------------------------------------------------


def bfs_distances(graph: Graph, root: int, max_levels: int = 256,
                  backend: str = "segment") -> np.ndarray:
    return np.asarray(
        bfs_depths(make_relay(graph, backend=backend), jnp.int32(root), max_levels)
    )


def bfs_spg(graph: Graph, u: int, v: int, max_levels: int = 256,
            backend: str = "segment") -> SPGResult:
    """Exact oracle via two full BFSs (O(E) each, no pruning)."""
    engine = make_relay(graph, backend=backend)
    du = bfs_depths(engine, jnp.int32(u), max_levels)
    dv = bfs_depths(engine, jnp.int32(v), max_levels)
    d = int(du[v])
    if u == v:
        return SPGResult(u=u, v=v, dist=0, edge_ids=np.zeros((0,), np.int64), d_top=INF)
    mask = np.asarray((du[graph.src] + 1 + dv[graph.dst]) == d)
    rev = _reverse_edge_map(np.asarray(graph.src), np.asarray(graph.dst), graph.n_vertices)
    mask = mask | mask[rev]
    return SPGResult(u=u, v=v, dist=d, edge_ids=np.flatnonzero(mask), d_top=INF)


# ---------------------------------------------------------------------------
# Bi-BFS baseline = guided search with an empty landmark set
# ---------------------------------------------------------------------------


@lru_cache(maxsize=8)
def _bibfs_search_step(n_vertices: int, max_levels: int):
    """One jitted degenerate chunk search per (V, max_levels).  The
    search context rides in as a pytree argument, so every same-sized
    graph/backend shares this entry; constructing ``jax.jit`` inside
    ``bibfs_spg_batch`` instead would recompile on every call (QBS004).
    With no landmark every sketch entry is INF, so recover gets no job."""
    return jax.jit(partial(search_chunk, n_vertices=n_vertices,
                           max_levels=max_levels, max_chain=1))


def bibfs_spg_batch(graph: Graph, us, vs, max_levels: int = 512,
                    backend: str = "segment") -> list[SPGResult]:
    us = np.asarray(us, np.int32).reshape(-1)
    vs = np.asarray(vs, np.int32).reshape(-1)
    # empty landmark set -> G- == G, the Bi-BFS degeneration
    ctx = make_search_context(graph, None, backend=backend)
    b = us.shape[0]
    inf = jnp.int32(INF)
    zero = jnp.int32(0)
    queries = Query(
        u=jnp.asarray(us), v=jnp.asarray(vs),
        d_top=jnp.full((b,), inf),
        du_land=jnp.full((b, 1), inf), dv_land=jnp.full((b, 1), inf),
        meta_edge=jnp.zeros((b, 1, 1), bool),
        d_star_u=jnp.full((b,), zero), d_star_v=jnp.full((b,), zero),
    )
    step = _bibfs_search_step(graph.n_vertices, max_levels)
    res = step(ctx, queries)
    rev = _reverse_edge_map(np.asarray(graph.src), np.asarray(graph.dst), graph.n_vertices)
    mask = np.asarray(res.edge_mask)
    mask = mask | mask[:, rev]
    dists = np.asarray(res.dist)
    return [
        SPGResult(u=int(us[k]), v=int(vs[k]), dist=int(dists[k]),
                  edge_ids=np.flatnonzero(mask[k]), d_top=INF)
        for k in range(b)
    ]


def bibfs_spg(graph: Graph, u: int, v: int, max_levels: int = 512,
              backend: str = "segment") -> SPGResult:
    return bibfs_spg_batch(graph, [u], [v], max_levels=max_levels,
                           backend=backend)[0]


# ---------------------------------------------------------------------------
# PPL — pruned path labelling (Algorithm 1)
# ---------------------------------------------------------------------------


class PPLIndex:
    """Pruned path labelling over *all* vertices in degree order.

    Labels are a dense (V, V) int32 matrix in vertex-order index space with
    INF for pruned entries (fine at baseline scales; the paper's point is
    that this family cannot scale, which the dense footprint makes vivid).
    """

    def __init__(self, graph: Graph, store_parents: bool = False,
                 max_levels: int = 256):
        self.graph = graph
        self.store_parents = store_parents
        v = graph.n_vertices
        deg = np.asarray(graph.degrees())
        self.order = np.argsort(-deg, kind="stable").astype(np.int32)
        src = np.asarray(graph.src)
        dst = np.asarray(graph.dst)
        indptr = np.asarray(graph.indptr)
        self._adj = (indptr, dst)
        real = src != dst
        self._edge_set = set(zip(src[real].tolist(), dst[real].tolist()))

        lab = np.full((v, v), INF, np.int64)  # (vertex, landmark-rank)
        parents: dict[tuple[int, int], list[int]] = {}
        for k, vk in enumerate(self.order):
            depth = np.full((v,), INF, np.int64)
            depth[vk] = 0
            frontier = np.zeros((v,), bool)
            frontier[vk] = True
            level = 0
            while frontier.any() and level < max_levels:
                f_idx = np.flatnonzero(frontier)
                # d_{L_{k-1}}(v_k, u) via already-built labels
                dq = (lab[f_idx, :] + lab[vk, None, :]).min(axis=1)
                dq = np.minimum(dq, INF)
                keep = dq >= depth[f_idx]          # label unless strictly covered
                expand = dq > depth[f_idx]         # expand only if strictly better
                labelled = f_idx[keep]
                lab[labelled, k] = depth[labelled]
                if store_parents and level > 0:
                    for uu in labelled:
                        s, e = indptr[uu], indptr[uu + 1]
                        nb = dst[s:e]
                        ps = nb[depth[nb] == depth[uu] - 1]
                        if ps.size:
                            parents[(int(uu), k)] = ps.tolist()
                nxt = np.zeros((v,), bool)
                for uu in f_idx[expand]:
                    s, e = indptr[uu], indptr[uu + 1]
                    nb = dst[s:e]
                    fresh = nb[depth[nb] == INF]
                    depth[fresh] = level + 1
                    nxt[fresh] = True
                frontier = nxt
                level += 1
        self.lab = lab
        self.parents = parents
        self.rank_to_vertex = self.order
        self.vertex_to_rank = np.empty((v,), np.int64)
        self.vertex_to_rank[self.order] = np.arange(v)

    def label_entries(self) -> int:
        return int((self.lab < INF).sum())

    def dist(self, u: int, v: int) -> int:
        return int(min(np.min(self.lab[u] + self.lab[v]), INF))

    def query(self, u: int, v: int) -> SPGResult:
        """Recursive SPG answering (§3.2), memoized over sub-queries."""
        edges: set[tuple[int, int]] = set()
        memo: set[tuple[int, int]] = set()

        def solve(a: int, b: int) -> None:
            if a == b:
                return
            key = (min(a, b), max(a, b))
            if key in memo:
                return
            memo.add(key)
            d = int(min(np.min(self.lab[a] + self.lab[b]), INF))
            if d >= INF:
                return
            if d == 1:
                edges.add(key)
                return
            sums = self.lab[a] + self.lab[b]
            ranks = np.flatnonzero(sums == d)
            for k in ranks:
                r = int(self.rank_to_vertex[k])
                if r in (a, b):
                    continue
                if self.store_parents:
                    self._emit_parent_walk(a, k, edges)
                    self._emit_parent_walk(b, k, edges)
                solve(a, r)
                solve(b, r)

        solve(u, v)
        d = self.dist(u, v)
        return SPGResult(u=u, v=v, dist=d,
                         edge_ids=self._edges_to_ids(edges), d_top=INF)

    def _emit_parent_walk(self, x: int, rank: int, edges: set) -> None:
        """ParentPPL accelerator: emit tree edges along stored parent sets."""
        stack = [x]
        seen = {x}
        r = int(self.rank_to_vertex[rank])
        while stack:
            cur = stack.pop()
            if self.lab[cur, rank] == 1:
                edges.add((min(cur, r), max(cur, r)))
                continue
            for p in self.parents.get((cur, rank), ()):
                edges.add((min(cur, p), max(cur, p)))
                if p not in seen:
                    seen.add(p)
                    stack.append(p)

    def _edges_to_ids(self, edges: set[tuple[int, int]]) -> np.ndarray:
        src = np.asarray(self.graph.src)
        dst = np.asarray(self.graph.dst)
        if not edges:
            return np.zeros((0,), np.int64)
        es = np.asarray(sorted(edges), np.int64)
        keys = src.astype(np.int64) * self.graph.n_vertices + dst
        order = np.argsort(keys)
        want = np.concatenate([
            es[:, 0] * self.graph.n_vertices + es[:, 1],
            es[:, 1] * self.graph.n_vertices + es[:, 0],
        ])
        pos = np.searchsorted(keys[order], want)
        pos = np.clip(pos, 0, keys.size - 1)
        ids = order[pos]
        ok = keys[ids] == want
        return np.unique(ids[ok])

    def memory_bytes(self) -> int:
        n_labels = self.label_entries()
        per = 5  # 32-bit landmark id + 8-bit distance (paper's accounting)
        if self.store_parents:
            per += 0  # parents accounted separately below
        total = n_labels * per
        if self.store_parents:
            total += sum(4 * len(p) for p in self.parents.values())
        return total
