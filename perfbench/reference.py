"""Plain numpy reference for shortest-path-graph (SPG) answers.

The benchmark's own copy of the repository's numpy oracle, independent of
the program: it imports nothing from it and takes nothing it made.  It
holds a graph as its own CSR over the undirected edge list the benchmark
generated, answers ``(dist, edge_ids)`` for a pair with two breadth-first
searches, and follows edge updates with plain set algebra.

Edge ids are directed edge-slot numbers.  The layout is the one the
program documents for ``from_edges``: slots sorted by source vertex, and
under one source first the neighbours above it, then those below it,
each ascending.  ``edge_ids`` of an answer are every slot (both
orientations) that lies on some shortest path: the guarantee the
configurations state.
"""
from __future__ import annotations

import numpy as np

INF = 1 << 20      # the "no path" distance of the served answers


class RefGraph:
    """One graph version: CSR arrays plus a memo of BFS depth rows."""

    def __init__(self, edges: np.ndarray, n_vertices: int, memo_roots=()):
        keys = self.canonical_keys(edges, n_vertices)
        keys = keys[keys // n_vertices != keys % n_vertices]
        self.n = n_vertices
        self.keys = keys                       # canonical lo * n + hi
        lo, hi = keys // n_vertices, keys % n_vertices
        m = keys.size
        s = np.concatenate([lo, hi])
        d = np.concatenate([hi, lo])
        # by source; under one source the upper neighbours (first half,
        # already ascending) before the lower ones (second half, ascending)
        order = np.argsort(s * 2 + (d < s), kind="stable")
        self.src = s[order]
        self.dst = d[order]
        self.indptr = np.searchsorted(self.src, np.arange(n_vertices + 1))
        # rev[i]: the slot of edge i in the other orientation; entry j of
        # the first half and entry j of the second are one edge
        slot = np.empty(2 * m, np.int64)
        slot[order] = np.arange(2 * m)
        self.rev = np.empty(2 * m, np.int64)
        self.rev[slot[:m]] = slot[m:]
        self.rev[slot[m:]] = slot[:m]
        self.memo_roots = {int(r) for r in memo_roots}
        self._depth: dict[int, np.ndarray] = {}

    @staticmethod
    def canonical_keys(edges: np.ndarray, n_vertices: int) -> np.ndarray:
        """Sorted ``lo * n + hi`` keys of an undirected edge list."""
        e = np.asarray(edges, np.int64).reshape(-1, 2)
        return np.unique(np.minimum(e[:, 0], e[:, 1]) * n_vertices
                         + np.maximum(e[:, 0], e[:, 1]))

    def depths(self, root: int) -> np.ndarray:
        """BFS hop counts from ``root`` (``INF`` where unreachable)."""
        got = self._depth.get(root)
        if got is not None:
            return got
        depth = np.full(self.n, INF, np.int64)
        depth[root] = 0
        frontier = np.array([root], np.int64)
        level = 0
        deg = self.indptr[1:] - self.indptr[:-1]
        left = self.src.size - deg[root]       # slots out of unvisited vertices
        while frontier.size:
            out = int(deg[frontier].sum())
            if out <= left:                    # push: scan the frontier's slots
                sl = self._slots(frontier)
                nb = self.dst[sl]
                nb = nb[depth[nb] == INF]
            else:                              # pull: scan the unvisited ones
                sl = self._slots(np.flatnonzero(depth == INF))
                nb = self.src[sl[depth[self.dst[sl]] == level]]
            level += 1
            depth[nb] = level
            frontier = np.flatnonzero(depth == level) if nb.size else nb
            left -= int(deg[frontier].sum())
        if root in self.memo_roots:
            self._depth[root] = depth
        return depth

    def _slots(self, vertices: np.ndarray) -> np.ndarray:
        """Every slot out of ``vertices``, in one gather."""
        start = self.indptr[vertices]
        cnt = self.indptr[vertices + 1] - start
        return np.repeat(start - np.cumsum(cnt) + cnt, cnt) + np.arange(cnt.sum())

    def spg(self, u: int, v: int) -> tuple[int, np.ndarray]:
        """``(dist, edge_ids)``: ``dist`` is ``INF`` without a path and 0
        (no edges) for ``u == v``; ``edge_ids`` ascending."""
        if u == v:
            return 0, np.zeros(0, np.int64)
        du, dv = self.depths(u), self.depths(v)
        d = int(du[v])
        if d >= INF:
            return INF, np.zeros(0, np.int64)
        # only slots out of vertices on some shortest path can qualify
        sl = self._slots(np.flatnonzero(du + dv == d))
        sl = sl[du[self.src[sl]] + 1 + dv[self.dst[sl]] == d]
        return d, np.unique(np.concatenate([sl, self.rev[sl]]))

    def one_path(self, u: int, v: int) -> tuple[int, np.ndarray]:
        """The control: one shortest path (both orientations of its edges)
        instead of all of them.  Breaks the "every shortest path"
        guarantee wherever two shortest paths exist."""
        d, eids = self.spg(u, v)
        if d == 0 or d >= INF:
            return d, eids
        du = self.depths(u)
        dv = self.depths(v)
        path, x = [], u
        ip = self.indptr
        for _ in range(d):
            sl = np.arange(ip[x], ip[x + 1])
            nxt = sl[(du[self.dst[sl]] == du[x] + 1)
                     & (dv[self.dst[sl]] == dv[x] - 1)][0]
            path.append(nxt)
            x = int(self.dst[nxt])
        path = np.asarray(path, np.int64)
        return d, np.sort(np.concatenate([path, self.rev[path]]))

    def updated(self, inserts: np.ndarray, deletes: np.ndarray) -> "RefGraph":
        """The next version: ``inserts`` added, ``deletes`` removed (an
        edge in both is inserted)."""
        n = self.n

        def keys_of(pairs):
            p = np.asarray(pairs, np.int64).reshape(-1, 2)
            return np.unique(np.minimum(p[:, 0], p[:, 1]) * n
                             + np.maximum(p[:, 0], p[:, 1]))

        ins, dels = keys_of(inserts), keys_of(deletes)
        keys = np.union1d(self.keys[~np.isin(self.keys, dels)], ins)
        return RefGraph(np.stack([keys // n, keys % n], axis=1), n,
                        self.memo_roots)
