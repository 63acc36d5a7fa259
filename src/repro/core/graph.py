"""Fixed-shape CSR graph representation, builders and synthetic generators.

The QbS engine operates on unweighted, undirected graphs stored as a
symmetrized directed edge list (every undirected edge appears in both
orientations) plus a CSR ``indptr``.  All shapes are static so every phase
jits cleanly; padding uses self-loops on the last vertex (an isolated
padding vertex where ``pad_vertices_to`` asks for one), which are no-ops
for level-synchronous BFS (a self loop re-delivers a message to an
already-visited vertex) and lie on no shortest path.  ``EdgeSlots`` is the
host mirror the update path merges edge batches into.
"""
from __future__ import annotations

from functools import cached_property
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

# Distance sentinel.  Small enough that INF + INF + INF fits int32 with room
# to spare, large enough to exceed any real distance (max_levels <= 2**14).
INF = 1 << 20
INF_I32 = np.int32(INF)


class Graph(NamedTuple):
    """Symmetrized CSR graph. ``src``/``dst`` are sorted by ``src``."""

    indptr: jax.Array  # (V+1,) int32
    src: jax.Array     # (E,) int32
    dst: jax.Array     # (E,) int32

    @property
    def n_vertices(self) -> int:
        return int(self.indptr.shape[0]) - 1

    @property
    def n_edges(self) -> int:
        """Directed edge-slot count (2x undirected edges + padding)."""
        return int(self.src.shape[0])

    def degrees(self) -> jax.Array:
        return self.indptr[1:] - self.indptr[:-1]

    def hub_mask(self, n_hubs: int | None = None,
                 top_frac: float = 0.01) -> np.ndarray:
        """Host-side ``(V,)`` bool mask of the top-degree "hub" vertices —
        the degree-skew metadata consumers outside the engine key on (the
        serving cache's hub-aware eviction protects entries whose
        endpoints land in this set).  ``n_hubs`` picks an explicit count;
        otherwise the top ``top_frac`` of vertices (at least one).  Ties
        break by vertex id (stable sort), matching ``select_landmarks``.
        Derived from ``frontier.hub_split`` so there is exactly one
        definition of "hub" (self-loop edge padding excluded from the
        degree count: the padding vertex carries every pad slot as a self
        loop and must never rank as a hub)."""
        from .frontier import hub_split

        if n_hubs is None:
            n_hubs = max(1, int(self.n_vertices * top_frac))
        return hub_split(self, int(n_hubs)).is_hub

    def hub_split(self, n_hubs: int | None = None):
        """Degree split for the hybrid frontier backend: the top-``n_hubs``
        vertices by (self-loop-free) degree form a dense hub block, the rest
        stay on the sparse edge-list relay.  Returns a host-side
        ``frontier.HubSplit``; see ``core.frontier`` for the engine that
        consumes it."""
        from .frontier import hub_split

        return hub_split(self, n_hubs)


def from_edges(
    edges: np.ndarray,
    n_vertices: int,
    *,
    pad_vertices_to: int | None = None,
    pad_edges_to: int | None = None,
) -> Graph:
    """Build a symmetrized ``Graph`` from an (M, 2) undirected edge array.

    Self-loops and duplicate edges are dropped.  Optional padding appends
    isolated vertices and self-loop edge slots on the last padding vertex so
    differently-sized test graphs share one jit cache entry.
    """
    edges = np.asarray(edges, dtype=np.int64).reshape(-1, 2)
    mask = edges[:, 0] != edges[:, 1]
    edges = edges[mask]
    if edges.size:
        lo = np.minimum(edges[:, 0], edges[:, 1])
        hi = np.maximum(edges[:, 0], edges[:, 1])
        canon = np.unique(lo * np.int64(n_vertices) + hi)
        lo = (canon // n_vertices).astype(np.int32)
        hi = (canon % n_vertices).astype(np.int32)
        s = np.concatenate([lo, hi])
        d = np.concatenate([hi, lo])
    else:
        s = np.zeros((0,), np.int32)
        d = np.zeros((0,), np.int32)

    n_v = n_vertices
    if pad_vertices_to is not None:
        if pad_vertices_to < n_vertices:
            raise ValueError("pad_vertices_to < n_vertices")
        n_v = pad_vertices_to
    n_e = s.shape[0]
    if pad_edges_to is not None:
        if pad_edges_to < n_e:
            raise ValueError(f"pad_edges_to={pad_edges_to} < {n_e}")
        pad_v = n_v - 1  # isolated when padding vertices were requested
        extra = pad_edges_to - n_e
        s = np.concatenate([s, np.full((extra,), pad_v, np.int32)])
        d = np.concatenate([d, np.full((extra,), pad_v, np.int32)])

    order = np.argsort(s, kind="stable")
    s = s[order].astype(np.int32)
    d = d[order].astype(np.int32)
    indptr = np.zeros((n_v + 1,), np.int64)
    np.add.at(indptr, s + 1, 1)
    indptr = np.cumsum(indptr).astype(np.int32)
    return Graph(jnp.asarray(indptr), jnp.asarray(s), jnp.asarray(d))


def edge_set(graph: Graph) -> np.ndarray:
    """Host-side ``(M, 2)`` canonical undirected edge array (lo < hi, sorted
    lexicographically) of the real edges in ``graph`` — the inverse of
    ``from_edges`` up to padding.  Padding self-loop slots are excluded, so
    the result depends only on the edge *set*, never on pad capacity."""
    s = np.asarray(graph.src, np.int64)
    d = np.asarray(graph.dst, np.int64)
    real = s < d  # one orientation per undirected edge; drops self-loop pads
    return np.stack([s[real], d[real]], axis=1)


def edge_keys(edges: np.ndarray, n_vertices: int) -> np.ndarray:
    """Canonical sorted int64 keys (``lo * V + hi``, self-loops dropped) for
    an (M, 2) undirected edge array — the set-algebra currency shared by
    ``apply_edge_updates`` and ``QbSIndex.apply_update``."""
    edges = np.asarray(edges, np.int64).reshape(-1, 2)
    if edges.size and (edges.min() < 0 or edges.max() >= n_vertices):
        raise ValueError(f"edge endpoint out of range for {n_vertices} vertices")
    edges = edges[edges[:, 0] != edges[:, 1]]
    lo = np.minimum(edges[:, 0], edges[:, 1])
    hi = np.maximum(edges[:, 0], edges[:, 1])
    return np.unique(lo * np.int64(n_vertices) + hi)


def capacity_step(cap: int) -> int:
    """The edge-slot capacity one step above ``cap``: ``cap // 16`` more
    slots (at least one undirected edge's two), so a capacity never grows
    by more than 1/16 at a time once it holds 32 slots."""
    return cap + max(2, cap // 16)


class EdgeSlots:
    """Host mirror of a ``Graph``'s live edge slots, in CSR order.

    Slot ``(s, d)`` has the key ``s * V + (d - s - 1) % V``: by source,
    and under one source the neighbours above it, then those below it,
    each ascending -- the order ``from_edges`` gives.  So one sorted int64
    array is the whole CSR, and an update batch merges into it with
    ``searchsorted`` and ``insert``/``delete`` instead of a sort.  ``rev``
    is each live slot's reverse slot; ``capacity`` counts the padding
    slots too (self loops on vertex ``V - 1``, after every live slot)."""

    def __init__(self, n_vertices: int, keys: np.ndarray, rev: np.ndarray,
                 capacity: int):
        self.n_vertices = n_vertices
        self.keys = keys          # (L,) int64, strictly ascending
        self.rev = rev            # (L,) int64
        self.capacity = capacity

    @classmethod
    def of_graph(cls, graph: Graph) -> "EdgeSlots":
        """The mirror of a device ``Graph`` (one device-to-host copy)."""
        s = np.asarray(graph.src, np.int64)
        d = np.asarray(graph.dst, np.int64)
        live = s != d                  # padding slots are the only self loops
        keys = _slot_keys(s[live], d[live], graph.n_vertices)
        if np.any(keys[1:] <= keys[:-1]):
            raise ValueError("edge list is not in from_edges order")
        rev = np.searchsorted(keys, _slot_keys(d[live], s[live],
                                               graph.n_vertices))
        return cls(graph.n_vertices, keys, rev, graph.n_edges)

    @property
    def n_live(self) -> int:
        return int(self.keys.size)

    @cached_property
    def csr(self) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """``(indptr, src, dst)`` int32 on the host, padding included: the
        arrays of ``graph()``."""
        n = np.int64(self.n_vertices)
        s = self.keys // n
        d = (self.keys % n + s + 1) % n
        pad = np.full(self.capacity - self.n_live, n - 1, np.int64)
        src = np.concatenate([s, pad]).astype(np.int32)
        dst = np.concatenate([d, pad]).astype(np.int32)
        indptr = np.zeros(self.n_vertices + 1, np.int64)
        indptr[1:] = np.cumsum(np.bincount(src, minlength=self.n_vertices))
        return indptr.astype(np.int32), src, dst

    def rev_slots(self) -> np.ndarray:
        """``(capacity,)`` int32 reverse slot of every slot; every padding
        slot maps to the first padding slot."""
        pad = np.full(self.capacity - self.n_live, self.n_live, np.int64)
        return np.concatenate([self.rev, pad]).astype(np.int32)

    def graph(self) -> Graph:
        """The device ``Graph``: bit-identical to ``from_edges`` over the
        same edges at the same capacity, uploaded once."""
        return Graph(*(jnp.asarray(a) for a in self.csr))

    def has(self, keys: np.ndarray) -> np.ndarray:
        """Which canonical edge keys (``lo * V + hi``) are present."""
        n = self.n_vertices
        want = _slot_keys(keys // n, keys % n, n)
        at = np.minimum(np.searchsorted(self.keys, want), max(self.n_live - 1, 0))
        return (self.keys[at] == want) if self.n_live else np.zeros(keys.size, bool)

    def updated(self, inserts: np.ndarray, deletes: np.ndarray) -> "EdgeSlots":
        """The mirror after an effective batch: ``inserts`` are canonical
        keys of absent edges, ``deletes`` of present ones, both sorted and
        disjoint.  O(L + k log L) for a batch of k edges.

        The capacity keeps its step while the live slots fit.  A batch
        that changes a full graph (no free slot, as ``from_edges`` builds
        one) takes one step, and a batch that overflows takes as many as
        it needs -- so a graph that takes no update keeps its exact size,
        and every later batch within a step keeps every shape."""
        n = self.n_vertices
        lo_i, hi_i = inserts // n, inserts % n
        lo_d, hi_d = deletes // n, deletes % n
        gone = np.searchsorted(self.keys, np.concatenate([
            _slot_keys(lo_d, hi_d, n), _slot_keys(hi_d, lo_d, n)]))
        keep = np.ones(self.n_live, bool)
        keep[gone] = False
        old = np.flatnonzero(keep)
        kept = self.keys[keep]
        k = inserts.size
        new = np.concatenate([_slot_keys(lo_i, hi_i, n), _slot_keys(hi_i, lo_i, n)])
        order = np.argsort(new)
        new = new[order]
        at = np.searchsorted(kept, new)
        keys = np.insert(kept, at, new)
        # where each kept slot and each new slot lands
        moved = np.full(self.n_live, -1, np.int64)
        moved[old] = np.arange(kept.size) + np.searchsorted(
            at, np.arange(kept.size), side="right")
        placed = np.empty(2 * k, np.int64)
        placed[order] = at + np.arange(2 * k)
        rev = np.empty(keys.size, np.int64)
        rev[moved[old]] = moved[self.rev[old]]
        rev[placed] = np.concatenate([placed[k:], placed[:k]])
        cap = self.capacity
        if (k or deletes.size) and cap <= self.n_live:
            cap = capacity_step(cap)
        while cap < keys.size:
            cap = capacity_step(cap)
        return EdgeSlots(n, keys, rev, cap)


def _slot_keys(s, d, n: int) -> np.ndarray:
    """CSR-order keys of directed slots ``(s, d)`` (see ``EdgeSlots``)."""
    s = np.asarray(s, np.int64)
    d = np.asarray(d, np.int64)
    return s * np.int64(n) + (d - s - 1) % np.int64(n)


def apply_edge_updates(
    slots: EdgeSlots,
    inserts: np.ndarray | None = None,
    deletes: np.ndarray | None = None,
) -> tuple[EdgeSlots, np.ndarray, np.ndarray]:
    """Apply one batch to a graph's mirror: ``(slots_new, ins, dels)``,
    where ``ins``/``dels`` are the batch's *effective* canonical keys
    (insert-of-absent, delete-of-present; an edge in both is inserted --
    inserts win; self loops dropped).

    ``slots_new.graph()`` is the next epoch's ``Graph``.  The capacity
    moves along ``capacity_step`` (see ``EdgeSlots.updated``), so jitted
    consumers with static shapes keep their compilation cache across the
    epochs of a step.  Because the layout is ``from_edges``' own, the
    edge-slot ids of the surviving edges depend only on the edge set and
    the capacity -- an independently rebuilt graph over the same edges at
    the same capacity is bit-identical.
    """
    n_v = slots.n_vertices
    none = np.zeros((0,), np.int64)
    ins0 = none if inserts is None else edge_keys(inserts, n_v)
    del0 = none if deletes is None else edge_keys(deletes, n_v)
    ins = ins0[~slots.has(ins0)]                  # insert-of-absent only
    dels = del0[slots.has(del0)]                  # delete-of-present only
    dels = dels[~np.isin(dels, ins0)]             # inserts win a tie
    return slots.updated(ins, dels), ins, dels


def to_networkx(graph: Graph):
    import networkx as nx

    g = nx.Graph()
    g.add_nodes_from(range(graph.n_vertices))
    s = np.asarray(graph.src)
    d = np.asarray(graph.dst)
    real = s != d
    g.add_edges_from(zip(s[real].tolist(), d[real].tolist()))
    return g


# ---------------------------------------------------------------------------
# Generators (host-side; the data pipeline is host code in real frameworks).
# ---------------------------------------------------------------------------

def gnp_random_graph(n: int, avg_degree: float, seed: int, **pad) -> Graph:
    """Erdos-Renyi-ish sparse sampler: E = n*avg_degree/2 sampled pairs."""
    rng = np.random.default_rng(seed)
    m = max(1, int(n * avg_degree / 2))
    edges = rng.integers(0, n, size=(m, 2), dtype=np.int64)
    return from_edges(edges, n, **pad)


def barabasi_albert_graph(n: int, m: int, seed: int, **pad) -> Graph:
    """Preferential-attachment generator (hub-heavy, matches social/web
    regimes of the paper: Twitter/Youtube-like degree skew)."""
    rng = np.random.default_rng(seed)
    m = max(1, min(m, n - 1))
    targets = list(range(m))
    repeated: list[int] = []
    edges = []
    for v in range(m, n):
        for t in targets:
            edges.append((v, t))
        repeated.extend(targets)
        repeated.extend([v] * m)
        # sample next targets from the degree-weighted multiset
        idx = rng.integers(0, len(repeated), size=(m,))
        targets = list({repeated[i] for i in idx})
        while len(targets) < m:
            targets.append(int(rng.integers(0, v + 1)))
    return from_edges(np.asarray(edges, np.int64), n, **pad)


def random_regular_graph(n: int, degree: int, seed: int, **pad) -> Graph:
    """~degree-regular random graph via unions of random matchings:
    flat degree distribution AND small diameter — the Friendster regime
    (ring_of_cliques is flat-degree but long-diameter; keep it for tests)."""
    rng = np.random.default_rng(seed)
    edges = []
    for _ in range(max(1, degree // 2)):
        perm = rng.permutation(n)
        edges.append(np.stack([np.arange(n), perm], axis=1))
    return from_edges(np.concatenate(edges), n, **pad)


def ring_of_cliques(n_cliques: int, clique_size: int, seed: int = 0, **pad) -> Graph:
    """Flat-degree, long-diameter stress regime (tests)."""
    edges = []
    n = n_cliques * clique_size
    for c in range(n_cliques):
        base = c * clique_size
        for i in range(clique_size):
            for j in range(i + 1, clique_size):
                edges.append((base + i, base + j))
        nxt = ((c + 1) % n_cliques) * clique_size
        edges.append((base, nxt))
    return from_edges(np.asarray(edges, np.int64), n, **pad)


def grid_graph(rows: int, cols: int, **pad) -> Graph:
    edges = []
    for r in range(rows):
        for c in range(cols):
            v = r * cols + c
            if c + 1 < cols:
                edges.append((v, v + 1))
            if r + 1 < rows:
                edges.append((v, v + cols))
    return from_edges(np.asarray(edges, np.int64), rows * cols, **pad)


def largest_connected_component(edges: np.ndarray, n: int) -> tuple[np.ndarray, int]:
    """Relabel ``edges`` to the largest connected component. Host-side."""
    edges = np.asarray(edges, np.int64).reshape(-1, 2)
    parent = np.arange(n)

    def find(x: int) -> int:
        root = x
        while parent[root] != root:
            root = parent[root]
        while parent[x] != root:
            parent[x], x = root, parent[x]
        return root

    for a, b in edges:
        ra, rb = find(int(a)), find(int(b))
        if ra != rb:
            parent[ra] = rb
    roots = np.array([find(i) for i in range(n)])
    vals, counts = np.unique(roots, return_counts=True)
    big = vals[np.argmax(counts)]
    keep = roots == big
    remap = -np.ones(n, np.int64)
    remap[keep] = np.arange(keep.sum())
    mask = keep[edges[:, 0]] & keep[edges[:, 1]]
    out = remap[edges[mask]]
    return out, int(keep.sum())


def select_landmarks(graph: Graph, n_landmarks: int) -> np.ndarray:
    """Paper's strategy: highest-degree vertices (§6.1 Landmarks)."""
    deg = np.asarray(graph.degrees())
    order = np.argsort(-deg, kind="stable")
    return np.sort(order[:n_landmarks]).astype(np.int32)
