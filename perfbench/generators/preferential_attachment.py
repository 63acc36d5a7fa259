"""Generator ``preferential_attachment``: seeded preferential-attachment
graphs with an exact edge count.

Parameters (a configuration's ``graph``): ``n_vertices``, ``n_edges``.

The benchmark's own copy of the repository's preferential-attachment
generator (``barabasi_albert_graph``), rewritten so that the undirected
edge count is exactly the configuration's ``n_edges`` for every seed and
so that a graph of a million vertices is made in about a second.

The process (Batagelj and Brandes, "Efficient generation of large random
networks", Phys. Rev. E 71, 2005): the first ``k0`` vertices form a
clique; every later vertex ``v`` attaches ``m_v`` edges, each to the
endpoint of a slot drawn uniformly from the endpoint list of all earlier
edges, which is a draw proportional to degree.  ``m_v`` is the floor or
the ceiling of the mean ``(n_edges - clique) / (n_vertices - k0)``,
spread evenly so the sum is exact.  A vertex that draws the same target
twice draws again, so the graph has no multi-edge and no self loop, and
every vertex is connected to vertex 0.
"""
from __future__ import annotations

import numpy as np


def attachment_schedule(n_vertices: int, n_edges: int) -> tuple[int, np.ndarray]:
    """``(k0, m)``: the seed-clique size and the edges each later vertex
    attaches (``m[i]`` for vertex ``k0 + i``).  Depends on the sizes only."""
    if n_vertices < 2 or n_edges < n_vertices - 1:
        raise ValueError(f"{n_edges} edges cannot connect {n_vertices} vertices")
    mean = n_edges / n_vertices
    k0 = int(np.ceil(mean)) + 1
    while True:
        rest = n_edges - k0 * (k0 - 1) // 2
        n_new = n_vertices - k0
        if n_new <= 0 or rest < n_new:
            raise ValueError(f"{n_edges} edges do not fit {n_vertices} vertices")
        if rest <= n_new * k0:
            break
        k0 += 1
    cum = (np.arange(n_new + 1, dtype=np.int64) * rest) // n_new
    return k0, np.diff(cum)


def generate(spec: dict, seed) -> np.ndarray:
    """The generator's entry: ``spec`` is the configuration's ``graph``."""
    return preferential_attachment(int(spec["n_vertices"]),
                                   int(spec["n_edges"]), seed)


def preferential_attachment(n_vertices: int, n_edges: int, seed) -> np.ndarray:
    """``(n_edges, 2)`` int64 undirected edges ``(new vertex, target)``,
    all distinct, no self loops; a pure function of the sizes and ``seed``."""
    k0, m = attachment_schedule(n_vertices, n_edges)
    rng = np.random.default_rng(seed)
    clique = np.array([(a, b) for b in range(k0) for a in range(b + 1, k0)],
                      np.int64).reshape(-1, 2)
    n_c = clique.shape[0]
    src = np.concatenate([clique[:, 0],
                          np.repeat(np.arange(k0, n_vertices, dtype=np.int64), m)])
    # endpoint list: slot 2i is edge i's new vertex, slot 2i+1 its target
    first_edge = np.concatenate([[n_c], n_c + np.cumsum(m)[:-1]])
    lim = 2 * np.repeat(first_edge, m)       # slots of strictly earlier edges
    target = np.concatenate([clique[:, 1], np.zeros(lim.size, np.int64)])
    ref = np.full(n_edges, -1, np.int64)
    ref[n_c:] = (rng.random(n_edges - n_c) * lim).astype(np.int64)
    _resolve(src, target, ref, n_c)
    todo = _repeated_targets(src, target, n_c)
    while todo.size:
        # a repeated target draws again, from the endpoints as they stand
        slot = (rng.random(todo.size) * lim[todo - n_c]).astype(np.int64)
        target[todo] = np.where(slot & 1, target[slot >> 1], src[slot >> 1])
        todo = _repeated_targets(src, target, n_c)
    return np.stack([src, target], axis=1)


def _resolve(src, target, ref, n_c) -> None:
    """Follow each drawn slot to a vertex: an even slot names its edge's
    new vertex, an odd one the (earlier) edge's own target, which may
    itself be a draw; the chains are followed until they end."""
    slot = ref[n_c:].copy()
    out = np.empty_like(slot)
    pend = np.arange(slot.size)
    while pend.size:
        s = slot[pend]
        e = s >> 1
        even = (s & 1) == 0
        out[pend[even]] = src[e[even]]
        clique_t = ~even & (e < n_c)
        out[pend[clique_t]] = target[e[clique_t]]
        chain = ~even & (e >= n_c)
        slot[pend[chain]] = ref[e[chain]]
        pend = pend[chain]
    target[n_c:] = out


def _repeated_targets(src, target, n_c) -> np.ndarray:
    """Edge indices (beyond the clique) that repeat an earlier edge of the
    same new vertex."""
    key = src[n_c:] * (int(src.max()) + 1) + target[n_c:]
    order = np.argsort(key, kind="stable")
    dup = np.zeros(key.size, bool)
    dup[order[1:]] = key[order[1:]] == key[order[:-1]]
    return n_c + np.flatnonzero(dup)
