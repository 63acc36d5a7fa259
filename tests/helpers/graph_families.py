"""The graph families ``tests/test_core_qbs.py`` holds the replicated index
to, plus the two small graphs the vertex-sharded search was first checked
on, as ``name -> (graph builder, n_landmarks)``; and query pairs that
land in one planner lane each.  The sharded and batch-sharded serving
paths are held to the replicated index over these."""
from __future__ import annotations

import numpy as np

from repro.core import (
    barabasi_albert_graph,
    from_edges,
    gnp_random_graph,
    grid_graph,
    ring_of_cliques,
)


def _figure3():
    edges = np.array([(1, 2), (1, 3), (2, 4), (2, 5), (2, 6), (3, 4),
                      (5, 6), (5, 7)]) - 1
    return from_edges(edges, 7)


def _disconnected():
    edges = np.array([(0, 1), (1, 2), (2, 0), (3, 4), (4, 5), (5, 3)])
    return from_edges(edges, 7)   # vertex 6 isolated


def _gnp(seed: int, nl: int):
    # the (seed, R) cases of test_core_qbs.test_random_graphs_match_oracle
    n = int(np.random.default_rng(seed).integers(20, 50))
    return (lambda: gnp_random_graph(n, 3.5, seed=seed + 50)), min(nl, n // 3)


# name -> (graph builder, landmark count)
FAMILIES = {
    "figure3": (_figure3, 2),
    "gnp-s0-r1": _gnp(0, 1),
    "gnp-s1-r3": _gnp(1, 3),
    "gnp-s2-r5": _gnp(2, 5),
    "gnp-s3-r8": _gnp(3, 8),
    "hub-heavy": (lambda: barabasi_albert_graph(80, 2, seed=3), 6),
    "tied-grid": (lambda: grid_graph(6, 6), 4),
    "flat-degree": (lambda: ring_of_cliques(6, 5), 5),
    "disconnected": (_disconnected, 2),
    "gnp50-r4": (lambda: gnp_random_graph(50, 3.5, seed=11), 4),
    "grid6-r3": (lambda: grid_graph(6, 6), 3),
}

LANES = ("general", "landmark-pair", "one-sided")
# every (family, lane) case; one landmark makes no landmark pair
CASES = [(name, lane) for name, (_, r) in FAMILIES.items() for lane in LANES
         if r > 1 or lane != "landmark-pair"]


def lane_pairs(graph, landmarks, lane: str, *, n: int = 12, seed: int = 0):
    """``(us, vs)`` int32 pairs of one lane.  ``general``: no landmark
    endpoint, with a ``u == v`` pair and an adjacent pair among them;
    ``landmark-pair``: both endpoints landmarks; ``one-sided``: one of
    each, in both orders."""
    rng = np.random.default_rng(seed)
    lms = np.asarray(landmarks)
    is_lm = np.zeros(graph.n_vertices, bool)
    is_lm[lms] = True
    plain = np.flatnonzero(~is_lm)
    if lane == "general":
        us, vs = rng.choice(plain, n), rng.choice(plain, n)
        us[0] = vs[0]
        src, dst = np.asarray(graph.src), np.asarray(graph.dst)
        adj = np.flatnonzero(~is_lm[src] & ~is_lm[dst] & (src != dst))
        us[1], vs[1] = src[adj[0]], dst[adj[0]]
    elif lane == "landmark-pair":
        us, vs = rng.choice(lms, n), rng.choice(lms, n)
    elif lane == "one-sided":
        us, vs = rng.choice(lms, n), rng.choice(plain, n)
        us[::2], vs[::2] = vs[::2].copy(), us[::2].copy()
    else:
        raise ValueError(lane)
    return us.astype(np.int32), vs.astype(np.int32)
