"""Pair kind ``top_degree_anchored``: one endpoint uniformly among the
``k`` highest-degree vertices (ties to the lower id), the other uniformly
among the rest, in random order.  Parameter: ``k``.  With ``k`` the
landmark count every pair takes the one-sided landmark lane."""
import numpy as np

LANES = ("one_sided",)


def draw(spec: dict, n_vertices: int, top: np.ndarray, n: int,
         rng: np.random.Generator) -> tuple[np.ndarray, np.ndarray]:
    k = int(spec["k"])
    top = np.asarray(top[:k], np.int64)
    hub = top[rng.integers(0, k, n)]
    rest = _skip_sorted(rng.integers(0, n_vertices - k, n), top)
    flip = rng.random(n) < 0.5
    us = np.where(flip, rest, hub)
    vs = np.where(flip, hub, rest)
    return us.astype(np.int32), vs.astype(np.int32)


def _skip_sorted(rank: np.ndarray, excluded: np.ndarray) -> np.ndarray:
    """Map ranks 0..n-k-1 onto the vertex ids that are not in the sorted
    ``excluded`` set."""
    out = rank.copy()
    for x in excluded:                     # ascending: each shifts the rest
        out = out + (out >= x)
    return out
