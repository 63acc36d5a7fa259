"""The deployment's graph: made by the generator its configuration names
(``perfbench/generators/<graph.generator>.py``, whose ``generate(spec,
seed)`` returns the undirected edge list), and the landmark rule the
traffic anchors on."""
from __future__ import annotations

import numpy as np

import plugins


def generate(spec: dict, seed) -> np.ndarray:
    """``(E, 2)`` int64 undirected edges over ``0 .. n_vertices - 1`` from
    the named generator, checked for range and self loops."""
    edges = np.asarray(plugins.load("generators", spec["generator"])
                       .generate(spec, seed), np.int64).reshape(-1, 2)
    n = int(spec["n_vertices"])
    if edges.size and (edges.min() < 0 or edges.max() >= n):
        raise ValueError(f"generator {spec['generator']!r} made a vertex "
                         f"outside 0..{n - 1}")
    if (edges[:, 0] == edges[:, 1]).any():
        raise ValueError(f"generator {spec['generator']!r} made a self loop")
    return edges


def top_degree(edges: np.ndarray, n_vertices: int, k: int) -> np.ndarray:
    """The ``k`` highest-degree vertices, ties broken by the lower vertex
    id, in ascending id order (the paper's landmark rule, section 6.1)."""
    deg = np.bincount(edges.reshape(-1), minlength=n_vertices)
    order = np.lexsort((np.arange(n_vertices), -deg))
    return np.sort(order[:k])


def clear_top(edges: np.ndarray, n_vertices: int, k: int) -> bool:
    """Whether the ``k``-th highest degree is above the next one, so that
    the top-``k`` set does not depend on how vertices are numbered."""
    deg = np.sort(np.bincount(edges.reshape(-1), minlength=n_vertices))[::-1]
    return bool(deg[k - 1] > deg[k])
