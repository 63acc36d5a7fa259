"""Generator ``ldbc_knows``: a seeded stand-in for the Person-knows-Person
graph of LDBC SNB Datagen, with exact person and edge counts.

Parameters (a configuration's ``graph``): ``n_vertices`` (persons),
``n_edges`` (undirected knows edges), and ``knows``, the stand-in's own
settings:

``degree_sigma``, ``degree_cap``
    each person's target degree is drawn from a log-normal of shape
    ``degree_sigma``, redrawn above ``degree_cap`` times the mean, then
    scaled so the targets' mean is ``2 * n_edges / n_vertices``;
``shares``
    the share of each target that each correlation pass makes (Datagen's
    three knows dimensions: university, interest, random);
``window``
    the scale, in persons, of a pass's locality (below);
``overshoot``
    how many more link ends than needed each pass deals, so that the
    links lost to self loops and duplicates are made up.

A pass sorts the persons by a seeded key of its own and gives every
person ``share * target`` link ends.  Each end sits at its person's rank
in the order, moved by a two-sided exponential offset of mean
``window * max(1, target / mean)`` persons (a hub's ends range further,
so it can find that many distinct friends); the ends are then paired in
the order of their moved positions.  So two persons link with a chance
that falls off exponentially with their distance in the pass's order,
and each person ends with about its target degree.  Self loops and
duplicates are dropped, the surplus is dropped uniformly at random, and
any shortfall is made up by further rounds of the last (random) pass, so
the edge count is exactly ``n_edges``.  Vectorised: a few sorts of the
link ends per pass.
"""
from __future__ import annotations

import numpy as np


def generate(spec: dict, seed) -> np.ndarray:
    """The generator's entry: ``spec`` is the configuration's ``graph``."""
    return ldbc_knows(int(spec["n_vertices"]), int(spec["n_edges"]),
                      spec["knows"], seed)


def target_degrees(n_vertices: int, n_edges: int, knows: dict,
                   rng: np.random.Generator) -> np.ndarray:
    """``(V,)`` float target degrees with mean ``2 E / V``."""
    sigma, cap = float(knows["degree_sigma"]), float(knows["degree_cap"])
    mean = 2.0 * n_edges / n_vertices
    # log-normal of median 1: its mean is exp(sigma^2 / 2); the cap is
    # taken relative to that mean, and values above it are drawn again
    lim = cap * np.exp(sigma * sigma / 2)
    deg = rng.lognormal(0.0, sigma, n_vertices)
    while (over := deg > lim).any():
        deg[over] = rng.lognormal(0.0, sigma, int(over.sum()))
    return deg * (mean / deg.mean())


def pass_edges(target: np.ndarray, share: float, window: float,
               rng: np.random.Generator) -> np.ndarray:
    """``(M, 2)`` links of one correlation pass (self loops included; the
    caller drops them)."""
    n = target.size
    rank = np.empty(n, np.int64)
    rank[rng.permutation(n)] = np.arange(n)       # the pass's seeded order
    want = share * target
    ends = np.floor(want).astype(np.int64)
    ends += rng.random(n) < want - ends           # rounded at random
    who = np.repeat(np.arange(n, dtype=np.int64), ends)
    if who.size % 2:
        who = who[:-1]
    reach = window * np.maximum(1.0, target / target.mean())
    pos = rank[who] + rng.laplace(0.0, 1.0, who.size) * reach[who]
    paired = who[np.argsort(pos, kind="stable")]
    return paired.reshape(-1, 2)


def ldbc_knows(n_vertices: int, n_edges: int, knows: dict, seed) -> np.ndarray:
    """``(n_edges, 2)`` int64 undirected edges ``(lo, hi)``, all distinct,
    no self loops; a pure function of the parameters and ``seed``."""
    rng = np.random.default_rng(seed)
    shares = [float(s) for s in knows["shares"]]
    window, over = float(knows["window"]), float(knows["overshoot"])
    target = target_degrees(n_vertices, n_edges, knows, rng)
    keys = np.zeros(0, np.int64)
    for share in shares:
        keys = np.union1d(keys, _keys(pass_edges(target, share * over, window,
                                                 rng), n_vertices))
    while keys.size < n_edges:                    # top up with the random pass
        more = pass_edges(target, shares[-1], window, rng)
        keys = np.union1d(keys, _keys(more, n_vertices))
    keys = np.sort(rng.choice(keys, n_edges, replace=False))
    return np.stack([keys // n_vertices, keys % n_vertices], axis=1)


def _keys(pairs: np.ndarray, n: int) -> np.ndarray:
    """Sorted distinct ``lo * n + hi`` keys of the pairs that are no self
    loop."""
    a, b = pairs[:, 0], pairs[:, 1]
    keep = a != b
    return np.unique(np.minimum(a, b)[keep] * n + np.maximum(a, b)[keep])
