"""The one traffic generator: reads a traffic mix's parameters (a JSON file
under ``perfbench/traffic/``) and makes its queries and edge updates from
the seed.  A new mix is a new data file; a new way of drawing pairs or of
timing arrivals is a new file under ``pairs/`` or ``arrivals/``, found by
the ``kind`` the mix names (``plugins.py``).  This code does not change.

Parameters of a mix:

``arrivals``
    When queries are sent: ``{"kind": K, ...}`` with the parameters of
    ``arrivals/K.py`` (``closed_loop``: ``clients``).
``pairs``
    How a query's two endpoints are drawn: ``{"kind": K, ...}`` with the
    parameters of ``pairs/K.py`` (``uniform``; ``top_degree_anchored``:
    ``k``).
``updates`` (optional)
    ``{"period_s": P, "inserts": I, "deletes": D}``: one writer sends an
    edge-update batch every P seconds of the window, I inserts of absent
    edges and D deletes of present edges, each drawn uniformly.

Queries are one fixed sequence per seed; the loop hands them out in
order, so a faster system answers a longer prefix of the same sequence.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

import plugins

STREAM_GRAPH, STREAM_QUERIES, STREAM_WARMUP, STREAM_UPDATES = 0, 1, 2, 3
STREAM_CHECK, STREAM_ARRIVALS = 4, 5


def load_mix(path: Path) -> dict:
    """The mix's parameters, with its pair and arrival kinds looked up (an
    unknown kind is an error)."""
    mix = json.loads(Path(path).read_text())
    for kind in ("pairs", "arrivals"):
        plugins.load(kind, mix[kind]["kind"])
    return mix


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per use of the seed."""
    return np.random.default_rng([int(seed), stream])


def pair_lanes(spec: dict) -> tuple[str, ...]:
    """The serving lanes the mix's pairs can land in."""
    return tuple(plugins.load("pairs", spec["kind"]).LANES)


def arrivals(spec: dict, rng: np.random.Generator):
    """The mix's arrival process."""
    return plugins.load("arrivals", spec["kind"]).Arrivals(spec, rng)


def draw_pairs(spec: dict, n_vertices: int, top: np.ndarray, n: int,
               rng: np.random.Generator) -> tuple[np.ndarray, np.ndarray]:
    """``n`` query pairs ``(us, vs)`` as the mix's ``pairs`` spec says;
    ``top`` are the highest-degree vertices, ascending."""
    return plugins.load("pairs", spec["kind"]).draw(spec, n_vertices, top, n, rng)


def update_batches(keys: np.ndarray, n_vertices: int, spec: dict,
                   n_batches: int, rng: np.random.Generator):
    """``n_batches`` edge-update batches ``(inserts (I, 2), deletes (D, 2))``
    applied in order to the edge set ``keys`` (canonical ``lo * n + hi``):
    each deletes present edges and inserts absent ones, drawn uniformly
    from the edge set as the earlier batches left it."""
    keys = np.asarray(keys, np.int64)
    out = []
    for _ in range(n_batches):
        dels = keys[rng.choice(keys.size, int(spec["deletes"]), replace=False)]
        ins: list[int] = []
        while len(ins) < int(spec["inserts"]):
            a, b = (int(x) for x in rng.integers(0, n_vertices, 2))
            k = min(a, b) * n_vertices + max(a, b)
            if a != b and k not in ins and \
                    keys[np.searchsorted(keys, k) % keys.size] != k:
                ins.append(k)
        ins_a = np.sort(np.asarray(ins, np.int64))
        keys = np.union1d(keys[~np.isin(keys, dels)], ins_a)
        as_pairs = lambda ks: np.stack([ks // n_vertices,  # noqa: E731
                                        ks % n_vertices], axis=1)
        out.append((as_pairs(ins_a), as_pairs(np.sort(dels))))
    return out
