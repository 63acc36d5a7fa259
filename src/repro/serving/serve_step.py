"""Serving steps: the QbS shortest-path-graph query pipeline.

``make_spg_serve_step`` / ``serve_spg_batch``: the persistent,
fully-jitted batched pipeline over a built ``QbSIndex`` — label gather
-> sketch (min-plus on the Pallas kernel when the index was built with
``use_pallas=True``, the default) -> vmapped guided search ->
device-side edge-mask symmetrization.  The step is fixed-shape (``B =
index.chunk`` lanes), returns device arrays with no host sync, and serves
the general (non-landmark-endpoint) lane; ``serve_spg_batch`` answers
arbitrary batches through the planner/service stack (``serving.planner``
routes lanes, ``serving.service`` executes them with double-buffered
async dispatch — same as ``QbSIndex.query_batch``).
"""
from __future__ import annotations

from typing import Callable

import numpy as np


def make_spg_serve_step(index) -> Callable:
    """Return the persistent jitted SPG serving step of a ``QbSIndex``.

    The step maps int32 query arrays ``(us, vs)`` of shape ``(B,)`` (any
    fixed B; reuse one B for one compile cache entry — ``index.chunk`` is
    the canonical choice) to device arrays ``(dist (B,), edge_mask (B, E)
    bool)``.  The edge mask is already symmetrized through the reverse-edge
    map, so callers never touch the host ``(B, E)`` gather the legacy path
    paid per chunk.  No host sync happens inside the step (two chained jit
    dispatches: search program + symmetrization program; see
    ``QbSIndex.__init__`` for why they are separate).

    The label tables the step reads are the index's *packed* uint8/uint16
    arrays (``QbSIndex.packed``, DESIGN.md §10): gathered rows widen to
    int32 in registers inside the jit program, so HBM label traffic is
    ~4x cheaper than the int32 layout while results stay bit-identical.

    Landmark-endpoint queries are *not* handled here (they have no label
    entries; the pipeline returns garbage lanes for them) — route them to
    the vectorized landmark lane steps (``QbSIndex.landmark_pair_step`` /
    ``landmark_onesided_step``) as the planner does.

    A vertex-sharded index (``core.sharded.ShardedIndex``,
    ``QbSIndex.build(..., sharded=...)``) satisfies the same contract:
    its step runs the general lane over the mesh-resident label/CSR
    blocks and returns the same replicated, already-symmetrized arrays —
    callers cannot tell the layouts apart (DESIGN.md §11).
    """
    return index.serve_step


def serve_spg_batch(index, us, vs) -> tuple[np.ndarray, np.ndarray]:
    """Answer an arbitrary-size query batch through the planner/service
    stack (lane routing, dedup, double-buffered chunk dispatch).  Returns
    ``(dist (N,) int32, edge_mask (N, E) bool)``.
    """
    return index.query_batch_arrays(us, vs)
