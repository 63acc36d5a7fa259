"""Jit'd public wrappers for the Pallas kernels.

Each kernel compiles for a TPU and runs under the Pallas interpreter on
any other backend (same dataflow, same BlockSpec tiling), decided when the
call is traced.  ``use_pallas=False`` falls back to the pure-jnp reference
(what XLA:TPU would fuse anyway); the flag exists so the serving path can be
profiled both ways.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import ref
from .frontier import bitmap_expand as _bitmap_expand_pallas
from .minplus import minplus as _minplus_pallas


def minplus(a: jax.Array, b: jax.Array, *, use_pallas: bool = True) -> jax.Array:
    """Tropical matmul  C = A (minplus) B.  Shapes (M,K) x (K,N) -> (M,N)."""
    if not use_pallas:
        return ref.minplus_ref(a, b)
    return _minplus_pallas(a, b)


def bitmap_expand(frontier: jax.Array, adjacency: jax.Array, *, use_pallas: bool = True) -> jax.Array:
    """One BFS expansion level over a dense adjacency block (OR-AND matmul)."""
    if not use_pallas:
        return ref.bitmap_expand_ref(frontier, adjacency)
    return _bitmap_expand_pallas(frontier, adjacency)


def sketch_d_top(lu: jax.Array, lv: jax.Array, meta_dist: jax.Array, *, use_pallas: bool = True) -> jax.Array:
    """d_top for a query batch via two chained min-plus contractions
    (the Pallas-accelerated sketching fast path)."""
    t = minplus(lu, meta_dist, use_pallas=use_pallas)           # (B, R)
    return jnp.min(t + lv, axis=1)
