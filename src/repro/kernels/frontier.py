"""Pallas TPU kernel: dense-block BFS frontier expansion on the MXU.

The labelling phase is |R| simultaneous BFSs (Algorithm 2).  On hub-dense
graph blocks the level-synchronous expansion

    next[r, w] = OR_{v} frontier[r, v] AND adjacency[v, w]

is an OR-AND semiring matmul.  Unlike min-plus, this semiring *does* map
onto the MXU: cast to f32, matmul, threshold (>0).  The kernel is a blocked
matmul with a K-grid accumulator; the final grid step applies the
threshold so the boolean never round-trips through HBM as f32.

This is the TPU-native replacement for the paper's per-thread adjacency
walks: a (R, V) x (V, V) block product with 128-aligned VMEM tiles keeps
the MXU busy instead of chasing pointers.  The edge-list ``segment_max``
path in ``repro.core`` remains the scalable route for sparse graphs; this
kernel serves the dense blocks (hub-hub subgraphs) where tens of percent
of all traversal work concentrates (§6.5 of the paper: high-centrality
regions dominate query work).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .calling import interpret_mode, match_vma


def _expand_kernel(f_ref, a_ref, o_ref, acc_ref, *, k_grid: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        f_ref[...], a_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(pl.program_id(2) == k_grid - 1)
    def _finish():
        o_ref[...] = (acc_ref[...] > 0.5).astype(jnp.bool_)


def _pad_to(x: jax.Array, m: int, axis: int) -> jax.Array:
    rem = (-x.shape[axis]) % m
    if rem == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, rem)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("tm", "tn", "tk", "interpret"))
def bitmap_expand(
    frontier: jax.Array,
    adjacency: jax.Array,
    *,
    tm: int = 8,
    tn: int = 128,
    tk: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """next[r, w] = any_v frontier[r, v] & adjacency[v, w].

    frontier (R, V) bool; adjacency (V, W) bool -> (R, W) bool.
    ``interpret=None`` compiles the kernel on a TPU and interprets it on
    every other backend.
    """
    if frontier.ndim != 2 or adjacency.ndim != 2:
        raise ValueError("rank-2 inputs required")
    if frontier.shape[1] != adjacency.shape[0]:
        raise ValueError(f"bad shapes {frontier.shape} x {adjacency.shape}")
    r = frontier.shape[0]
    v = adjacency.shape[1]
    f = _pad_to(_pad_to(frontier.astype(jnp.float32), tm, 0), tk, 1)
    a = _pad_to(_pad_to(adjacency.astype(jnp.float32), tk, 0), tn, 1)
    k_grid = f.shape[1] // tk
    grid = (f.shape[0] // tm, a.shape[1] // tn, k_grid)
    (f, a), vma = match_vma(f, a)

    out = pl.pallas_call(
        functools.partial(_expand_kernel, k_grid=k_grid),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tm, tk), lambda i, j, k: (i, k)),
            pl.BlockSpec((tk, tn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((tm, tn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((f.shape[0], a.shape[1]), jnp.bool_,
                                       vma=vma),
        scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        interpret=interpret_mode(interpret, vma),
    )(f, a)
    return out[:r, :v]


def _expand_packed_kernel(f_ref, w_ref, o_ref, acc_ref, *, k_grid: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Unpack the (tk, tn/32) uint32 word tile into the (tk, tn) f32 operand
    # in VMEM: bit i of word w is column 32*w + i (core.packing order).  The
    # dense mask exists only here, per tile — HBM holds the words.
    words = w_ref[...]
    bits = (words[:, :, None] >> jnp.arange(32, dtype=jnp.uint32)) & jnp.uint32(1)
    # via int32: Mosaic has no uint32 -> float32 cast (bits are 0/1)
    a = bits.reshape(words.shape[0], -1).astype(jnp.int32).astype(jnp.float32)
    acc_ref[...] += jnp.dot(
        f_ref[...], a, preferred_element_type=jnp.float32
    )

    @pl.when(pl.program_id(2) == k_grid - 1)
    def _finish():
        o_ref[...] = (acc_ref[...] > 0.5).astype(jnp.bool_)


@functools.partial(jax.jit,
                   static_argnames=("n_cols", "tm", "tk", "interpret"))
def bitmap_expand_packed(
    frontier: jax.Array,
    adj_words: jax.Array,
    *,
    n_cols: int | None = None,
    tm: int = 8,
    tk: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """``bitmap_expand`` over a *bit-packed* adjacency: frontier (R, V)
    bool x adj_words (V, W) uint32 (32 little-endian columns per word,
    ``core.packing.pack_bits`` layout) -> (R, n_cols) bool.

    The adjacency never materializes densely in HBM: each grid step loads a
    uint32 word tile and unpacks it in VMEM right before the OR-AND matmul,
    so the hub-hub reachability rows stay 32x smaller end-to-end.

    The word tile spans all words of a row up to 128 of them (4,096
    columns), and 128-word blocks beyond: a TPU block's last dimension must
    be the whole array's or a multiple of 128.  ``interpret=None`` compiles
    on a TPU and interprets elsewhere.
    """
    if frontier.ndim != 2 or adj_words.ndim != 2:
        raise ValueError("rank-2 inputs required")
    if frontier.shape[1] != adj_words.shape[0]:
        raise ValueError(f"bad shapes {frontier.shape} x {adj_words.shape}")
    r = frontier.shape[0]
    n = adj_words.shape[1] * 32 if n_cols is None else n_cols
    tw = min(adj_words.shape[1], 128)
    tn = tw * 32
    f = _pad_to(_pad_to(frontier.astype(jnp.float32), tm, 0), tk, 1)
    w = _pad_to(_pad_to(adj_words, tk, 0), tw, 1)
    k_grid = f.shape[1] // tk
    grid = (f.shape[0] // tm, w.shape[1] // tw, k_grid)
    (f, w), vma = match_vma(f, w)

    out = pl.pallas_call(
        functools.partial(_expand_packed_kernel, k_grid=k_grid),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tm, tk), lambda i, j, k: (i, k)),
            pl.BlockSpec((tk, tw), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((tm, tn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((f.shape[0], w.shape[1] * 32),
                                       jnp.bool_, vma=vma),
        scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        interpret=interpret_mode(interpret, vma),
    )(f, w)
    return out[:r, :n]
