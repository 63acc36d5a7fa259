"""Pallas TPU kernel: blocked min-plus (tropical) matmul.

Sketching (Eq. 3) is a min-plus contraction (B, R) x (R, R) -> (B, R).  The
MXU multiplies-and-adds and cannot evaluate a (min, +) semiring, so this
kernel targets the **VPU**: 8x128-aligned VMEM tiles, an unrolled loop over
the contraction dim broadcasting one A-column + one B-row per step, and a
running elementwise minimum held in registers/VMEM.  This is the honest TPU
mapping of the paper's nested landmark-pair loop (Algorithm 3, lines 2-5):
arithmetic intensity is O(K) per output element, so for K = |R| = 20..128
the op is compute-bound on the VPU rather than HBM-bound.

Block shapes: A tile (TM, K), B tile (K, TN), C tile (TM, TN); K is kept
whole (R <= 128 after padding) so the grid is (M/TM, N/TN) with no K-grid —
each grid cell touches A and B exactly once: no revisits, no accumulator
spills.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .calling import interpret_mode, match_vma


def _minplus_kernel(a_ref, b_ref, o_ref, *, k_steps: int):
    # K is static, so the contraction unrolls over static ref slices: Mosaic
    # has no lowering for a traced ``dynamic_slice``, and cannot prove a
    # traced lane offset (``pl.ds(k, 1)``) 128-aligned.  Only the k_steps
    # real columns are visited; the INF-safe padding beyond them could
    # never win the minimum.
    acc = a_ref[:, 0:1] + b_ref[0:1, :]        # (TM, 1) + (1, TN)
    for k in range(1, k_steps):
        acc = jnp.minimum(acc, a_ref[:, k:k + 1] + b_ref[k:k + 1, :])
    o_ref[...] = acc


def _pad_to(x: jax.Array, m: int, axis: int, fill) -> jax.Array:
    size = x.shape[axis]
    rem = (-size) % m
    if rem == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, rem)
    return jnp.pad(x, widths, constant_values=fill)


@functools.partial(jax.jit, static_argnames=("tm", "tn", "interpret"))
def minplus(
    a: jax.Array,
    b: jax.Array,
    *,
    tm: int = 128,
    tn: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """C[m, n] = min_k (A[m, k] + B[k, n]) with INF-safe padding.

    ``interpret=None`` decides from the backend the call is traced for:
    compiled on a TPU, the Pallas interpreter everywhere else.
    """
    if a.ndim != 2 or b.ndim != 2 or a.shape[1] != b.shape[0]:
        raise ValueError(f"bad shapes {a.shape} x {b.shape}")
    if jnp.issubdtype(a.dtype, jnp.unsignedinteger) or \
            jnp.issubdtype(b.dtype, jnp.unsignedinteger):
        # packed uint8/uint16 tables must widen first (sentinel + sentinel
        # wraps around in the narrow dtype): core.packing.widen_dist
        raise ValueError(
            f"minplus on unsigned dtypes {a.dtype}/{b.dtype}; widen packed "
            f"tables with core.packing.widen_dist before the contraction")
    m, k = a.shape
    _, n = b.shape
    big = jnp.asarray(1 << 24, a.dtype)  # > INF, still overflow-safe

    ap = _pad_to(_pad_to(a, tm, 0, big), 128, 1, big)
    bp = _pad_to(_pad_to(b, 128, 0, big), tn, 1, big)
    kp = ap.shape[1]
    (ap, bp), vma = match_vma(ap, bp)

    grid = (ap.shape[0] // tm, bp.shape[1] // tn)
    out = pl.pallas_call(
        functools.partial(_minplus_kernel, k_steps=k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tm, kp), lambda i, j: (i, 0)),
            pl.BlockSpec((kp, tn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((tm, tn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((ap.shape[0], bp.shape[1]), a.dtype,
                                       vma=vma),
        interpret=interpret_mode(interpret, vma),
    )(ap, bp)
    return out[:m, :n]
