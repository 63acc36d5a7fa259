"""Bit-packed memory layouts (DESIGN.md §10).

QbS's premise is that the precomputed label table is small enough that
online queries are memory-bandwidth-cheap.  This module owns the packed
representations that make that true in HBM, not just on paper:

* **Packed distance tables** (``PackedLabels``): the ``(V, R)`` label
  table plus the meta-graph arrays and the serving-lane ``(R, V)``
  landmark-distance table stored as ``uint8`` (escape hatch to ``uint16``
  chosen at build time from the measured diameter).  ``INF`` is encoded as
  the dtype max — a *sentinel*, because the true ``INF = 1 << 20`` cannot
  fit a narrow lane.  ``widen_dist`` restores exact int32 semantics
  (sentinel -> ``INF``) and is the one sanctioned widening point: it runs
  *inside* jit programs, so the int32 view lives in registers/VMEM of the
  consuming computation and the packed array is what HBM holds (rule
  QBS007 enforces the host-side half of this contract).
* **Bit-packed reachability words** (``pack_bits`` / ``unpack_bits``):
  ``(..., N)`` bool <-> ``(..., ceil(N/32))`` uint32, 32 little-endian
  columns per word — the layout shared by the distributed labelling
  exchange and the hybrid frontier's hub-hub adjacency block
  (``kernels.frontier.bitmap_expand_packed`` unpacks word tiles on the
  fly inside the kernel).

Packing is exact, never lossy: every stored distance is either finite and
below the sentinel (enforced at pack time) or exactly ``INF`` (labelling
clamps there), so ``widen_dist(pack_dist(x)) == x`` bit-for-bit and every
packed pipeline stays bit-identical to the unpacked oracle.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .graph import INF
from ..tracing import scope

# Escape-hatch ladder: narrowest first; the dtype max is the INF sentinel.
_PACK_DTYPES = (np.uint8, np.uint16)


def sentinel_of(dtype) -> int:
    """The INF sentinel of a packed dtype: its maximum value."""
    return int(np.iinfo(np.dtype(dtype)).max)


def choose_pack_dtype(*arrays) -> np.dtype:
    """Pick the narrowest packed dtype for a set of distance arrays.

    The max *finite* (< INF) value across the arrays is the measured
    diameter bound; the escape hatch to uint16 triggers exactly when that
    bound would collide with the uint8 sentinel (255).  ``None`` entries
    are skipped so callers can pass optional tables.
    """
    m = 0
    for a in arrays:
        if a is None:
            continue
        a = np.asarray(a)
        finite = a[a < INF]
        if finite.size:
            m = max(m, int(finite.max()))
    for dtype in _PACK_DTYPES:
        if m < sentinel_of(dtype):
            return np.dtype(dtype)
    raise ValueError(
        f"max finite distance {m} collides with the uint16 sentinel "
        f"{sentinel_of(np.uint16)}; no packed layout fits")


def pack_dist(a, dtype) -> jax.Array:
    """Pack an int32 distance array (INF = no entry) into ``dtype`` with
    the dtype-max sentinel standing in for INF.  Host-side, build-time
    only; raises if any finite value would collide with the sentinel
    (``choose_pack_dtype`` guarantees it doesn't)."""
    a = np.asarray(a)
    sent = sentinel_of(dtype)
    bad = (a >= sent) & (a < INF)
    if bad.any():
        raise ValueError(
            f"finite distance {int(a[bad].max())} >= sentinel {sent}; "
            f"promote the pack dtype")
    return jnp.asarray(np.where(a >= INF, sent, a).astype(dtype))


def widen_dist(a: jax.Array) -> jax.Array:
    """Widen a (possibly packed) distance array to int32 with INF restored.

    Dual-mode: signed inputs pass through as int32 (the unpacked oracle
    path), unsigned inputs are sentinel-decoded.  This is the *only*
    sanctioned widening of packed tables and it belongs inside jit
    programs — the int32 view materializes in the consuming computation,
    never as a persistent HBM array (QBS007 guards host code).
    """
    if not jnp.issubdtype(a.dtype, jnp.unsignedinteger):
        return a.astype(jnp.int32)
    sent = jnp.iinfo(a.dtype).max          # static: derived from the dtype
    a32 = a.astype(jnp.int32)
    return jnp.where(a32 == sent, INF, a32)


class PackedLabels(NamedTuple):
    """The labelling's distance tables in packed HBM layout (all the same
    dtype, chosen once at build by ``choose_pack_dtype``).  A pytree:
    rides into jit programs as-is; consumers gather narrow rows and widen
    with ``widen_dist`` in registers."""

    label_dist: jax.Array        # (V, R) uint8/uint16, sentinel = INF
    meta_w: jax.Array            # (R, R) direct meta edge weights
    meta_dist: jax.Array         # (R, R) meta-graph APSP
    lm_dist: jax.Array | None = None   # (R, V) vertex-to-landmark (serving lanes)

    @property
    def dtype(self) -> np.dtype:
        return np.dtype(self.label_dist.dtype)

    @property
    def sentinel(self) -> int:
        return sentinel_of(self.label_dist.dtype)

    @property
    def nbytes(self) -> int:
        return sum(int(np.prod(a.shape)) * np.dtype(a.dtype).itemsize
                   for a in self if a is not None)


def pack_labelling(scheme, lm_dist=None, *, dtype=None) -> PackedLabels:
    """Pack a ``LabellingScheme`` (and optionally the serving-lane
    ``(R, V)`` landmark-distance table) into one ``PackedLabels``.  The
    dtype is chosen from the measured max finite distance across *all*
    tables so one sentinel convention covers the whole index."""
    if dtype is None:
        dtype = choose_pack_dtype(
            scheme.label_dist, scheme.meta_w, scheme.meta_dist, lm_dist)
    return PackedLabels(
        label_dist=pack_dist(scheme.label_dist, dtype),
        meta_w=pack_dist(scheme.meta_w, dtype),
        meta_dist=pack_dist(scheme.meta_dist, dtype),
        lm_dist=None if lm_dist is None else pack_dist(lm_dist, dtype),
    )


@partial(jax.jit, static_argnames=("dtype",))
def _pack_tables(label_dist, meta_w, meta_dist, lm_dist, dtype):
    with scope("qbs.update"):
        sent = sentinel_of(dtype)
        return PackedLabels(*(jnp.where(a >= INF, sent, a).astype(dtype)
                              for a in (label_dist, meta_w, meta_dist, lm_dist)))


def pack_tables(scheme, lm_dist, dtype) -> PackedLabels:
    """``pack_labelling`` at ``dtype`` as one device program, for the
    update path: no table leaves the device and nothing waits for it.
    Unlike ``pack_dist`` it checks nothing, so ``dtype`` must be one
    ``choose_pack_dtype`` gives for these tables (``QbSIndex.apply_update``
    probes them, or keeps the narrowest dtype where no distance can have
    grown)."""
    return _pack_tables(scheme.label_dist, scheme.meta_w, scheme.meta_dist,
                        lm_dist, dtype=np.dtype(dtype))


def packed_size_bytes(packed: PackedLabels) -> dict:
    """Byte accounting for the compression win: packed vs the int32
    baseline layout of the same tables (``benchmarks/label_size.py``
    commits the ratio to BENCH.json)."""
    n_elems = sum(int(np.prod(a.shape)) for a in packed if a is not None)
    return {
        "packed_bytes": packed.nbytes,
        "int32_bytes": n_elems * 4,
        "dtype": str(packed.dtype),
        "ratio": (n_elems * 4) / max(packed.nbytes, 1),
    }


# ---------------------------------------------------------------------------
# Bit-packed boolean words (shared by distributed exchange + hybrid frontier)
# ---------------------------------------------------------------------------


def pack_bits(x: jax.Array) -> jax.Array:
    """(..., N) bool -> (..., ceil(N/32)) uint32, 32 little-endian columns
    per word (bit ``i`` of word ``w`` is column ``32 * w + i``)."""
    n = x.shape[-1]
    pad = (-n) % 32
    if pad:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    x = x.reshape(*x.shape[:-1], -1, 32).astype(jnp.uint32)
    weights = (jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32))
    return (x * weights).sum(axis=-1, dtype=jnp.uint32)


def unpack_bits(x: jax.Array, n: int) -> jax.Array:
    """(..., W) uint32 -> (..., n) bool (inverse of ``pack_bits``)."""
    bits = (x[..., :, None] >> jnp.arange(32, dtype=jnp.uint32)) & jnp.uint32(1)
    out = bits.reshape(*x.shape[:-1], -1)
    return out[..., :n].astype(bool)
