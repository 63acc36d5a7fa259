"""How a kernel is called: compiled or interpreted, and over which varying
mesh axes.

* ``interpret_mode`` — compiled on a TPU, interpreted on every other
  backend, decided when the call is traced (never at import, so
  importing the package claims no device).
* ``match_vma`` — the batch-sharded general lane calls the sketch kernel
  inside a ``jax.shard_map`` on a query block that varies over the mesh
  and a meta table that is replicated.  The kernel body must see both
  operands varying over the same axes, and its output must declare them
  (``jax.ShapeDtypeStruct(..., vma=...)``).  Outside a shard_map every set
  is empty and nothing changes.
"""
from __future__ import annotations

import jax
from jax.experimental.pallas import tpu as pltpu


def interpret_mode(interpret: bool | None, vma: frozenset = frozenset()):
    """The ``interpret=`` value for ``pallas_call``; ``None`` decides from
    the default backend.  Operands that vary over mesh axes (``vma``) need
    the Pallas TPU interpreter, the only one that types them.  Every other
    call takes the generic HLO interpreter: the TPU interpreter keeps one
    process-wide simulated memory, which kernels running at once on
    several threads (the replicas of a router) overwrite."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if not interpret:
        return False
    return pltpu.InterpretParams() if vma else True


def match_vma(*xs: jax.Array) -> tuple[tuple[jax.Array, ...], frozenset]:
    """Cast each operand to vary over the union of the operands' varying
    axes; returns the cast operands and that union."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in xs))
    cast = tuple(
        jax.lax.pcast(x, tuple(sorted(vma - jax.typeof(x).vma)), to="varying")
        for x in xs)
    return cast, vma
