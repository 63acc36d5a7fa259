"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module must never
touch jax device state (the dry-run sets the forced host device count
before any jax initialization)."""
from __future__ import annotations

import numpy as np


def make_production_mesh(*, multi_pod: bool = False):
    import jax
    from jax.sharding import Mesh

    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devs = jax.devices()
    if len(devs) == n:
        return jax.make_mesh(shape, axes)
    if len(devs) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devs)} — run under "
            "XLA_FLAGS=--xla_force_host_platform_device_count=512 (dryrun.py "
            "sets this automatically)"
        )
    return Mesh(np.array(devs[:n]).reshape(shape), axes)

