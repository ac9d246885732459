"""Production mesh construction.

Single pod: 16×16 = 256 chips, axes ("data", "model").
Multi-pod:  2×16×16 = 512 chips, axes ("pod", "data", "model") — the "pod"
axis carries pure data parallelism across pods (DCN-ish boundary).

Defined as functions (never module-level constants) so importing this module
never touches jax device state.
"""
from __future__ import annotations

import numpy as np


def make_production_mesh(*, multi_pod: bool = False):
    import jax
    from jax.sharding import Mesh

    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devices)} — the "
            "dry-run entrypoint must set XLA_FLAGS=--xla_force_host_platform_"
            "device_count=512 before any jax import"
        )
    return Mesh(np.asarray(devices[:n]).reshape(shape), axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """(data, model) mesh over the first data·model devices: the chips of a
    TPU host, or fake CPU host devices in the sharding unit tests."""
    import jax
    from jax.sharding import Mesh

    n = data * model
    devices = jax.devices()
    assert len(devices) >= n, (len(devices), n)
    return Mesh(np.asarray(devices[:n]).reshape(data, model), ("data", "model"))
