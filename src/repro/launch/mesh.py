"""Production mesh definitions.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state — the dry-run driver
sets ``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any
jax import; everything else sees the real single CPU device.

Mesh axes:
  single-pod: (data=16, model=16)           — 256 chips (one v5e pod)
  multi-pod:  (pod=2, data=16, model=16)    — 512 chips (2 pods)

Axis roles: ``data`` shards the global batch (and FSDP weight rows),
``model`` shards heads / FFN columns / experts / long KV sequences,
``pod`` is pure data parallelism across pods (weights replicated across
pods; gradient all-reduce crosses the inter-pod links once per step).
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_host_mesh():
    """1-device mesh with the production axis names (tests / examples)."""
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def data_axes(mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def batch_shards(mesh) -> int:
    import numpy as np
    return int(np.prod([mesh.shape[a] for a in data_axes(mesh)]))
