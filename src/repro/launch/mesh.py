"""Device meshes for the SC_RB plans.

Functions (never module-level constants), so importing this module never
touches jax device state: callers control when devices materialize.

Axes:
  - host:       (data=n)                     — every local device
  - single-pod: (data=16, model=16)          — 256 chips (one v5e pod)
  - multi-pod:  (pod=2, data=16, model=16)   — 512 chips (2 pods)

Rows (N) are sharded over ``data_axes(mesh)``: ``pod`` composed with
``data``, in that nesting order, so scaling to N pods is a mesh-shape change
only. The mesh plans (``repro.core.distributed``, ``MeshRows``) shard rows
over those axes and psum the (D, K) Gram products across them; ``model``
carries no rows, and the partitioned fit takes one device per row shard
(``partition_devices``).
"""
from __future__ import annotations

import jax
import numpy as np

from repro.utils import make_auto_mesh


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_auto_mesh(shape, axes)


def make_host_mesh() -> jax.sharding.Mesh:
    """Whatever devices exist locally, as a 1-D data mesh (tests/examples)."""
    n = len(jax.devices())
    return make_auto_mesh((n,), ("data",))


def partition_devices(mesh: jax.sharding.Mesh) -> tuple:
    """One device per data-axis shard (model-axis index 0) — the devices the
    partitioned fit (``placement="partitioned"``) pins one partition's
    single-device sub-fit to, so partitions spread over the same axes that
    carry N in the SPMD plans."""
    axes = data_axes(mesh)
    arr = np.asarray(mesh.devices)
    idx = tuple(slice(None) if name in axes else 0
                for name in mesh.axis_names)
    return tuple(arr[idx].flat)


def data_axes(mesh: jax.sharding.Mesh) -> tuple:
    """The mesh axes rows are sharded over, in nesting order.

    Every row PartitionSpec in the SPMD pipeline composes ``pod`` with
    ``data`` (see module docstring), so this is the single source of truth
    for "which axes carry N" — shared by the shard_map collectives in
    ``repro.core.distributed`` and the ``MeshRows`` representation.
    """
    return tuple(a for a in ("pod", "data") if a in mesh.shape)
