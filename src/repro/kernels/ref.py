"""Pure-jnp oracles for every Pallas kernel in this package.

These are the correctness references: small, obviously-correct, dense
implementations. Tests sweep shapes/dtypes and assert the Pallas kernels
(run in ``interpret=True`` on CPU) and the XLA production fallbacks in
``ops.py`` match these to tolerance.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# Knuth multiplicative-hash constant (2^32 / golden ratio, odd).
# numpy scalar (a literal under tracing) so Pallas kernels can close over it.
HASH_MIX = np.uint32(2654435769)


def rb_binning_ref(
    x: jax.Array,          # (N, d) float
    widths: jax.Array,     # (R, d) float  — per-grid per-dim bin widths
    biases: jax.Array,     # (R, d) float  — per-grid per-dim offsets, in [0, width)
    hash_a: jax.Array,     # (R, d) uint32 — per-(grid, dim) odd multipliers
    hash_c: jax.Array,     # (R,)   uint32 — per-grid mixing constants
    d_g: int,              # features per grid (power of two)
) -> jax.Array:
    """Hashed Random Binning: map each point to one feature column per grid.

    Returns idx int32 (N, R) with ``idx[i, g] in [g*d_g, (g+1)*d_g)`` — the ELL
    representation of the sparse RB feature matrix Z (one nonzero per row per
    grid, value 1/sqrt(R) applied by the caller).
    """
    assert d_g & (d_g - 1) == 0, "d_g must be a power of two"
    shift = 32 - int(d_g).bit_length() + 1  # 32 - log2(d_g)
    # bin coordinates: floor((x - u) / w), per grid
    bins = jnp.floor((x[:, None, :] - biases[None, :, :]) / widths[None, :, :])
    bins_u = bins.astype(jnp.int32).astype(jnp.uint32)                 # (N, R, d)
    h = jnp.sum(bins_u * hash_a[None, :, :], axis=-1, dtype=jnp.uint32)  # (N, R)
    h = (h + hash_c[None, :]) * HASH_MIX
    local = (h >> jnp.uint32(shift)).astype(jnp.int32)                 # [0, d_g)
    offsets = (jnp.arange(widths.shape[0], dtype=jnp.int32) * d_g)[None, :]
    return local + offsets


def z_matmul_ref(
    idx: jax.Array,        # (N, R) int32 — ELL column indices
    v: jax.Array,          # (D, K) float — dense right factor
    rowscale: jax.Array,   # (N,) float   — per-row scaling (e.g. deg^-1/2 / sqrt(R))
) -> jax.Array:
    """out = diag(rowscale) · Z_pattern · v where Z_pattern[i, idx[i,g]] = 1.

    Dense oracle: materializes one-hot rows. (N, K).
    """
    d = v.shape[0]
    onehot = jax.nn.one_hot(idx, d, dtype=v.dtype)        # (N, R, D)
    out = jnp.einsum("nrd,dk->nk", onehot, v)
    return out * rowscale[:, None]


def zt_matmul_ref(
    idx: jax.Array,        # (N, R) int32
    u: jax.Array,          # (N, K) float — dense left factor
    rowscale: jax.Array,   # (N,) float
    d: int,                # number of feature columns D
) -> jax.Array:
    """out = Z_patternᵀ · diag(rowscale) · u.   (D, K)."""
    onehot = jax.nn.one_hot(idx, d, dtype=u.dtype)        # (N, R, D)
    return jnp.einsum("nrd,nk->dk", onehot, u * rowscale[:, None])


def kmeans_assign_ref(
    x: jax.Array,          # (N, d)
    centroids: jax.Array,  # (K, d)
) -> tuple[jax.Array, jax.Array]:
    """Nearest-centroid assignment. Returns (labels int32 (N,), sqdist (N,))."""
    x2 = jnp.sum(x * x, axis=-1, keepdims=True)           # (N, 1)
    c2 = jnp.sum(centroids * centroids, axis=-1)          # (K,)
    d2 = x2 - 2.0 * x @ centroids.T + c2[None, :]         # (N, K)
    labels = jnp.argmin(d2, axis=-1).astype(jnp.int32)
    best = jnp.min(d2, axis=-1)
    return labels, jnp.maximum(best, 0.0)
