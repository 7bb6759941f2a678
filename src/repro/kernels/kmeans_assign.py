"""Pallas TPU kernel for fused k-means assignment (distance + argmin).

Final stage of Alg. 2: Lloyd iterations over the spectral embedding
(N × K_emb, K_emb small). The fused kernel computes the (block_n, K)
squared-distance tile via one MXU matmul plus rank-1 norms and reduces to
labels/min-distance without materializing the full N×K distance matrix in
HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kmeans_assign_kernel(x_ref, c_ref, lab_ref, dist_ref):
    x = x_ref[...]                                      # (bn, d)
    c = c_ref[...]                                      # (K, d)
    x2 = jnp.sum(x * x, axis=-1, keepdims=True)         # (bn, 1)
    c2 = jnp.sum(c * c, axis=-1)                        # (K,)
    xc = jax.lax.dot(x, c.T, precision=jax.lax.Precision.HIGHEST,
                     preferred_element_type=jnp.float32)
    d2 = x2 - 2.0 * xc + c2[None, :]                    # (bn, K)
    lab_ref[...] = jnp.argmin(d2, axis=-1, keepdims=True).astype(jnp.int32)
    dist_ref[...] = jnp.maximum(jnp.min(d2, axis=-1, keepdims=True), 0.0)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def kmeans_assign_pallas(
    x: jax.Array,          # (N, d) float32
    centroids: jax.Array,  # (K, d) float32
    *,
    block_n: int = 1024,
    interpret: bool = True,
) -> tuple[jax.Array, jax.Array]:
    n, d = x.shape
    k = centroids.shape[0]
    assert n % block_n == 0
    grid = (n // block_n,)
    labels, dists = pl.pallas_call(
        _kmeans_assign_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i: (i, 0)),
            pl.BlockSpec((k, d), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, 1), jnp.int32),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x, centroids)
    return labels[:, 0], dists[:, 0]
