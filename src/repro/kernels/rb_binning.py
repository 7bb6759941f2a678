"""Pallas TPU kernel for hashed Random Binning feature generation (Alg. 1).

This is the paper's graph-construction hot spot: O(N·R·d) work to map every
point into one bin per random grid. The TPU adaptation (DESIGN.md §3.1) makes
the feature space static via multiply-shift hashing, so the kernel is pure
VPU element-wise math over VMEM tiles — no hash-map, no dynamic shapes.

Tiling: grid (N/block_n, d_pad/block_d). Each program loads an x tile
(block_n, block_d), the (block_d, R) slices of the transposed grid
parameters, and accumulates the (block_n, R) int32 hash of all R grids in
the output block, which stays resident across the dimension axis. A
``lax.fori_loop`` walks the block's dimensions one at a time, so the live
intermediate is one (block_n, R) tile at any d (mnist's d = 780 included).

The hash runs in int32 with wrapping multiply/add and a logical right
shift: mod 2³² these are the same bits as the uint32 form of
``kernels/ref.py``, which the TPU compiler cannot reduce.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ref import HASH_MIX

# HASH_MIX reinterpreted as int32 (same bits mod 2³²)
_MIX_I32 = int(HASH_MIX) - (1 << 32)
# Input dimensions per program: the x tile's lane width once d > 128
BLOCK_D = 128


def _rb_binning_kernel(
    x_ref,        # (block_n, block_d) float32
    w_ref,        # (block_d, R) float32
    b_ref,        # (block_d, R) float32
    a_ref,        # (block_d, R) int32 (uint32 bits)
    c_ref,        # (1, R) int32 (uint32 bits)
    out_ref,      # (block_n, R) int32
    *,
    d_g: int,
):
    kd = pl.program_id(1)
    shift = 32 - int(d_g).bit_length() + 1
    x = x_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)

    def body(j, h):
        # column j of the tile as (block_n, 1): a masked lane sum adds only
        # zeros to x[:, j], so it is exact
        xj = jnp.sum(jnp.where(lane == j, x, 0.0), axis=1, keepdims=True)
        w = w_ref[pl.ds(j, 1), :]                      # (1, R)
        b = b_ref[pl.ds(j, 1), :]
        a = a_ref[pl.ds(j, 1), :]
        bins = jnp.floor((xj - b) / w).astype(jnp.int32)
        return h + bins * a

    h = jax.lax.fori_loop(0, x.shape[1], body,
                          jnp.zeros(out_ref.shape, jnp.int32))

    @pl.when(kd == 0)
    def _init():
        out_ref[...] = h

    @pl.when(kd != 0)
    def _acc():
        out_ref[...] += h

    @pl.when(kd == pl.num_programs(1) - 1)
    def _finish():
        hh = (out_ref[...] + c_ref[...]) * jnp.int32(_MIX_I32)
        local = jax.lax.shift_right_logical(hh, jnp.int32(shift))
        offs = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 1) * d_g
        out_ref[...] = local + offs


@functools.partial(
    jax.jit, static_argnames=("d_g", "block_n", "block_d", "interpret")
)
def rb_binning_pallas(
    x: jax.Array,         # (N, d_pad) float32
    widths_t: jax.Array,  # (d_pad, R) float32
    biases_t: jax.Array,  # (d_pad, R) float32
    hash_a_t: jax.Array,  # (d_pad, R) int32
    hash_c: jax.Array,    # (1, R) int32
    *,
    d_g: int,
    block_n: int = 256,
    block_d: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Pallas entry point; the caller (ops.py) pads N to ``block_n`` and d
    to ``block_d``, transposes the grid parameters and bit-casts the hash
    constants to int32."""
    n, d = x.shape
    r = widths_t.shape[1]
    assert n % block_n == 0 and d % block_d == 0, (n, d, block_n, block_d)
    kern = functools.partial(_rb_binning_kernel, d_g=d_g)
    par = pl.BlockSpec((block_d, r), lambda i, kd: (kd, 0))
    return pl.pallas_call(
        kern,
        grid=(n // block_n, d // block_d),   # out accumulates over axis 1
        in_specs=[
            pl.BlockSpec((block_n, block_d), lambda i, kd: (i, kd)),
            par, par, par,
            pl.BlockSpec((1, r), lambda i, kd: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, r), lambda i, kd: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, r), jnp.int32),
        interpret=interpret,
    )(x, widths_t, biases_t, hash_a_t, hash_c)
