"""Pallas TPU kernels for the ELL sparse products in the SC_RB eigensolver.

The eigensolver inner loop (DESIGN.md §3.2/§3.3) is dominated by
``q = Ẑᵀ·u`` (scatter-add) and ``y = Ẑ·q`` (gather) over the RB feature
matrix Z stored in ELL form: ``idx int32 (N, R)``, one nonzero per (row,
grid), structural value 1 (the 1/√R·deg^{-1/2} weights are folded into a
per-row scale).

TPU has no efficient scatter, so both kernels use the MoE-dispatch trick:
grid ``g`` owns the column strip ``[g·d_g, (g+1)·d_g)``, and inside a block we
contract a one-hot matrix against the dense factor on the **MXU** —
scatter/gather become dense matmuls with block-diagonal structure.

Layout. The kernels work on the transposed problem, with rows of the data
on the 128-wide lane axis:

  idx_t  (R, N)              int32  — ELL indices, one grid per sublane row
  u_t    (K, N) / y_t (K, N) float  — tall factors, transposed
  s      (1, N)              float  — per-row scale
  v4     (R, nc, K, dc)      float  — the (D, K) factor split per grid into
                                      ``nc`` column chunks of ``dc`` bins

so an index row ``idx_t[r]`` is a (1, block_n) lane vector and its one-hot
tile ``(dc, block_n)`` is an iota compare against that row broadcast over
sublanes. Every block's last two dimensions are either full or aligned to
the (8, 128) tiling, and the loop over the grids of a block is a
``lax.fori_loop`` with dynamic sublane / leading-dimension indexing, so the
kernel body does not grow with R or d_g.

The gather ``Ẑ·V`` is one bf16 MXU pass per one-hot tile: the one-hot is
exact in bf16, and an f32 factor chunk enters as its three exact bf16 terms
(``bf16_terms``) stacked on the row axis, so the three row blocks of the
product sum back to the f32 gather bit for bit. The scatter ``Ẑᵀ·U`` and
the fused Gram kernel run at ``Precision.HIGHEST``: their sums of f32
products would not stay exact under a split. Both match the
gather/segment-sum XLA route to f32 rounding.

``ops.py`` builds these layouts from the natural (N, R) / (N, K) / (D, K)
arrays and slices the padding back off.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HIGHEST = jax.lax.Precision.HIGHEST

# Bins per one-hot tile: the (dc, block_n) one-hot and the (K, dc) factor
# chunk stay small at any d_g (d_g ≤ 65536 ⇒ up to 128 chunks per grid).
DG_CHUNK = 512
# Grids per block: the idx_t block is (block_r, block_n), so block_r is a
# multiple of 8 (or all of R); capped so the (block_r, K, dc) factor block
# stays a few hundred KiB.
BLOCK_R_CAP = 32


def dg_chunk(d_g: int) -> int:
    """Column-chunk width ``dc`` for a per-grid hash width ``d_g``."""
    return min(d_g, DG_CHUNK)


def pick_block_r(r: int) -> int:
    """Grids per block: the largest multiple of 8 dividing R (≤ the cap),
    or all of R when R has none (a full dimension is always a legal block)."""
    for c in range(min(BLOCK_R_CAP, r) // 8 * 8, 0, -8):
        if r % c == 0:
            return c
    return r


def _onehot_t(row, base, dc, dtype):
    """(dc, bn) one-hot: ``[c, i] = (row[0, i] − base == c)``."""
    iota = jax.lax.broadcasted_iota(jnp.int32, (dc, row.shape[1]), 0)
    return (iota == row - base).astype(dtype)


def bf16_terms(v):
    """f32 ``v`` as three bf16 terms, each held in f32: hi = bf16(v),
    mid = bf16(v − hi), lo = bf16(v − hi − mid). Each remainder is exact in
    f32 and three 8-bit significands cover f32's 24 bits, so
    ``(hi + mid) + lo == v + 0`` bit for bit: only a zero's sign is lost,
    as it is in any one-hot product, whose other terms are +0."""
    def bf16(x):
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    hi = bf16(v)
    mid = bf16(v - hi)
    return hi, mid, bf16(v - hi - mid)


def _gather_chunk(v, onehot):
    """(K, bn) f32 = v · onehot, exactly, in one bf16 MXU pass. An f32
    ``v`` enters as its terms [hi; mid; lo] stacked on the row axis (padded
    to bf16's 16-row sublane tile); each product column picks one value of
    each term, so the three row blocks sum back to ``v``'s gathered values.
    The pass is pinned to DEFAULT precision: under a caller's
    ``default_matmul_precision("highest")`` (the fit's) it would otherwise
    run as several."""
    dot = functools.partial(jax.lax.dot, precision=jax.lax.Precision.DEFAULT,
                            preferred_element_type=jnp.float32)
    if v.dtype == jnp.bfloat16:
        return dot(v, onehot)
    k = v.shape[0]
    terms = [*bf16_terms(v.astype(jnp.float32))]
    if 3 * k % 16:
        terms.append(jnp.zeros((-3 * k % 16, v.shape[1]), jnp.float32))
    y = dot(jnp.concatenate(terms).astype(jnp.bfloat16), onehot)
    return (y[:k] + y[k:2 * k]) + y[2 * k:3 * k]


def _z_matmul_kernel(idx_ref, v_ref, s_ref, y_ref, *, d_g, dc, block_r):
    """y_t[:, tile] = s ∘ Σ_r V[idx[tile, r], :]ᵀ, accumulated over the
    (grid block, bin chunk) axes of the launch grid."""
    g, c = pl.program_id(1), pl.program_id(2)
    last = (g == pl.num_programs(1) - 1) & (c == pl.num_programs(2) - 1)

    @pl.when((g == 0) & (c == 0))
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    def body(r, acc):
        base = (g * block_r + r) * d_g + c * dc
        onehot = _onehot_t(idx_ref[pl.ds(r, 1), :], base, dc, jnp.bfloat16)
        return acc + _gather_chunk(v_ref[r, 0], onehot)

    y_ref[...] += jax.lax.fori_loop(0, block_r, body,
                                    jnp.zeros(y_ref.shape, jnp.float32))

    @pl.when(last)
    def _scale():
        y_ref[...] *= s_ref[...]


def _zt_matmul_kernel(idx_ref, u_ref, s_ref, q_ref, *, d_g, dc, block_r):
    """q[strip] += (s∘u)ᵀ · onehot over the row tiles of the launch grid."""
    g, c, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    us = u_ref[...].astype(jnp.float32) * s_ref[...]               # (K, bn)

    @pl.when(j == 0)
    def _init():
        q_ref[...] = jnp.zeros_like(q_ref)

    def body(r, carry):
        base = (g * block_r + r) * d_g + c * dc
        onehot = _onehot_t(idx_ref[pl.ds(r, 1), :], base, dc, jnp.float32)
        q_ref[r, 0] += jax.lax.dot_general(
            us, onehot, (((1,), (1,)), ((), ())), precision=_HIGHEST,
            preferred_element_type=jnp.float32)                     # (K, dc)
        return carry

    jax.lax.fori_loop(0, block_r, body, 0)


def _specs_check(idx_t, block_n, block_r):
    r, n = idx_t.shape
    assert n % block_n == 0 and r % block_r == 0, (r, n, block_r, block_n)


@functools.partial(
    jax.jit, static_argnames=("d_g", "block_n", "block_r", "interpret"))
def z_matmul_pallas(
    idx_t: jax.Array,     # (R, N) int32
    v4: jax.Array,        # (R, nc, K, dc) float
    s: jax.Array,         # (1, N) float32
    *,
    d_g: int,
    block_n: int = 128,
    block_r: int = 8,
    interpret: bool = False,
) -> jax.Array:
    """y_t = (diag(s) · Z · V)ᵀ : (K, N) float32."""
    r, n = idx_t.shape
    _, nc, k, dc = v4.shape
    _specs_check(idx_t, block_n, block_r)
    assert nc * dc == d_g
    kern = functools.partial(_z_matmul_kernel, d_g=d_g, dc=dc,
                             block_r=block_r)
    return pl.pallas_call(
        kern,
        grid=(n // block_n, r // block_r, nc),   # y accumulates over axes 1, 2
        in_specs=[
            pl.BlockSpec((block_r, block_n), lambda i, g, c: (g, i)),
            pl.BlockSpec((block_r, 1, k, dc), lambda i, g, c: (g, c, 0, 0)),
            pl.BlockSpec((1, block_n), lambda i, g, c: (0, i)),
        ],
        out_specs=pl.BlockSpec((k, block_n), lambda i, g, c: (0, i)),
        out_shape=jax.ShapeDtypeStruct((k, n), jnp.float32),
        interpret=interpret,
    )(idx_t, v4, s)


@functools.partial(
    jax.jit, static_argnames=("d_g", "block_n", "block_r", "interpret"))
def zt_matmul_pallas(
    idx_t: jax.Array,     # (R, N) int32
    u_t: jax.Array,       # (K, N) float
    s: jax.Array,         # (1, N) float32
    *,
    d_g: int,
    block_n: int = 128,
    block_r: int = 8,
    interpret: bool = False,
) -> jax.Array:
    """q = Zᵀ · diag(s) · u in the (R, nc, K, dc) layout, float32."""
    r, n = idx_t.shape
    k = u_t.shape[0]
    dc = dg_chunk(d_g)
    nc = d_g // dc
    _specs_check(idx_t, block_n, block_r)
    kern = functools.partial(_zt_matmul_kernel, d_g=d_g, dc=dc,
                             block_r=block_r)
    return pl.pallas_call(
        kern,
        grid=(r // block_r, nc, n // block_n),   # q accumulates over axis 2
        in_specs=[
            pl.BlockSpec((block_r, block_n), lambda g, c, j: (g, j)),
            pl.BlockSpec((k, block_n), lambda g, c, j: (0, j)),
            pl.BlockSpec((1, block_n), lambda g, c, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_r, 1, k, dc),
                               lambda g, c, j: (g, c, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((r, nc, k, dc), jnp.float32),
        interpret=interpret,
    )(idx_t, u_t, s)


def _gram_matmul_kernel(idx_ref, u_ref, s_ref, y_ref, q_ref, *, d_g, dc, nc,
                        block_r):
    """Fused Gram mat-vec y = Ẑ·(Ẑᵀu): the ELL index strip streams through
    VMEM once per phase instead of once per kernel per product.

    Grid is (2, N tiles, R blocks), phase slowest / block fastest. The
    (R, nc, K, dc) intermediate q is a VMEM scratch buffer that lives for
    the whole launch and never touches HBM. Phase 0 accumulates
    q[strip] += (s∘u)ᵀ·onehot over all row tiles (the scatter of
    ``_zt_matmul_kernel``); phase 1 gathers y[tile] = s∘Σ q[strip]·onehot
    (the gather of ``_z_matmul_kernel``). The y output's index map parks on
    block 0 during phase 0, so nothing is written back before the gather
    phase initializes it.
    """
    # program_id must be read at the top level of the kernel body: in
    # interpret mode the evaluator only substitutes it outside cond branches.
    ph, i, g = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    last_g = pl.num_programs(2) - 1

    def rows(fn, carry):
        """fn(grid row r of the block, bin chunk c, carry) over the block."""
        def per_r(r, carry):
            return jax.lax.fori_loop(
                0, nc, lambda c, cc: fn(r, c, cc), carry)
        return jax.lax.fori_loop(0, block_r, per_r, carry)

    def onehot(r, c):
        base = (g * block_r + r) * d_g + c * dc
        return _onehot_t(idx_ref[pl.ds(r, 1), :], base, dc, jnp.float32)

    @pl.when(ph == 0)
    def _scatter():
        us = u_ref[...].astype(jnp.float32) * s_ref[...]            # (K, bn)

        def fn(r, c, carry):
            contrib = jax.lax.dot_general(
                us, onehot(r, c), (((1,), (1,)), ((), ())),
                precision=_HIGHEST, preferred_element_type=jnp.float32)
            q_ref[g * block_r + r, c] = jnp.where(
                i == 0, contrib, q_ref[g * block_r + r, c] + contrib)
            return carry

        rows(fn, 0)

    @pl.when(ph == 1)
    def _gather():
        def fn(r, c, acc):
            return acc + jax.lax.dot(
                q_ref[g * block_r + r, c], onehot(r, c), precision=_HIGHEST,
                preferred_element_type=jnp.float32)

        acc = rows(fn, jnp.zeros(y_ref.shape, jnp.float32))

        @pl.when(g == 0)
        def _init():
            y_ref[...] = jnp.zeros_like(y_ref)

        y_ref[...] += acc

        @pl.when(g == last_g)
        def _scale():
            y_ref[...] *= s_ref[...]


def gram_vmem_bytes(r: int, k: int, d_g: int) -> int:
    """VMEM the fused kernel's resident (R, nc, K, dc) intermediate claims:
    K is padded to the 8-row sublane tile."""
    return r * (-(-k // 8) * 8) * d_g * 4


@functools.partial(
    jax.jit, static_argnames=("d_g", "block_n", "block_r", "interpret"))
def gram_matmul_pallas(
    idx_t: jax.Array,     # (R, N) int32
    u_t: jax.Array,       # (K, N) float
    s: jax.Array,         # (1, N) float32
    *,
    d_g: int,
    block_n: int = 128,
    block_r: int = 8,
    interpret: bool = False,
) -> jax.Array:
    """y_t = (Ẑ Ẑᵀ u)ᵀ : (K, N) float32 in one kernel launch; the (D, K)
    intermediate q = Ẑᵀu lives in VMEM scratch. The caller
    (``ops.gram_matmul``) guards that it fits the VMEM budget and composes
    the two single-product kernels otherwise."""
    r, n = idx_t.shape
    k = u_t.shape[0]
    dc = dg_chunk(d_g)
    nc = d_g // dc
    _specs_check(idx_t, block_n, block_r)
    kern = functools.partial(_gram_matmul_kernel, d_g=d_g, dc=dc, nc=nc,
                             block_r=block_r)
    return pl.pallas_call(
        kern,
        grid=(2, n // block_n, r // block_r),    # phase slowest, block fastest
        in_specs=[
            pl.BlockSpec((block_r, block_n), lambda p, i, g: (g, i)),
            pl.BlockSpec((k, block_n), lambda p, i, g: (0, i)),
            pl.BlockSpec((1, block_n), lambda p, i, g: (0, i)),
        ],
        # parked on block 0 through phase 0, per-tile during phase 1
        out_specs=pl.BlockSpec((k, block_n), lambda p, i, g: (0, p * i)),
        out_shape=jax.ShapeDtypeStruct((k, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((r, nc, k, dc), jnp.float32)],
        interpret=interpret,
    )(idx_t, u_t, s)
