"""Jit'd public wrappers for the Pallas kernels, with XLA production fallbacks.

Dispatch policy (``impl``):
  - ``"pallas"``  — the Pallas kernel. On TPU this compiles to Mosaic; on CPU
    it runs in ``interpret=True`` (used by the correctness tests).
  - ``"xla"``     — pure-XLA implementation with bounded memory (chunked
    scans / segment_sum). This is the production path on CPU/GPU and the
    baseline the Pallas path is validated against.
  - ``"auto"``    — ``"pallas"`` on TPU backends, ``"xla"`` elsewhere.

All wrappers handle ragged shapes by padding to the kernel tiling and
slicing back, so callers never need to know block sizes.
"""
from __future__ import annotations

import contextlib
import contextvars
import functools
from typing import Mapping, Optional

import jax
import jax.numpy as jnp

from repro.kernels import ell_spmm, kmeans_assign as _kmeans_kernel, rb_binning as _rb_kernel
from repro.kernels.ref import HASH_MIX


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _resolve(impl: str) -> str:
    if impl == "auto":
        return "pallas" if _on_tpu() else "xla"
    return impl


def _pad_rows(a: jax.Array, mult: int, fill=0):
    n = a.shape[0]
    pad = (-n) % mult
    if pad == 0:
        return a, n
    widths = [(0, pad)] + [(0, 0)] * (a.ndim - 1)
    return jnp.pad(a, widths, constant_values=fill), n


def _pad_cols(a: jax.Array, mult: int):
    pad = (-a.shape[1]) % mult
    return jnp.pad(a, ((0, 0), (0, pad))) if pad else a


def _largest_divisor(n: int, cap: int) -> int:
    for c in range(min(cap, n), 0, -1):
        if n % c == 0:
            return c
    return 1


# --------------------------------------------------------------------------
# Row-tile sizing — one shared picker for every Pallas wrapper.
#
# All wrappers pad the row dimension to the chosen block and slice back, so
# any block size is *valid*; the picker's job is to not tile past the data
# (a 3-row input should not pad to 1024) while keeping the TPU-friendly
# power-of-two, ≥ sublane-multiple shape. Per-op caps live in
# ``DEFAULT_BLOCK_ROWS`` and are overridable either per call (``block_rows=``)
# or for a whole pipeline run via ``block_rows_overrides`` (which is how
# ``ExecutionPlan.block_rows`` reaches the kernels without threading an
# argument through every stage).
# --------------------------------------------------------------------------

DEFAULT_BLOCK_ROWS: dict[str, int] = {
    "rb_binning": 256,
    "ell_spmm": 128,
    "kmeans_assign": 1024,
}

_BLOCK_ROWS_OVERRIDES: contextvars.ContextVar[Mapping[str, int]] = (
    contextvars.ContextVar("block_rows_overrides", default={}))


@contextlib.contextmanager
def block_rows_overrides(overrides: Optional[Mapping[str, int]]):
    """Scoped per-op row-block caps, keyed by ``DEFAULT_BLOCK_ROWS`` names.

    The executor wraps each pipeline run in this context so a plan's
    ``block_rows`` mapping applies to every kernel dispatch of that run and
    nothing else (contextvar ⇒ safe under concurrent runs)."""
    merged = dict(_BLOCK_ROWS_OVERRIDES.get())
    merged.update(overrides or {})
    token = _BLOCK_ROWS_OVERRIDES.set(merged)
    try:
        yield
    finally:
        _BLOCK_ROWS_OVERRIDES.reset(token)


def _next_pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


def pick_block_rows(op: str, n: int, override: Optional[int] = None) -> int:
    """Row-tile size for a Pallas wrapper: the largest power of two that is
    ≤ the op's cap and no larger than the padded row count needs.

    ``override`` (a per-call ``block_rows=`` argument) wins over the
    run-scoped ``block_rows_overrides`` mapping, which wins over
    ``DEFAULT_BLOCK_ROWS[op]``. Caps must be powers of two — the kernels pad
    rows to the block, and 8 is the fp32 sublane minimum on TPU.
    """
    cap = override or _BLOCK_ROWS_OVERRIDES.get().get(op) \
        or DEFAULT_BLOCK_ROWS[op]
    cap = int(cap)
    if cap < 8 or cap & (cap - 1):
        raise ValueError(
            f"block_rows cap for {op!r} must be a power of two ≥ 8, got {cap}")
    return max(8, min(cap, _next_pow2(n)))


# --------------------------------------------------------------------------
# RB binning
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("d_g", "r_chunk"))
def _rb_binning_xla(x, widths, biases, hash_a, hash_c, *, d_g, r_chunk=32):
    """Chunked-over-grids XLA path: O(N·r_chunk·d) peak temp memory."""
    shift = 32 - int(d_g).bit_length() + 1
    r = widths.shape[0]
    assert r % r_chunk == 0
    nchunk = r // r_chunk

    def body(_, args):
        w, b, a, c, offs = args           # (rc, d), ..., (rc,)
        bins = jnp.floor((x[:, None, :] - b[None, :, :]) / w[None, :, :])
        bins_u = bins.astype(jnp.int32).astype(jnp.uint32)
        h = jnp.sum(bins_u * a[None, :, :], axis=-1, dtype=jnp.uint32)
        h = (h + c[None, :]) * HASH_MIX
        local = (h >> jnp.uint32(shift)).astype(jnp.int32)
        return None, local + offs[None, :] * d_g

    resh = lambda t: t.reshape((nchunk, r_chunk) + t.shape[1:])
    offs = jnp.arange(r, dtype=jnp.int32)
    _, cols = jax.lax.scan(
        body, None,
        (resh(widths), resh(biases), resh(hash_a), resh(hash_c), resh(offs)),
    )
    # (nchunk, N, r_chunk) -> (N, R)
    return jnp.transpose(cols, (1, 0, 2)).reshape(x.shape[0], r)


def rb_binning(
    x: jax.Array,
    widths: jax.Array,
    biases: jax.Array,
    hash_a: jax.Array,
    hash_c: jax.Array,
    *,
    d_g: int,
    impl: str = "auto",
    block_rows: Optional[int] = None,
) -> jax.Array:
    """ELL column indices of the hashed RB feature matrix: int32 (N, R)."""
    impl = _resolve(impl)
    r, d = widths.shape
    if impl == "xla":
        return _rb_binning_xla(
            x, widths, biases, hash_a, hash_c,
            d_g=d_g, r_chunk=_largest_divisor(r, 32),
        )
    block_n = pick_block_rows("rb_binning", x.shape[0], block_rows)
    xp, n = _pad_rows(x, block_n)
    # walk d in lane-aligned blocks; padded dimensions hash to zero
    # (x = 0, width 1, bias 0, multiplier 0)
    block_d = min(d, _rb_kernel.BLOCK_D)
    pad_d = (-d) % block_d
    as_i32 = lambda a: jax.lax.bitcast_convert_type(a, jnp.int32)
    cols_t = lambda a, fill: jnp.pad(a.T, ((0, pad_d), (0, 0)),
                                     constant_values=fill)
    out = _rb_kernel.rb_binning_pallas(
        jnp.pad(xp, ((0, 0), (0, pad_d))),
        cols_t(widths, 1.0), cols_t(biases, 0.0), cols_t(as_i32(hash_a), 0),
        as_i32(hash_c)[None, :],
        d_g=d_g,
        block_n=block_n,
        block_d=block_d,
        interpret=not _on_tpu(),
    )
    return out[:n]


# --------------------------------------------------------------------------
# ELL bin counts: m = Zᵀ·1 as exact int32 occupancies
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("d",))
def _bin_counts_xla(idx, *, d):
    return jnp.zeros((d,), jnp.int32).at[idx.reshape(-1)].add(1)


def bin_counts(idx: jax.Array, *, d: int, d_g: int, impl: str = "auto") -> jax.Array:
    """Per-column occupancy of the ELL pattern: int32 (D,).

    Integer accumulation is order-invariant, so summing per-chunk counts in
    the streaming degree pass is bit-identical to the single-shot result —
    the property tests/test_streaming.py pins down.

    The ``impl="pallas"`` route is **eager-only**: it slices rows with a
    host-side Python ``for`` loop (each slice would unroll into the trace,
    one kernel launch per 2²² rows, silently bloating the program). Calling
    it under ``jax.jit`` raises; inside jit use ``impl="xla"`` — the
    streaming degree pass calls this eagerly once per host chunk.
    """
    impl = _resolve(impl)
    if impl == "xla":
        return _bin_counts_xla(idx, d=d)
    # direct jax.core.Tracer reference on purpose: if a future jax removes
    # it, this fails loudly (as does the guard's test) instead of silently
    # dropping the eager-only protection
    if isinstance(idx, jax.core.Tracer):
        raise TypeError(
            "bin_counts(impl='pallas') is eager-only: its row slicing is a "
            "host-side Python loop that would unroll under tracing. Call it "
            "outside jax.jit, or use impl='xla' (traceable scatter-add).")
    # Pallas route: reuse the zt kernel with unit weights. float32 holds the
    # counts exactly below 2^24, so accumulate in row slices of < 2^22 rows
    # (per-bin occupancy within a slice is bounded by the slice height) and
    # sum the slices in exact int32.
    n = idx.shape[0]
    slice_rows = 1 << 22
    total = jnp.zeros((d,), jnp.int32)
    for start in range(0, n, slice_rows):
        part = idx[start:start + slice_rows]
        m = part.shape[0]
        ones = jnp.ones((m, 1), jnp.float32)
        unit = jnp.ones((m,), jnp.float32)
        counts = zt_matmul(part, ones, unit, d, d_g=d_g, impl="pallas")
        total = total + jnp.round(counts[:, 0]).astype(jnp.int32)
    return total


# --------------------------------------------------------------------------
# ELL spmm: y = diag(s)·Z·v   and   q = Zᵀ·diag(s)·u
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("r_chunk",))
def _z_matmul_xla(idx, v, rowscale, *, r_chunk):
    n, r = idx.shape
    k = v.shape[1]
    nchunk = r // r_chunk

    def body(acc, cols):                  # cols: (N, r_chunk)
        gathered = jnp.take(v, cols, axis=0)          # (N, r_chunk, K)
        return acc + jnp.sum(gathered, axis=1), None

    idx_c = jnp.transpose(idx.reshape(n, nchunk, r_chunk), (1, 0, 2))
    acc, _ = jax.lax.scan(body, jnp.zeros((n, k), v.dtype), idx_c)
    return acc * rowscale[:, None].astype(v.dtype)


@functools.partial(jax.jit, static_argnames=("d", "r_chunk"))
def _zt_matmul_xla(idx, u, rowscale, *, d, r_chunk):
    n, r = idx.shape
    k = u.shape[1]
    nchunk = r // r_chunk
    us = u * rowscale[:, None].astype(u.dtype)

    def body(acc, cols):                  # cols: (N, r_chunk)
        flat = cols.reshape(-1)                                  # (N·rc,)
        data = jnp.broadcast_to(us[:, None, :], (n, r_chunk, k)).reshape(-1, k)
        return acc + jax.ops.segment_sum(data, flat, num_segments=d), None

    idx_c = jnp.transpose(idx.reshape(n, nchunk, r_chunk), (1, 0, 2))
    acc, _ = jax.lax.scan(body, jnp.zeros((d, k), u.dtype), idx_c)
    return acc


def _ell_block_n(n: int, block_rows: Optional[int]) -> int:
    """Row tile of the ELL kernels. Rows sit on the 128-wide lane axis
    there, so a tile that does not cover every row is a multiple of 128."""
    block_n = pick_block_rows("ell_spmm", n, block_rows)
    return block_n if block_n >= n else max(block_n, 128)


def _ell_operands(idx, rowscale, block_n):
    """(idx_t (R, N_pad), s (1, N_pad), n): the kernels' transposed row
    layout; padded rows get scale 0, so they contribute nothing."""
    idx_p, n = _pad_rows(idx, block_n)
    s_p, _ = _pad_rows(rowscale.astype(jnp.float32), block_n)
    return idx_p.T, s_p[None, :], n


def _tall_t(u, block_n):
    """(N, K) → (K_pad, N_pad): rows padded to the tile, K to the 8-row
    sublane tile."""
    return _pad_cols(_pad_rows(u, block_n)[0], 8).T


def _factor_chunks(v, r, d_g):
    """(D, K) → (R, nc, K_pad, dc), the z kernel's per-grid bin chunks."""
    dc = ell_spmm.dg_chunk(d_g)
    vp = _pad_cols(v, 8)
    return vp.reshape(r, d_g // dc, dc, vp.shape[1]).transpose(0, 1, 3, 2)


def _factor_unchunk(q4, k):
    """(R, nc, K_pad, dc) → (D, K), inverse of ``_factor_chunks``."""
    r, nc, kp, dc = q4.shape
    return q4.transpose(0, 1, 3, 2).reshape(r * nc * dc, kp)[:, :k]


def z_matmul(
    idx: jax.Array,
    v: jax.Array,
    rowscale: jax.Array,
    *,
    d_g: int,
    impl: str = "auto",
    block_rows: Optional[int] = None,
) -> jax.Array:
    """y = diag(rowscale) · Z_pattern · v.  (N, K)."""
    impl = _resolve(impl)
    r = idx.shape[1]
    if impl == "xla":
        return _z_matmul_xla(idx, v, rowscale, r_chunk=_largest_divisor(r, 8))
    block_n = _ell_block_n(idx.shape[0], block_rows)
    idx_t, s, n = _ell_operands(idx, rowscale, block_n)
    y_t = ell_spmm.z_matmul_pallas(
        idx_t, _factor_chunks(v, r, d_g), s, d_g=d_g,
        block_n=block_n, block_r=ell_spmm.pick_block_r(r),
        interpret=not _on_tpu(),
    )
    return y_t[:v.shape[1], :n].T.astype(v.dtype)


def zt_matmul(
    idx: jax.Array,
    u: jax.Array,
    rowscale: jax.Array,
    d: int,
    *,
    d_g: int,
    impl: str = "auto",
    block_rows: Optional[int] = None,
) -> jax.Array:
    """q = Z_patternᵀ · diag(rowscale) · u.  (D, K)."""
    impl = _resolve(impl)
    r = idx.shape[1]
    if impl == "xla":
        return _zt_matmul_xla(idx, u, rowscale, d=d, r_chunk=_largest_divisor(r, 8))
    assert d == r * d_g, (d, r, d_g)
    block_n = _ell_block_n(idx.shape[0], block_rows)
    idx_t, s, _ = _ell_operands(idx, rowscale, block_n)
    q4 = ell_spmm.zt_matmul_pallas(
        idx_t, _tall_t(u, block_n), s, d_g=d_g,
        block_n=block_n, block_r=ell_spmm.pick_block_r(r),
        interpret=not _on_tpu(),
    )
    return _factor_unchunk(q4, u.shape[1]).astype(u.dtype)


# Upper bound on the VMEM the fused Gram kernel's resident (D, K)
# intermediate may claim (``ell_spmm.gram_vmem_bytes``); above it the
# dispatch composes the two single-product kernels (the intermediate then
# lives in HBM). The v5e compiler's default scoped-VMEM limit is 16 MiB and
# the launch's streamed blocks and one-hot tiles need ~0.1 MiB beside the
# intermediate: at R = 256, K ≤ 16 the 8 MiB of d_g = 512 fuses, the
# 16 MiB of d_g = 1024 does not.
GRAM_FUSE_VMEM_BYTES = 12 * 2 ** 20


def gram_matmul(
    idx: jax.Array,
    u: jax.Array,
    rowscale: jax.Array,
    d: int,
    *,
    d_g: int,
    impl: str = "auto",
    block_rows: Optional[int] = None,
) -> jax.Array:
    """y = Ẑ Ẑᵀ u — the eigensolver's Gram mat-vec, fused when it fits.

    On the Pallas route the ``Ẑᵀu`` / ``Ẑq`` pair runs as ONE kernel
    (``ell_spmm.gram_matmul_pallas``): the ELL index strip is streamed
    through VMEM once per phase and the (D, K) intermediate stays
    VMEM-resident between the scatter and gather phases instead of
    round-tripping through HBM. When that intermediate exceeds
    ``GRAM_FUSE_VMEM_BYTES`` the dispatch composes the two single-product
    kernels — identical math, same tiling. The XLA route is the reference
    composition of the two XLA paths.
    """
    impl = _resolve(impl)
    r, k = idx.shape[1], u.shape[1]
    if impl == "xla":
        rc = _largest_divisor(r, 8)
        q = _zt_matmul_xla(idx, u, rowscale, d=d, r_chunk=rc)
        return _z_matmul_xla(idx, q, rowscale, r_chunk=rc)
    if ell_spmm.gram_vmem_bytes(r, k, d_g) > GRAM_FUSE_VMEM_BYTES:
        q = zt_matmul(idx, u, rowscale, d, d_g=d_g, impl="pallas",
                      block_rows=block_rows)
        return z_matmul(idx, q, rowscale, d_g=d_g, impl="pallas",
                        block_rows=block_rows)
    block_n = _ell_block_n(idx.shape[0], block_rows)
    idx_t, s, n = _ell_operands(idx, rowscale, block_n)
    y_t = ell_spmm.gram_matmul_pallas(
        idx_t, _tall_t(u, block_n), s, d_g=d_g,
        block_n=block_n, block_r=ell_spmm.pick_block_r(r),
        interpret=not _on_tpu(),
    )
    return y_t[:k, :n].T.astype(u.dtype)


# --------------------------------------------------------------------------
# k-means assignment
# --------------------------------------------------------------------------

@jax.jit
def _kmeans_assign_xla(x, centroids):
    x2 = jnp.sum(x * x, axis=-1, keepdims=True)
    c2 = jnp.sum(centroids * centroids, axis=-1)
    xc = jnp.matmul(x, centroids.T, precision=jax.lax.Precision.HIGHEST)
    d2 = x2 - 2.0 * xc + c2[None, :]
    return (
        jnp.argmin(d2, axis=-1).astype(jnp.int32),
        jnp.maximum(jnp.min(d2, axis=-1), 0.0),
    )


def kmeans_assign(
    x: jax.Array, centroids: jax.Array, *, impl: str = "auto",
    block_rows: Optional[int] = None,
) -> tuple[jax.Array, jax.Array]:
    """(labels int32 (N,), squared distance to nearest centroid (N,))."""
    impl = _resolve(impl)
    if impl == "xla":
        return _kmeans_assign_xla(x, centroids)
    block_n = pick_block_rows("kmeans_assign", x.shape[0], block_rows)
    xp, n = _pad_rows(x, block_n)
    labels, dists = _kmeans_kernel.kmeans_assign_pallas(
        xp, centroids, block_n=block_n, interpret=not _on_tpu()
    )
    return labels[:n], dists[:n]


def kmeans_assign_stats(
    x: jax.Array, centroids: jax.Array, *, impl: str = "auto"
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Fused per-batch k-means statistics for streaming/mini-batch updates.

    One assignment pass (routed through the Pallas/XLA kernel) plus the
    segment reductions every Sculley-style update needs:
    ``(labels (N,), counts (k,), sums (k, d), inertia scalar)``. Keeping the
    reduction fused with the assignment means a streamed chunk is uploaded
    once and only O(k·d) statistics leave the device.
    """
    labels, dists = kmeans_assign(x, centroids, impl=impl)
    k = centroids.shape[0]
    counts = jax.ops.segment_sum(
        jnp.ones(x.shape[:1], jnp.float32), labels, num_segments=k)
    sums = jax.ops.segment_sum(x.astype(jnp.float32), labels, num_segments=k)
    return labels, counts, sums, jnp.sum(dists)
