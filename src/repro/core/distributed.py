"""Distributed SC_RB: mesh-placement collectives + the thin SPMD entry point.

This module is the *placement layer* under the plan-based executor
(``repro.core.executor``): the shard_map factories here are the only place
collectives appear, so the communication schedule stays explicit and
auditable (DESIGN.md §3.4) — per eigensolver iteration exactly one
all-reduce of the (D, K) projected block:

  rows of X / Z.idx / U       → sharded over the data axes (pod, data)
  q = Ẑᵀ·u                    → local ELL product + psum over data axes
  y = Ẑ·q                     → purely local (q replicated after psum)
  k-means statistics          → within-shard chunk scan + (K,)/(K, dim) psum

``chunk_size`` composes streaming with sharding everywhere: the local ELL
products and the k-means assignment/stats sweeps run as ``lax.scan`` over
row chunks, so per-device temporary memory stays O(chunk) regardless of the
shard size. ``distributed_kmeans`` consumes the embedding shard-chunk-wise —
no O(N) gather and no O(N/shards) distance temporary. RB grid parameters are
derived from the seed, so every host materializes identical grids with zero
communication.

``sc_rb_distributed`` is a wrapper over ``executor.execute`` with a
``placement="mesh"`` plan; the per-stage logic lives in the executor and
``repro.core.rowmatrix.MeshRows``.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import streaming
from repro.core.kmeans import KMeansResult, _plusplus_init
from repro.kernels import ops
from repro.launch.mesh import data_axes
from repro.utils import StageTimer

_data_axes = data_axes   # back-compat alias (moved to repro.launch.mesh)


def make_gram_matvec(mesh: Mesh, idx: jax.Array, rowscale: jax.Array,
                     d: int, d_g: int, impl: str = "auto",
                     compress: bool = False,
                     chunk_size: Optional[int] = None):
    """Row-sharded Â·u mat-vec with an explicit psum over the data axes.

    ``compress=True`` runs the (D, K) all-reduce payload in bf16 (halving THE
    collective of this workload); the local partial sums and the subsequent
    gather stay fp32, so only the single reduction is rounded — measured
    harmless for clustering quality (tests/test_distributed.py) and the Ritz
    values converge identically at tol 1e-4 (§Perf).

    ``chunk_size`` chunks *within* each row shard: the local ELL products run
    as a ``lax.scan`` over row chunks with a single (D, K) accumulator, so
    per-device temporary memory for the gather/scatter stays
    O(chunk_size · R) regardless of the shard size. Composes with
    ``compress`` — the collective is unchanged.
    """
    axes = data_axes(mesh)
    row_spec = P(axes if len(axes) > 1 else axes[0])

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(row_spec[0], None), P(row_spec[0], None), row_spec),
        check_vma=False,   # kernels allocate unvarying scan carries internally
        out_specs=P(row_spec[0], None))
    def gram(u_local, idx_local, scale_local):
        if chunk_size is None:
            q = ops.zt_matmul(idx_local, u_local, scale_local, d,
                              d_g=d_g, impl=impl)      # local partial (D, K)
        else:
            q = streaming.chunked_zt_matmul(
                idx_local, u_local, scale_local, d=d, d_g=d_g,
                chunk_size=chunk_size, impl=impl)
        if compress:
            q = jax.lax.psum(q.astype(jnp.bfloat16), axes).astype(jnp.float32)
        else:
            q = jax.lax.psum(q, axes)                  # THE collective
        if chunk_size is None:
            return ops.z_matmul(idx_local, q, scale_local, d_g=d_g, impl=impl)
        return streaming.chunked_z_matmul(
            idx_local, q, scale_local, d_g=d_g, chunk_size=chunk_size,
            impl=impl)

    return lambda u: gram(u, idx, rowscale)


def make_degree_pass(mesh: Mesh, d: int, d_g: int, impl: str = "auto",
                     compress: bool = False,
                     chunk_size: Optional[int] = None):
    """The Eq. 6 degree pass deg = Z(Zᵀ1) as a function of the row-sharded
    (N, R) ELL, also emitting the replicated (D,) bin occupancies Zᵀ1 that
    the first product computes anyway — the fitted model's degree dual,
    captured at no extra collective sweep. Same blocking/collective
    structure as ``make_gram_matvec``. The ELL is an argument, so a
    ``jax.jit`` of the pass never embeds it as a constant.
    """
    axes = data_axes(mesh)
    row_spec = P(axes if len(axes) > 1 else axes[0])

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(row_spec[0], None),),
        check_vma=False,
        out_specs=(row_spec, P(None)))
    def degpass(idx_local):
        n_local, r = idx_local.shape
        inv_sqrt_r = jnp.float32(1.0 / np.sqrt(r))
        ones = jnp.ones((n_local, 1), jnp.float32)
        scale_local = jnp.full((n_local,), inv_sqrt_r, jnp.float32)
        if chunk_size is None:
            q = ops.zt_matmul(idx_local, ones, scale_local, d,
                              d_g=d_g, impl=impl)
        else:
            q = streaming.chunked_zt_matmul(
                idx_local, ones, scale_local, d=d, d_g=d_g,
                chunk_size=chunk_size, impl=impl)
        if compress:
            q = jax.lax.psum(q.astype(jnp.bfloat16), axes).astype(jnp.float32)
        else:
            q = jax.lax.psum(q, axes)
        if chunk_size is None:
            y = ops.z_matmul(idx_local, q, scale_local, d_g=d_g, impl=impl)
        else:
            y = streaming.chunked_z_matmul(
                idx_local, q, scale_local, d_g=d_g, chunk_size=chunk_size,
                impl=impl)
        # undo the 1/√R value folding: raw occupancies (exact up to ~2 ulp)
        return y[:, 0], q[:, 0] * jnp.sqrt(jnp.float32(r))

    return degpass


def make_zt_matvec(mesh: Mesh, idx: jax.Array, rowscale: jax.Array,
                   d: int, d_g: int, impl: str = "auto",
                   chunk_size: Optional[int] = None):
    """Row-sharded Ẑᵀ·u → replicated (D, K): local ELL product + psum."""
    axes = data_axes(mesh)
    row_spec = P(axes if len(axes) > 1 else axes[0])

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(row_spec[0], None), P(row_spec[0], None), row_spec),
        check_vma=False,
        out_specs=P(None, None))
    def zt(u_local, idx_local, scale_local):
        if chunk_size is None:
            q = ops.zt_matmul(idx_local, u_local, scale_local, d,
                              d_g=d_g, impl=impl)
        else:
            q = streaming.chunked_zt_matmul(
                idx_local, u_local, scale_local, d=d, d_g=d_g,
                chunk_size=chunk_size, impl=impl)
        return jax.lax.psum(q, axes)

    return lambda u: zt(u, idx, rowscale)


def make_sharded_reduce(mesh: Mesh, fn: Callable, *,
                        chunk_size: Optional[int] = None):
    """``RowMatrix.reduce`` on a mesh: within-shard chunk scan + final psum.

    ``fn(acc, *chunk_arrays) -> acc`` must be an *additive* accumulator
    update whose ``init`` is the identity (zeros): each shard folds its own
    row chunks, then the per-shard accumulators are psum'd. Partial trailing
    chunks are zero-padded, so ``fn`` must be insensitive to all-zero rows
    (true for the sum/Gram accumulations this backs).
    """
    axes = data_axes(mesh)
    row_axis = axes if len(axes) > 1 else axes[0]

    def run(init, *tall):
        specs = tuple(P(row_axis, *([None] * (t.ndim - 1))) for t in tall)
        out_specs = jax.tree_util.tree_map(lambda _: P(), init)

        @functools.partial(jax.shard_map, mesh=mesh, in_specs=specs,
                           out_specs=out_specs, check_vma=False)
        def local(*tl):
            m = tl[0].shape[0]
            c = min(chunk_size or m, m)
            pad = (-m) % c
            tp = [jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1))
                  for t in tl]
            steps = (m + pad) // c

            def body(acc, chunks):
                return fn(acc, *chunks), None

            acc, _ = jax.lax.scan(
                body, init,
                tuple(t.reshape((steps, c) + t.shape[1:]) for t in tp))
            return jax.tree_util.tree_map(
                lambda a: jax.lax.psum(a, axes), acc)

        return local(*tall)

    return run


def distributed_kmeans(
    key: jax.Array,
    u: jax.Array,
    k: int,
    mesh: Mesh,
    *,
    n_iters: int = 25,
    n_replicates: int = 10,
    impl: str = "auto",
    chunk_size: Optional[int] = None,
) -> Tuple[KMeansResult, dict]:
    """Lloyd k-means over a row-sharded embedding, consumed shard-chunk-wise.

    The mesh analogue of ``kmeans.streaming_kmeans`` — the embedding never
    leaves its row shards and no device ever materializes more than a chunk
    of derived state:

      1. *Seeding* — a pool of ``min(n, max(4k, 64))`` rows is gathered by
         index (O(pool·dim) cross-device traffic, the only gather anywhere);
         k-means++ D² seeding runs on the pool, once per replicate.
      2. *Updates* — exact Lloyd steps for **all replicates at once**: the
         centroids live in one (r, K, dim) tensor, and every chunk of the
         assignment/statistics sweep (a ``lax.scan`` over row chunks of each
         local shard, padded rows carry zero weight) is shared by all r
         replicates — the data is uploaded/swept once per step, not r times.
         One psum of the (r, K) counts and (r, K, dim) sums — O(r·K·dim)
         traffic per step.
      3. *Final sweep* — a per-chunk assignment pass for the best replicate
         emits the labels still sharded over the rows; only the winning
         replicate's (N,) int32 labels ever leave the mesh.

    Peak per-device temporary: the (chunk, dim) row block plus its
    (chunk, K) distance block — O(chunk), not O(N/shards) and not O(r·chunk)
    (replicates are processed sequentially per chunk via ``lax.map``).
    """
    axes = data_axes(mesh)
    row_axis = axes if len(axes) > 1 else axes[0]
    row_spec = P(row_axis, None)
    n, dim = u.shape
    n_shards = int(np.prod([mesh.shape[a] for a in axes]))
    if n % n_shards:
        raise ValueError(
            f"distributed k-means needs N divisible by the data shards: "
            f"N={n}, shards={n_shards}")
    if k > n:
        raise ValueError(f"k={k} exceeds row count n={n}")
    shard_rows = n // n_shards
    c = min(chunk_size or shard_rows, shard_rows)
    # Measured (not config-derived) residency: the tallest row block that
    # actually reaches the assignment kernel, recorded at trace time. If a
    # future edit materializes a whole shard per step, this becomes
    # shard_rows and the bench gate / residency tests fail.
    observed = {"assign_rows": 0}

    pool_size = min(n, max(4 * k, 64))
    with mesh:
        pool_idx = jax.random.choice(jax.random.fold_in(key, 0), n,
                                     (pool_size,), replace=False)
        pool = jax.block_until_ready(jnp.take(u, pool_idx, axis=0))
    rep_keys = jax.random.split(jax.random.fold_in(key, 1), n_replicates)

    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=(row_spec, P(None, None, None)),
                       out_specs=(P(), P(), P()), check_vma=False)
    def _stats(u_local, cents_r):
        # cents_r: (r, K, dim) — all replicates share each chunk sweep; the
        # per-replicate assignment runs as a sequential lax.map so the live
        # working set stays one (chunk, K) distance block, not r of them.
        m = u_local.shape[0]
        pad = (-m) % c
        up = jnp.pad(u_local, ((0, pad), (0, 0)))
        w = (jnp.arange(m + pad) < m).astype(jnp.float32)
        steps = (m + pad) // c
        r = cents_r.shape[0]

        def body(carry, args):
            counts, sums, inertia = carry
            uc, wc = args
            observed["assign_rows"] = max(observed["assign_rows"],
                                          uc.shape[0])

            def one_rep(cents):
                labels, dists = ops.kmeans_assign(uc, cents, impl=impl)
                cnt = jax.ops.segment_sum(wc, labels, num_segments=k)
                sm = jax.ops.segment_sum(uc * wc[:, None], labels,
                                         num_segments=k)
                return cnt, sm, jnp.sum(dists * wc)

            cnt, sm, iner = jax.lax.map(one_rep, cents_r)
            return (counts + cnt, sums + sm, inertia + iner), None

        init = (jnp.zeros((r, k), jnp.float32),
                jnp.zeros((r, k, dim), jnp.float32),
                jnp.zeros((r,), jnp.float32))
        (counts, sums, inertia), _ = jax.lax.scan(
            body, init, (up.reshape(steps, c, dim), w.reshape(steps, c)))
        return (jax.lax.psum(counts, axes), jax.lax.psum(sums, axes),
                jax.lax.psum(inertia, axes))

    @jax.jit
    def _lloyd(u_in, cents0_r):
        def step(cents_r, _):
            counts, sums, _ = _stats(u_in, cents_r)
            new = sums / jnp.maximum(counts, 1.0)[..., None]
            # keep previous centroid for empty clusters
            return jnp.where((counts > 0)[..., None], new, cents_r), None

        cents_r, _ = jax.lax.scan(step, cents0_r, None, length=n_iters)
        _, _, inertia = _stats(u_in, cents_r)
        return cents_r, inertia

    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=(row_spec, P(None, None)),
                       out_specs=P(row_axis), check_vma=False)
    def _assign(u_local, cents):
        m = u_local.shape[0]
        pad = (-m) % c
        up = jnp.pad(u_local, ((0, pad), (0, 0)))
        steps = (m + pad) // c

        def body(_, uc):
            observed["assign_rows"] = max(observed["assign_rows"],
                                          uc.shape[0])
            labels, _ = ops.kmeans_assign(uc, cents, impl=impl)
            return None, labels

        _, ls = jax.lax.scan(body, None, up.reshape(steps, c, dim))
        return ls.reshape(-1)[:m]

    with mesh:
        # one batched Lloyd run over the (r, K, dim) centroid tensor — every
        # assignment sweep is shared by all replicates
        cents0_r = jnp.stack([_plusplus_init(rk, pool, k) for rk in rep_keys])
        cents_r, inertia_r = _lloyd(u, cents0_r)
        best = int(jnp.argmin(inertia_r))
        best_cents = cents_r[best]
        best_inertia = float(inertia_r[best])
        labels = jax.block_until_ready(_assign(u, best_cents))

    rows = observed["assign_rows"]
    diag = {
        # measured: tallest row block traced into the assignment kernel
        # across the Lloyd and label sweeps — equals the plan chunk unless
        # an O(N/shards) materialization creeps back in
        "kmeans_chunk_rows": rows,
        "kmeans_shard_rows": shard_rows,
        "kmeans_pool_rows": pool_size,
        "kmeans_replicates_batched": n_replicates,
        # per-device live set of one assignment step: the (rows, dim) row
        # block + its (rows, K) distance block — the bench gate's check
        # that the stage is O(shard_chunk), not O(N/shards)
        "kmeans_device_bytes_peak": rows * (dim + k) * 4,
        "kmeans_single_shard_bytes": shard_rows * (dim + k) * 4,
    }
    return KMeansResult(best_cents, labels, jnp.float32(best_inertia)), diag


def sc_rb_distributed(
    x: "np.ndarray | jax.Array",
    config,
    mesh: Mesh,
) -> Tuple[np.ndarray, StageTimer]:
    """Algorithm 2 on a multi-device mesh; returns (labels, stage timer).

    Thin wrapper over ``SCRBModel.fit`` with a ``placement="mesh"`` plan;
    ``config.chunk_size`` turns on within-shard chunking for the mat-vec
    scans *and* the k-means stage. The embedding stays sharded — only the
    labels (and the O(D·K) fitted-model state) leave the run.
    """
    from repro.core.model import SCRBModel
    model = SCRBModel.fit(x, config, mesh=mesh, keep_embedding=False)
    return model.fit_result.labels, model.fit_result.timer


def lower_clustering_cell(mesh: Mesh, *, n: int, dim: int, k: int,
                          n_grids: int, d_g: int, compress: bool = False):
    """Lower the distributed eigensolver iteration for roofline analysis
    (the paper-technique cell of EXPERIMENTS.md §Roofline)."""
    axes = data_axes(mesh)
    row = P(axes if len(axes) > 1 else axes[0], None)
    vec = P(axes if len(axes) > 1 else axes[0])
    d = n_grids * d_g
    idx = jax.ShapeDtypeStruct((n, n_grids), jnp.int32)
    scale = jax.ShapeDtypeStruct((n,), jnp.float32)
    u = jax.ShapeDtypeStruct((n, k), jnp.float32)

    def one_iteration(idx, scale, u):
        mv = make_gram_matvec(mesh, idx, scale, d, d_g, impl="xla",
                              compress=compress)
        return mv(u)

    ns = lambda s: jax.sharding.NamedSharding(mesh, s)
    with mesh:
        return jax.jit(one_iteration,
                       in_shardings=(ns(row), ns(vec), ns(row))
                       ).lower(idx, scale, u)
