"""Jit'd Lloyd k-means with k-means++ seeding and replicates (Alg. 2 step 5).

Matches the paper's protocol (Matlab kmeans, 10 replicates): best-of-r
restarts by inertia. The assignment step routes through the fused Pallas /
XLA kernel in ``repro.kernels.ops``.

Three clustering drivers back the executor's k-means stage, one per data
representation (``repro.core.rowmatrix``): ``kmeans`` (device-dense, bit-
identical to the seed pipeline), ``streaming_kmeans`` (host-chunked), and
``repro.core.distributed.distributed_kmeans`` (mesh-sharded, shard-chunk-
wise — it reuses ``_plusplus_init`` pool seeding from here).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops
from repro.utils import prefetch_to_device


class KMeansResult(NamedTuple):
    centroids: jax.Array  # (k, d)
    labels: jax.Array     # (n,) int32
    inertia: jax.Array    # scalar


def _plusplus_init(key: jax.Array, x: jax.Array, k: int) -> jax.Array:
    """k-means++ seeding (D² weighting)."""
    n = x.shape[0]
    k0, key = jax.random.split(key)
    first = jax.random.randint(k0, (), 0, n)
    cents0 = jnp.zeros((k, x.shape[1]), x.dtype).at[0].set(x[first])
    d0 = jnp.sum((x - x[first][None, :]) ** 2, axis=-1)

    def body(i, carry):
        cents, mindist, key = carry
        key, kc = jax.random.split(key)
        logits = jnp.log(jnp.maximum(mindist, 1e-30))
        pick = jax.random.categorical(kc, logits)
        c = x[pick]
        cents = cents.at[i].set(c)
        dist_new = jnp.sum((x - c[None, :]) ** 2, axis=-1)
        return cents, jnp.minimum(mindist, dist_new), key

    cents, _, _ = jax.lax.fori_loop(1, k, body, (cents0, d0, key))
    return cents


def _lloyd(x: jax.Array, cents: jax.Array, n_iters: int, impl: str) -> KMeansResult:
    k = cents.shape[0]

    def step(cents, _):
        labels, dists = ops.kmeans_assign(x, cents, impl=impl)
        onehot_counts = jax.ops.segment_sum(
            jnp.ones_like(dists), labels, num_segments=k)
        sums = jax.ops.segment_sum(x, labels, num_segments=k)
        new = sums / jnp.maximum(onehot_counts, 1.0)[:, None]
        # keep previous centroid for empty clusters
        new = jnp.where((onehot_counts > 0)[:, None], new, cents)
        return new, None

    cents, _ = jax.lax.scan(step, cents, None, length=n_iters)
    labels, dists = ops.kmeans_assign(x, cents, impl=impl)
    return KMeansResult(cents, labels, jnp.sum(dists))


@functools.partial(
    jax.jit, static_argnames=("k", "n_iters", "n_replicates", "impl")
)
def kmeans(
    key: jax.Array,
    x: jax.Array,
    k: int,
    *,
    n_iters: int = 25,
    n_replicates: int = 10,
    impl: str = "auto",
) -> KMeansResult:
    """Best-of-``n_replicates`` Lloyd runs with k-means++ seeding."""
    x = x.astype(jnp.float32)

    def one(key):
        cents0 = _plusplus_init(key, x, k)
        return _lloyd(x, cents0, n_iters, impl)

    keys = jax.random.split(key, n_replicates)
    results = jax.lax.map(one, keys)       # sequential — bounded memory
    best = jnp.argmin(results.inertia)
    return KMeansResult(
        results.centroids[best], results.labels[best], results.inertia[best]
    )


def row_normalize(u: jax.Array, eps: float = 1e-12) -> jax.Array:
    """Normalize each spectral-embedding row to unit ℓ₂ norm (Alg. 2 step 4)."""
    norms = jnp.linalg.norm(u, axis=1, keepdims=True)
    return u / jnp.maximum(norms, eps)


@functools.partial(
    jax.jit, static_argnames=("k", "batch_size", "n_steps", "impl"))
def minibatch_kmeans(
    key: jax.Array,
    x: jax.Array,
    k: int,
    *,
    batch_size: int = 4_096,
    n_steps: int = 100,
    impl: str = "auto",
) -> KMeansResult:
    """Mini-batch k-means (Sculley 2010) — the beyond-paper path for the
    final clustering stage at N ≫ 10⁷: each step touches ``batch_size``
    rows, with per-center 1/count learning rates, so the stage costs
    O(steps·batch·K·d) instead of the paper's O(N·K²·t).
    """
    x = x.astype(jnp.float32)
    n = x.shape[0]
    kinit, kloop = jax.random.split(key)
    # clamp the seed pool to n: choice(replace=False) crashes for tiny
    # inputs where the default pool max(4k, 64) exceeds the row count
    pool = min(n, max(4 * k, 64))
    sample0 = x[jax.random.choice(kinit, n, (pool,), replace=False)]
    cents0 = _plusplus_init(jax.random.fold_in(kinit, 1), sample0, k)

    def step(carry, skey):
        cents, counts = carry
        rows = jax.random.choice(skey, n, (batch_size,))
        xb = x[rows]
        labels, _ = ops.kmeans_assign(xb, cents, impl=impl)
        add = jax.ops.segment_sum(jnp.ones((batch_size,), jnp.float32),
                                  labels, num_segments=k)
        sums = jax.ops.segment_sum(xb, labels, num_segments=k)
        counts_new = counts + add
        lr = add / jnp.maximum(counts_new, 1.0)
        target = sums / jnp.maximum(add, 1.0)[:, None]
        cents = jnp.where((add > 0)[:, None],
                          cents + lr[:, None] * (target - cents), cents)
        return (cents, counts_new), None

    (cents, _), _ = jax.lax.scan(
        step, (cents0, jnp.zeros((k,), jnp.float32)),
        jax.random.split(kloop, n_steps))
    labels, dists = ops.kmeans_assign(x, cents, impl=impl)
    return KMeansResult(cents, labels, jnp.sum(dists))


# --------------------------------------------------------------------------
# Out-of-core k-means over host-resident row chunks (streaming pipeline
# stages 4–5): chunked row normalization, reservoir-seeded k-means++, and
# Sculley-style mini-batch updates fed by prefetched chunk iteration.
# --------------------------------------------------------------------------

Chunks = Union[Sequence[np.ndarray], "object"]   # ChunkedDense or np blocks


def _as_chunk_list(chunks: Chunks) -> list[np.ndarray]:
    if hasattr(chunks, "chunks"):                # streaming.ChunkedDense
        return [np.asarray(c, np.float32) for c in chunks.chunks]
    return [np.asarray(c, np.float32) for c in chunks]


def row_normalize_chunks(chunks: Chunks, *, prefetch: bool = True,
                         measure: Optional[dict] = None):
    """Chunked Alg. 2 step 4: unit-ℓ₂ rows, one chunk on device at a time.

    Row normalization is row-local, so this agrees with ``row_normalize``
    on the concatenated array for any chunking: the same jax computation
    runs per chunk, and only the reduction order XLA picks for the row norm
    at a given row count can move the result by a few ulp.
    """
    from repro.core.streaming import ChunkedDense
    out = [
        np.asarray(row_normalize(c))
        for c in prefetch_to_device(_as_chunk_list(chunks), enabled=prefetch,
                                    measure=measure)
    ]
    return ChunkedDense(tuple(out))


def _reservoir_sample_chunks(
    chunks: Sequence[np.ndarray], pool_size: int, rng: np.random.Generator
) -> np.ndarray:
    """Uniform reservoir (Algorithm R) over streamed row chunks — one pass,
    O(pool_size) host memory, never concatenates the dataset."""
    dim = chunks[0].shape[1]
    pool = np.empty((pool_size, dim), np.float32)
    seen = 0
    for c in chunks:
        rows = c.shape[0]
        gidx = seen + np.arange(rows)
        head = gidx < pool_size                  # fill phase
        pool[gidx[head]] = c[head]
        tail = ~head
        if np.any(tail):
            draws = rng.integers(0, gidx[tail] + 1)
            replace = draws < pool_size
            # later rows overwrite earlier ones on collision — matches the
            # sequential algorithm (np fancy assignment keeps the last write)
            pool[draws[replace]] = c[tail][replace]
        seen += rows
    return pool


@functools.partial(jax.jit, static_argnames=("impl",))
def _minibatch_update(xb, cents, counts, *, impl):
    """One Sculley step from a full chunk: per-center 1/count learning rate."""
    _, add, sums, _ = ops.kmeans_assign_stats(xb, cents, impl=impl)
    counts_new = counts + add
    lr = add / jnp.maximum(counts_new, 1.0)
    target = sums / jnp.maximum(add, 1.0)[:, None]
    cents = jnp.where((add > 0)[:, None],
                      cents + lr[:, None] * (target - cents), cents)
    return cents, counts_new


def streaming_kmeans(
    key: jax.Array,
    chunks: Chunks,
    k: int,
    *,
    n_steps: int = 100,
    n_replicates: int = 4,
    impl: str = "auto",
    prefetch: bool = True,
    measure: Optional[dict] = None,
) -> KMeansResult:
    """k-means over host-resident row chunks — no O(N) device allocation.

    The out-of-core final stage of the streaming SC_RB pipeline:

      1. *Seeding* — a uniform reservoir sample (one streamed pass) stands in
         for the full dataset; k-means++ D² seeding runs on the pool, once
         per replicate.
      2. *Updates* — ``minibatch_kmeans``-style steps (Sculley 2010) fed by
         cyclic prefetched chunk iteration; every replicate shares each
         uploaded chunk, so r replicates cost one data pass.
      3. *Final sweep* — one chunked assignment pass scoring every
         replicate's inertia and emitting its per-chunk host labels (O(r·N)
         int32 host memory, same order as the chunked embedding itself — a
         second streamed pass would cost more than the label storage); the
         best replicate's chunks are concatenated into the result.

    Peak device residency: one chunk + O(r·k·dim) centroids.
    """
    chunk_list = _as_chunk_list(chunks)
    n = sum(c.shape[0] for c in chunk_list)
    if k > n:
        raise ValueError(f"k={k} exceeds row count n={n}")
    seed = int(jax.random.randint(key, (), 0, jnp.iinfo(jnp.int32).max))
    rng = np.random.default_rng(seed)
    pool_size = min(n, max(4 * k, 64))
    pool = jnp.asarray(_reservoir_sample_chunks(chunk_list, pool_size, rng))

    rep_keys = jax.random.split(jax.random.fold_in(key, 1), n_replicates)
    cents = [_plusplus_init(rk, pool, k) for rk in rep_keys]
    counts = [jnp.zeros((k,), jnp.float32) for _ in range(n_replicates)]

    step = 0
    while step < n_steps:
        for xb in prefetch_to_device(chunk_list, enabled=prefetch,
                                     measure=measure):
            if step >= n_steps:
                break
            for rep in range(n_replicates):
                cents[rep], counts[rep] = _minibatch_update(
                    xb, cents[rep], counts[rep], impl=impl)
            step += 1

    inertia = np.zeros((n_replicates,))
    label_chunks = [[] for _ in range(n_replicates)]
    for xb in prefetch_to_device(chunk_list, enabled=prefetch, measure=measure):
        for rep in range(n_replicates):
            labels_c, dists = ops.kmeans_assign(xb, cents[rep], impl=impl)
            inertia[rep] += float(jnp.sum(dists))
            label_chunks[rep].append(np.asarray(labels_c))
    best = int(np.argmin(inertia))
    return KMeansResult(
        np.asarray(cents[best]), np.concatenate(label_chunks[best]),
        np.float32(inertia[best]))
