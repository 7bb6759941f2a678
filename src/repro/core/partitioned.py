"""Divide-and-conquer partitioned SC_RB — ``placement="partitioned"``.

The paper's linear-in-N fit still ends in one global eigensolve; following
the divide-and-conquer SC line (Li et al., arXiv:2104.15042) this module
replaces it with an embarrassingly parallel map + a tiny reduce:

  1. **partition**  rows split into P near-equal partitions (seeded shuffle
     so sorted inputs don't yield single-cluster partitions; an input given
     as a block list is split by whole blocks, each partition streaming its
     own chunks under ``host_chunked`` residency);
  2. **partition_fits**  each partition runs the *existing* executor
     recursively (``placement="single"``, same residency knobs) with one
     shared fitted ``FeatureMap``, so every partition lives in the same
     D-dimensional feature space. Fits run in a thread pool — one partition
     per local device (or per mesh data-shard via
     ``launch.mesh.partition_devices``), jit cache shared, GIL released
     inside XLA;
  3. **merge**  each partition is summarized by its ``local_clusters``
     k-means centroids *in feature space* (cluster-mass-weighted means of
     ẑ rows, one ``rmatvec`` against the one-hot labels per partition —
     O(P·K·D) total). The union of representatives is factored by one tiny
     (m × m) eigendecomposition (m = P·K representatives) into a merged
     right subspace V, Σ, and the representatives are clustered by a
     weighted k-means into the K global centroids;
  4. **label**  all N rows stream through the standard out-of-sample path
     (transform → fitted-degree normalize → V Σ⁻¹ → row-normalize → nearest
     centroid) — the same jitted ops ``SCRBModel.predict`` serves with, so
     ``predict(x_train)`` reproduces the fit labels exactly and the merged
     model saves/loads/serves unchanged.

No stage ever materializes a global (N, K+buffer) solver iterate; the only
cross-partition objects are the (D, K_l) centroid summaries and the (D,)
degree dual.
"""
from __future__ import annotations

import contextlib
import dataclasses
from concurrent.futures import ThreadPoolExecutor
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import featuremap, rowmatrix, streaming
from repro.core.kmeans import KMeansResult
from repro.core.options import PartitionOptions
from repro.kernels import ops
from repro.obs import trace as obs_trace
from repro.utils import StageTimer


# --------------------------------------------------------------------------
# Partitioning
# --------------------------------------------------------------------------

def partition_rows(x, n_partitions: int, *, shuffle: bool,
                   seed: int) -> List[Any]:
    """Split the input into ≤ ``n_partitions`` row groups.

    Arrays are split into near-equal slices (equal sizes except the tail, so
    per-partition jit compilations are shared); a seeded shuffle first when
    ``shuffle`` (contiguous slices of class-sorted data would hand each
    partition a single cluster). Block lists are split by whole blocks —
    each partition keeps its blocks as its own streaming chunks, never
    concatenated.
    """
    if n_partitions < 1:
        raise ValueError(f"n_partitions must be >= 1, got {n_partitions}")
    if isinstance(x, (list, tuple)):
        blocks = [np.asarray(b) for b in x]
        if not blocks:
            raise ValueError("empty block sequence")
        order = np.arange(len(blocks))
        if shuffle and len(blocks) > 1:
            order = np.random.default_rng(seed).permutation(len(blocks))
        groups = [g for g in np.array_split(order, n_partitions) if g.size]
        return [[blocks[i] for i in g] for g in groups]
    xs = np.asarray(x)
    n = xs.shape[0]
    size = -(-n // n_partitions)
    if shuffle:
        perm = np.random.default_rng(seed).permutation(n)
        return [xs[np.sort(perm[i:i + size])] for i in range(0, n, size)]
    return [xs[i:i + size] for i in range(0, n, size)]


def _part_rows(part) -> int:
    if isinstance(part, list):
        return sum(int(b.shape[0]) for b in part)
    return int(part.shape[0])


# --------------------------------------------------------------------------
# Merge: per-partition centroid representatives → merged subspace + centroids
# --------------------------------------------------------------------------

def _feature_space_representatives(res, local_k: int
                                   ) -> Tuple[np.ndarray, np.ndarray]:
    """One partition's summary: the (m_p, D) cluster means of its ẑ rows
    and their (m_p,) masses. Computed as one ``rmatvec`` of the one-hot
    label matrix — the representation's native Ẑᵀ·tall product, so the
    host-chunked residency guarantee holds (the one-hot tall block streams
    chunk-by-chunk)."""
    z = res.state["z"]
    labels = np.asarray(res.state["km"].labels)
    if isinstance(z, rowmatrix.HostChunkedRows):
        sizes = z.store.chunk_sizes
        offsets = np.concatenate([[0], np.cumsum(sizes)])
        onehot = streaming.ChunkedDense(tuple(
            (labels[offsets[i]:offsets[i + 1], None]
             == np.arange(local_k)[None, :]).astype(np.float32)
            for i in range(len(sizes))))
    else:
        onehot = jnp.asarray(
            (labels[:, None] == np.arange(local_k)[None, :]), jnp.float32)
    sums = np.asarray(z.rmatvec(onehot), np.float64)        # (D, local_k)
    mass = np.bincount(labels, minlength=local_k).astype(np.float64)
    keep = mass > 0
    means = (sums[:, keep] / mass[keep][None, :]).T          # (m_p, D)
    return means, mass[keep]


def merge_representatives(reps: np.ndarray, weights: np.ndarray, k: int
                          ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Factor the weighted representative matrix M (m, D) into the merged
    top-K right subspace: with S = W^{1/2} M, eigh(S Sᵀ) (an (m, m) problem,
    m = P·K_l) gives S Sᵀ = U Λ Uᵀ, so V = Sᵀ U Λ^{-1/2} are the right
    singular vectors and Σ = Λ^{1/2} the spectrum estimate. Returns
    (V (D, k), Σ (k,), rep_embedding (m, k) — the representatives projected
    into the merged space and row-normalized)."""
    m = reps.shape[0]
    if m < k:
        raise ValueError(
            f"only {m} non-empty partition representatives for k={k} "
            f"global clusters; raise n_partitions or local_clusters")
    sw = reps * np.sqrt(weights)[:, None]                    # (m, D)
    gram = sw @ sw.T                                         # (m, m)
    evals, evecs = np.linalg.eigh(gram)                      # ascending
    order = np.argsort(evals)[::-1][:k]
    lam = np.maximum(evals[order], 0.0)
    sig = np.sqrt(lam)
    inv_sig = np.where(sig > 1e-6, 1.0 / np.maximum(sig, 1e-30), 0.0)
    v = (sw.T @ evecs[:, order]) * inv_sig[None, :]          # (D, k)
    # representatives in the merged embedding: row-normalize(M V Σ⁻¹)
    rep_emb = (reps @ v) * inv_sig[None, :]
    norms = np.linalg.norm(rep_emb, axis=1, keepdims=True)
    rep_emb = rep_emb / np.maximum(norms, 1e-12)
    return v.astype(np.float32), sig.astype(np.float32), \
        rep_emb.astype(np.float32)


def _weighted_kmeans(rng: np.random.Generator, pts: np.ndarray,
                     weights: np.ndarray, k: int, *, iters: int,
                     replicates: int
                     ) -> Tuple[np.ndarray, np.ndarray, float]:
    """Mass-weighted Lloyd over the (m, k) representatives — m ≤ P·K_l is
    tiny, so this runs in numpy with k-means++ seeding and best-of-
    replicates by weighted inertia."""
    m = pts.shape[0]
    best = None
    for _ in range(max(1, replicates)):
        # weighted k-means++ init
        cents = np.empty((k, pts.shape[1]), np.float64)
        probs = weights / weights.sum()
        cents[0] = pts[rng.choice(m, p=probs)]
        d2 = ((pts - cents[0]) ** 2).sum(-1)
        for c in range(1, k):
            p = weights * d2
            total = p.sum()
            idx = rng.choice(m, p=p / total) if total > 0 else rng.choice(m)
            cents[c] = pts[idx]
            d2 = np.minimum(d2, ((pts - cents[c]) ** 2).sum(-1))
        labels = np.zeros((m,), np.int32)
        for _ in range(max(1, iters)):
            dists = ((pts[:, None, :] - cents[None, :, :]) ** 2).sum(-1)
            labels = dists.argmin(1)
            for c in range(k):
                sel = labels == c
                mass = weights[sel].sum()
                if mass > 0:
                    cents[c] = (pts[sel] * weights[sel, None]).sum(0) / mass
                else:       # empty cluster: reseed at the farthest point
                    cents[c] = pts[dists.min(1).argmax()]
        dists = ((pts[:, None, :] - cents[None, :, :]) ** 2).sum(-1)
        labels = dists.argmin(1)
        inertia = float((weights * dists[np.arange(m), labels]).sum())
        if best is None or inertia < best[2]:
            best = (cents.astype(np.float32), labels.astype(np.int32),
                    inertia)
    return best


# --------------------------------------------------------------------------
# The partitioned execute — called by executor.execute for the placement
# --------------------------------------------------------------------------

def _resolve_devices(plan) -> Sequence[Any]:
    if plan.mesh is not None:
        from repro.launch.mesh import partition_devices
        return partition_devices(plan.mesh)
    return tuple(jax.local_devices())


def execute_partitioned(x, cfg, plan, *, final_stage: str = "kmeans",
                        keep_embedding: bool = True,
                        keep_state: bool = False):
    """Run the divide-and-conquer fit; same contract as
    ``executor.execute`` (it is the ``placement="partitioned"`` branch of
    it). Timer stages: ``partition`` / ``rb_features`` (shared map fit) /
    ``partition_fits`` / ``merge`` / ``kmeans`` (the global labeling pass).
    """
    from repro.core import executor as _executor
    from repro.core.model import _oos_embed

    devices = _resolve_devices(plan)
    popts: Optional[PartitionOptions] = cfg.partition
    if popts is None:
        popts = PartitionOptions(n_partitions=max(2, len(devices)))
    k = cfg.n_clusters
    local_k = popts.local_clusters or k
    timer = StageTimer()

    with timer.stage("partition"):
        parts = partition_rows(x, popts.n_partitions,
                               shuffle=popts.shuffle, seed=cfg.seed)
    n_parts = len(parts)
    n_total = sum(_part_rows(p) for p in parts)
    if min(_part_rows(p) for p in parts) < local_k:
        raise ValueError(
            f"smallest partition has {min(_part_rows(p) for p in parts)} "
            f"rows < local_clusters={local_k}; lower n_partitions")

    # one shared fitted feature map ⇒ all partitions in one feature space
    fm = plan.feature_map
    if fm is None:
        fm = featuremap.from_config(cfg, impl=plan.impl)
    key = jax.random.PRNGKey(cfg.seed)
    with timer.stage("rb_features"):
        if plan.chunk_size is not None or isinstance(x, (list, tuple)):
            fitted = fm.fit(key, streaming.as_row_chunks(x, plan.chunk_size))
        else:
            fitted = fm.fit(key, jnp.asarray(x))

    sub_residency = ("host_chunked" if plan.chunk_size is not None
                     else "device")
    sub_plan = _executor.ExecutionPlan(
        placement="single", residency=sub_residency,
        chunk_size=plan.chunk_size, prefetch=plan.prefetch, impl=plan.impl,
        block_rows=plan.block_rows, feature_map=fitted,
        laplacian_normalize=plan.laplacian_normalize)
    sub_cfg = dataclasses.replace(cfg, n_clusters=local_k, partition=None)

    workers = popts.workers or max(1, min(n_parts, len(devices)))

    def fit_one(i: int, xp):
        dev = devices[i % len(devices)]
        ctx = (jax.default_device(dev)
               if len(devices) > 1 else contextlib.nullcontext())
        # this span runs on the worker thread, so each partition lands on
        # its own lane of the profiler's host plane (workers > 1 ⇒ parallel
        # lanes), inside the root "fit" span's time on the main thread
        with obs_trace.span("partition_fit", partition=i, device=str(dev),
                            rows=_part_rows(xp)):
            with ctx:
                # recursive executor reuse: each partition is a complete
                # single-placement SC_RB fit ending in its local k-means
                return _executor.execute(xp, sub_cfg, sub_plan,
                                         final_stage="kmeans",
                                         keep_embedding=False,
                                         keep_state=True)

    with timer.stage("partition_fits"):
        if workers > 1:
            with ThreadPoolExecutor(max_workers=workers,
                                    thread_name_prefix="partfit") as pool:
                results = list(pool.map(fit_one, range(n_parts), parts))
        else:
            results = [fit_one(i, xp) for i, xp in enumerate(parts)]

    with timer.stage("merge"):
        reps, weights = [], []
        for res in results:
            means, mass = _feature_space_representatives(res, local_k)
            reps.append(means)
            weights.append(mass)
        dual = np.sum([np.asarray(r.state["z"].degree_dual(), np.float64)
                       for r in results], axis=0)
        reps = np.concatenate(reps, axis=0)
        weights = np.concatenate(weights)
        v, sig, rep_emb = merge_representatives(reps, weights, k)
        dual = dual.astype(np.float32)
        centroids, rep_labels, rep_inertia = None, None, 0.0
        if final_stage == "kmeans":
            rng = np.random.default_rng(cfg.seed + 0x5EED)
            centroids, rep_labels, rep_inertia = _weighted_kmeans(
                rng, rep_emb, weights, k,
                iters=cfg.kmeans_iters, replicates=cfg.kmeans_replicates)

    # global labeling: stream every row through the out-of-sample path the
    # fitted model serves with — predict(x_train) reproduces these labels
    inv_sig = np.where(sig > 1e-6, 1.0 / np.maximum(sig, 1e-30),
                       0.0).astype(np.float32)
    proj = jnp.asarray(v * inv_sig[None, :])
    dual_j = jnp.asarray(dual)
    cents_j = None if centroids is None else jnp.asarray(centroids)
    batch = plan.chunk_size
    emb_chunks, label_chunks = [], []
    inertia = 0.0
    with timer.stage("kmeans"):
        for c in streaming.as_row_chunks(x, batch):
            xb = jnp.asarray(np.asarray(c, np.float32))
            u = _oos_embed(fitted, dual_j, proj, xb,
                           laplacian=plan.laplacian_normalize)
            if cents_j is not None:
                lab, d2 = ops.kmeans_assign(u, cents_j, impl=cfg.impl)
                label_chunks.append(np.asarray(lab))
                inertia += float(jnp.sum(d2))
            if keep_embedding:
                emb_chunks.append(np.asarray(u))

    labels = (np.concatenate(label_chunks)
              if label_chunks else None)
    embedding = (np.concatenate(emb_chunks, axis=0)
                 if emb_chunks else None)

    deg_min, deg_max = (min(r.diagnostics["degrees_min"] for r in results),
                        max(r.diagnostics["degrees_max"] for r in results))
    part_diag = {
        "n_partitions": n_parts,
        "workers": workers,
        "local_clusters": local_k,
        "shuffle": popts.shuffle,
        "partition_rows": [_part_rows(p) for p in parts],
        "partition_fit_s": [r.timer.total for r in results],
        "partition_stage_s": [dict(r.timer.times) for r in results],
        "representatives": int(reps.shape[0]),
        "rep_kmeans_inertia": float(rep_inertia),
        "merge_singular_values": [float(s) for s in sig],
        "devices": len(devices),
    }
    diagnostics = {
        "plan": {"placement": "partitioned", "residency": plan.residency,
                 "chunk_size": plan.chunk_size, "prefetch": plan.prefetch,
                 "impl": plan.impl},
        "feature_map": fitted.name,
        "solver": results[0].diagnostics["solver"],
        "solver_requested": cfg.solver_options.solver,
        "solver_precond": cfg.solver_options.precond,
        "solver_iterations": max(int(r.diagnostics["solver_iterations"])
                                 for r in results),
        "solver_resnorms": np.max(np.stack(
            [np.asarray(r.diagnostics["solver_resnorms"])
             for r in results]), axis=0),
        "degrees_min": deg_min,
        "degrees_max": deg_max,
        "n_features_D": fitted.n_features,
        "nnz": n_total * (fitted.n_grids if fitted.kind == "ell"
                          else fitted.n_features),
        "partitioned": part_diag,
    }
    if labels is not None:
        diagnostics["kmeans_inertia"] = inertia

    z_all = rowmatrix.PartitionedRows(
        parts=tuple(r.state["z"] for r in results), fmap=fitted, dual=dual)
    diagnostics.update(z_all.residency_diagnostics(cfg))
    km = None
    if labels is not None:
        km = KMeansResult(centroids=centroids, labels=labels,
                          inertia=inertia)
    state = None
    if keep_state:
        state = {
            "z": z_all,
            "features": rowmatrix.FittedFeatures(fitted, None),
            "eig": None, "u_hat": None, "km": km, "plan": plan,
            "oos_proj": None,
            # the merged O(D·K) out-of-sample state, precomputed — no extra
            # rmatvec pass needed by SCRBModel.fit
            "partitioned": {"right_vectors": v, "singular_values": sig,
                            "degree_dual": dual},
        }
    for res in results:
        res.state = None              # drop per-partition O(N_p) internals
    return _executor.FitResult(
        labels=labels,
        embedding=embedding,
        singular_values=sig,
        timer=timer,
        diagnostics=diagnostics,
        state=state,
    )
