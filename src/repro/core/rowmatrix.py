"""RowMatrix — the data-representation layer of the plan-based executor.

Algorithm 2's five stages (RB features, degrees, eigensolve, row-normalize,
k-means) are written ONCE in ``repro.core.executor`` against the protocol
below; what used to be three hand-written pipelines (single-shot, host-
chunked streaming, SPMD) is now three *representations* of the same
row-partitioned operand Ẑ = D̂^{-1/2}Z:

  - ``DeviceRows``      — the whole (N, R) ELL matrix on one device
    (``graph.NormalizedAdjacency``); tall dense operands are plain arrays.
  - ``HostChunkedRows`` — host-resident row chunks (``streaming.ChunkedELL``);
    tall dense operands are ``streaming.ChunkedDense`` and every sweep
    uploads one prefetched chunk at a time.
  - ``MeshRows``        — rows sharded over the mesh's data axes; mat-vecs
    run under ``shard_map`` with one (D, K) psum, and with a plan
    ``chunk_size`` every within-shard sweep is a ``lax.scan`` over row
    chunks, bounding per-device working sets to O(chunk) regardless of the
    shard size (the streaming × distributed composition).

Each representation implements the same small surface —

  ``matvec``/``rmatvec``/``gram``  the Ẑ / Ẑᵀ / ẐẐᵀ products,
  ``map_row_chunks(fn, *tall)``    apply a row-local fn chunk-by-chunk,
  ``reduce(fn, init, *tall)``      fold an additive accumulator over row
                                   chunks (init must be the identity, e.g.
                                   zeros: mesh placement psums the final
                                   accumulator across shards),
  ``eigenpairs`` / ``cluster``     the solver/k-means drivers that match the
                                   representation's residency,

— so an ``ExecutionPlan`` (placement × residency) picks a representation and
the executor never branches on where the data lives. Combinations that used
to fall between the hand-written paths (e.g. chunked-within-shard k-means)
are just plan points here.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional, Protocol, Tuple, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import eigensolver, featuremap, graph, streaming
from repro.core.kmeans import kmeans as _kmeans, streaming_kmeans
from repro.kernels import ops
from repro.utils import prefetch_to_device


def _solver_precond(cfg, deg) -> "Optional[np.ndarray]":
    """The (N,) diagonal preconditioner a config selects — the degree-based
    Jacobi diagonal for ``SolverOptions.precond="degree"`` (diag(ẐẐᵀ)_i =
    1/deg_i exactly under the RB self-collision identity), else None. The
    LOBPCG family applies it to the residual block; lanczos/subspace ignore
    it."""
    precond = cfg.solver_options.precond
    if precond == "degree":
        return eigensolver.degree_precond(np.asarray(deg))
    if precond in ("none", None):
        return None
    raise ValueError(
        f"unknown solver precond {precond!r}; options ('degree', 'none')")


@dataclasses.dataclass(frozen=True)
class FittedFeatures:
    """Stage-1 output: a *fitted* feature map + the representation's feature
    payload (device idx/Φ, host chunks, or sharded idx)."""

    fmap: Any       # fitted repro.core.featuremap.FeatureMap
    payload: Any


@runtime_checkable
class RowMatrix(Protocol):
    """A row-partitioned Ẑ with representation-specific residency/placement.

    ``tall`` operands (the (N, K) block iterates / embedding) use the
    representation's native tall type: ``jax.Array`` (device), ``ChunkedDense``
    (host chunks), or a row-sharded ``jax.Array`` (mesh).
    """

    kind: str

    @property
    def n(self) -> int: ...
    def degree_range(self) -> Tuple[float, float]: ...
    def degree_dual(self) -> np.ndarray: ...   # (D,) out-of-sample degrees
    def matvec(self, v): ...          # Ẑ v : (D, K) → tall
    def matvec_tall(self, v): ...     # Ẑ v in the native tall type
    def rmatvec(self, u): ...         # Ẑᵀ u : tall → (D, K)
    def gram(self, u): ...            # (Ẑ Ẑᵀ) u : tall → tall
    def random_tall(self, key, width: int, dist: str = "normal"): ...
    def map_row_chunks(self, fn: Callable, *tall): ...
    def reduce(self, fn: Callable, init, *tall): ...
    def eigenpairs(self, k: int, key: jax.Array, cfg,
                   x0=None) -> eigensolver.EigResult: ...
    def cluster(self, key: jax.Array, u_hat, cfg) -> Tuple[Any, dict]: ...


# --------------------------------------------------------------------------
# Single device, device residency — the seed pipeline's representation.
# --------------------------------------------------------------------------

@dataclasses.dataclass
class DeviceRows:
    """Whole-array residency on one device (bit-identical to the seed
    single-shot pipeline: same ops, same order, same keys).

    ``adj`` is either a ``graph.NormalizedAdjacency`` (ELL feature maps) or
    a ``featuremap.NormalizedDenseFeatures`` (dense maps) — same mat-vec
    surface, so every method below is representation-agnostic.
    """

    kind = "device"
    adj: Any

    @classmethod
    def fit_transform(cls, x, fm, cfg, plan, key) -> FittedFeatures:
        x = jnp.asarray(x)
        fitted = fm.fit(key, x)
        payload = jax.block_until_ready(fitted.transform(x))
        return FittedFeatures(fitted, payload)

    @classmethod
    def from_features(cls, feats: FittedFeatures, cfg, plan) -> "DeviceRows":
        fm = feats.fmap
        if fm.kind == "ell":
            adj = graph.build_normalized_adjacency(
                feats.payload, d=fm.n_features, d_g=fm.d_g,
                impl=plan.impl, normalize=plan.laplacian_normalize)
            jax.block_until_ready(adj.rowscale)
        else:
            adj = featuremap.build_normalized_dense(
                feats.payload, laplacian=plan.laplacian_normalize)
            jax.block_until_ready(adj.rowscale)
        return cls(adj)

    @property
    def n(self) -> int:
        return self.adj.n

    @property
    def deg(self) -> np.ndarray:
        return np.asarray(self.adj.deg)

    def degree_range(self) -> Tuple[float, float]:
        return float(jnp.min(self.adj.deg)), float(jnp.max(self.adj.deg))

    def matvec(self, v):
        return self.adj.matmat(v)

    def matvec_tall(self, v):
        return self.adj.matmat(v)

    def rmatvec(self, u):
        return self.adj.rmatmat(u)

    def gram(self, u):
        return self.adj.gram_matvec(u)

    def random_tall(self, key, width, dist="normal"):
        if dist == "rademacher":
            return jax.random.rademacher(key, (self.n, width), jnp.float32)
        return jax.random.normal(key, (self.n, width), jnp.float32)

    def map_row_chunks(self, fn, *tall):
        return fn(*tall)

    def reduce(self, fn, init, *tall):
        return fn(init, *tall)

    def degree_dual(self) -> np.ndarray:
        """The O(D) vector the out-of-sample degree of a new point is read
        from: bin occupancies Zᵀ1 for ELL maps (retained from the degree
        pass — no extra sweep), Φᵀ1 for dense maps."""
        if isinstance(self.adj, featuremap.NormalizedDenseFeatures):
            return np.asarray(self.adj.colsum, np.float32)
        if self.adj.counts is not None:
            return np.asarray(self.adj.counts, np.float32)
        counts = ops.bin_counts(self.adj.idx, d=self.adj.d, d_g=self.adj.d_g,
                                impl=self.adj.impl)
        return np.asarray(counts).astype(np.float32)

    def eigenpairs(self, k, key, cfg, x0=None) -> eigensolver.EigResult:
        so = cfg.solver_options
        eig = eigensolver.top_k_eigenpairs(
            self.adj.gram_matvec, self.n, k, key,
            solver=so.solver, max_iters=so.iters, tol=so.tol,
            buffer=so.buffer, x0=x0,
            precond=_solver_precond(cfg, self.deg),
            stable_tol=so.stable_tol)
        jax.block_until_ready(eig.vectors)
        return eig

    def cluster(self, key, u_hat, cfg) -> Tuple[Any, dict]:
        res = _kmeans(key, u_hat, cfg.n_clusters, n_iters=cfg.kmeans_iters,
                      n_replicates=cfg.kmeans_replicates, impl=cfg.impl)
        jax.block_until_ready(res.labels)
        return res, {}

    def residency_diagnostics(self, cfg) -> dict:
        return {}


# --------------------------------------------------------------------------
# Single placement, host-chunked residency — the streaming representation.
# --------------------------------------------------------------------------

@dataclasses.dataclass
class HostChunkedRows:
    """Host-resident row chunks; no stage allocates an O(N) device array.

    ``store`` is either a ``streaming.ChunkedELL`` (ELL feature maps) or a
    ``featuremap.ChunkedDenseFeatures`` (dense maps) — same chunk-sweep
    surface (prefetched uploads, ``gram_matvec_chunked``, ``h2d_stats``).
    """

    kind = "host_chunked"
    store: Any

    @classmethod
    def fit_transform(cls, x, fm, cfg, plan, key) -> FittedFeatures:
        x_chunks = streaming.as_row_chunks(x, plan.chunk_size)
        fitted = fm.fit(key, x_chunks)
        # transforms are row-local ⇒ bit-identical to the single-shot
        # transform for any chunking; chunk outputs are offloaded to host
        payload = tuple(
            np.asarray(fitted.transform(jnp.asarray(c, jnp.float32)))
            for c in x_chunks)
        return FittedFeatures(fitted, payload)

    @classmethod
    def from_features(cls, feats, cfg, plan) -> "HostChunkedRows":
        fm = feats.fmap
        if fm.kind == "ell":
            store = streaming.build_chunked_adjacency(
                feats.payload, d=fm.n_features, d_g=fm.d_g,
                impl=plan.impl, prefetch=plan.prefetch,
                normalize=plan.laplacian_normalize)
        else:
            store = featuremap.build_chunked_dense(
                feats.payload, laplacian=plan.laplacian_normalize,
                prefetch=plan.prefetch)
        return cls(store)

    @property
    def ell(self):
        """Back-compat alias for the storage layer (historically always a
        ``ChunkedELL``)."""
        return self.store

    @property
    def n(self) -> int:
        return self.store.n

    @property
    def deg(self) -> np.ndarray:
        return self.store.deg

    def degree_range(self) -> Tuple[float, float]:
        return float(np.min(self.store.deg)), float(np.max(self.store.deg))

    def degree_dual(self) -> np.ndarray:
        if isinstance(self.store, featuremap.ChunkedDenseFeatures):
            return np.asarray(self.store.colsum, np.float32)
        if self.store.counts is not None:
            return np.asarray(self.store.counts).astype(np.float32)
        counts = streaming.chunked_bin_counts(
            self.store.idx_chunks, d=self.store.d, d_g=self.store.d_g,
            impl=self.store.impl, prefetch=self.store.prefetch)
        return np.asarray(counts).astype(np.float32)

    def matvec(self, v):
        return self.store.matmat(v)

    def matvec_tall(self, v):
        """Ẑ v with the representation's native tall output — host-resident
        row chunks (``matvec`` concatenates on device, which is exactly the
        O(N·K) allocation the compressive path must avoid)."""
        return self.store.matmat_chunked(jnp.asarray(v, jnp.float32))

    def random_tall(self, key, width, dist="normal"):
        """A host-chunked random tall block: each chunk gets an
        independently folded key, so no (N, width) array is ever built."""
        sizes = self.store.chunk_sizes
        if dist == "rademacher":
            return streaming.ChunkedDense(tuple(
                np.asarray(jax.random.rademacher(
                    jax.random.fold_in(key, i), (s, width), jnp.float32))
                for i, s in enumerate(sizes)))
        return streaming.ChunkedDense.random_normal(key, sizes, width)

    def rmatvec(self, u):
        if isinstance(u, streaming.ChunkedDense):
            return self.store.rmatmat_chunked(u)
        return self.store.rmatmat(u)

    def gram(self, u):
        if isinstance(u, streaming.ChunkedDense):
            return self.store.gram_matvec_chunked(u)
        return self.store.gram_matvec(u)

    def _tall_chunks(self, tall):
        if isinstance(tall, streaming.ChunkedDense):
            return tall.chunks
        return tall  # already a sequence of aligned host chunks

    def map_row_chunks(self, fn, *tall):
        seqs = [self._tall_chunks(t) for t in tall]
        out = [
            np.asarray(fn(*cs))
            for cs in prefetch_to_device(zip(*seqs), enabled=self.ell.prefetch,
                                         measure=self.ell.h2d_stats)
        ]
        return streaming.ChunkedDense(tuple(out))

    def reduce(self, fn, init, *tall):
        seqs = [self._tall_chunks(t) for t in tall]
        acc = init
        for cs in prefetch_to_device(zip(*seqs), enabled=self.ell.prefetch,
                                     measure=self.ell.h2d_stats):
            acc = fn(acc, *cs)
        return acc

    def eigenpairs(self, k, key, cfg, x0=None) -> eigensolver.EigResult:
        so = cfg.solver_options
        return eigensolver.top_k_eigenpairs(
            self.ell.gram_matvec_chunked, self.n, k, key,
            solver=so.solver, max_iters=so.iters, tol=so.tol,
            buffer=so.buffer, streaming=True,
            chunk_sizes=self.ell.chunk_sizes, x0=x0,
            precond=_solver_precond(cfg, self.store.deg),
            stable_tol=so.stable_tol)

    def cluster(self, key, u_hat, cfg) -> Tuple[Any, dict]:
        kmeans_steps = max(cfg.kmeans_iters, u_hat.n_chunks)
        res = streaming_kmeans(
            key, u_hat, cfg.n_clusters, n_steps=kmeans_steps,
            n_replicates=cfg.kmeans_replicates, impl=cfg.impl,
            prefetch=self.ell.prefetch, measure=self.ell.h2d_stats)
        return res, {"kmeans_steps": kmeans_steps}

    def residency_diagnostics(self, cfg) -> dict:
        ell = self.ell
        return {
            "n_chunks": ell.n_chunks,
            "chunk_rows_max": ell.max_chunk_rows,
            "ell_device_bytes_peak": ell.ell_device_bytes_peak,
            # widest dense chunk on device: the (chunk, k+buffer) LOBPCG block
            "embedding_device_bytes_peak": ell.max_chunk_rows * 4
            * eigensolver.lobpcg_block_width(
                ell.n, cfg.n_clusters, cfg.solver_options.buffer),
            # measured: largest single H2D upload issued by any chunk sweep
            # (degrees, LOBPCG mat-vecs, row normalize, k-means) — the
            # runtime cross-check that no sweep streamed an O(N) item
            "h2d_max_chunk_bytes": ell.h2d_stats.get("max_item_bytes", 0),
            "prefetch": ell.prefetch,
        }


# --------------------------------------------------------------------------
# Mesh placement — rows sharded over the data axes; optional within-shard
# chunking (residency="host_chunked" under placement="mesh").
# --------------------------------------------------------------------------

@dataclasses.dataclass
class MeshRows:
    """Row-sharded Ẑ on a device mesh, explicit collectives via shard_map.

    ``chunk_size`` bounds every within-shard sweep (Gram mat-vec scans and
    the k-means assignment/stats sweeps) to O(chunk)-sized working sets, so
    streaming composes with sharding instead of being a separate pipeline.
    """

    kind = "mesh"
    mesh: Any                     # jax.sharding.Mesh
    idx: jax.Array                # (N, R) int32, row-sharded
    rowscale: jax.Array           # (N,) float32, row-sharded
    degrees: jax.Array            # (N,) float32, row-sharded
    d: int
    d_g: int
    impl: str = "auto"
    chunk_size: Optional[int] = None
    compress: bool = False
    counts: Optional[jax.Array] = None   # (D,) replicated Zᵀ1 (degree dual)
    _gram_cache: Any = dataclasses.field(default=None, repr=False,
                                         compare=False)

    @classmethod
    def fit_transform(cls, x, fm, cfg, plan, key) -> FittedFeatures:
        if fm.kind != "ell":
            raise ValueError(
                f"placement='mesh' currently supports ELL feature maps only "
                f"(got {fm.name!r} of kind {fm.kind!r}); run dense maps "
                f"under placement='single'")
        mesh = plan.mesh
        fitted = fm.fit(key, np.asarray(x))
        row_spec = cls._row_spec(mesh)
        xs = jax.device_put(np.asarray(x, np.float32),
                            cls._row_sharding(mesh))
        # per-shard transform: GSPMD cannot partition a Mosaic kernel, and
        # the map is row-local
        transform = jax.jit(jax.shard_map(
            lambda f, xl: f.transform(xl), mesh=mesh,
            in_specs=(P(), row_spec), out_specs=row_spec, check_vma=False))
        idx = jax.block_until_ready(transform(fitted, xs))
        return FittedFeatures(fitted, idx)

    @classmethod
    def from_features(cls, feats: FittedFeatures, cfg, plan) -> "MeshRows":
        from repro.core.distributed import make_degree_pass
        fm = feats.fmap
        mesh = plan.mesh
        idx = feats.payload
        n = idx.shape[0]
        d = fm.n_features
        scale_shard = cls._vec_sharding(mesh)
        with mesh:
            # one pass yields both the degrees and the replicated (D,) bin
            # occupancies — the fitted-model degree dual, kept for free
            deg, counts = jax.jit(make_degree_pass(
                mesh, d, fm.d_g, plan.impl,
                compress=plan.collective_compress,
                chunk_size=plan.chunk_size))(idx)
            if plan.laplacian_normalize:
                rowscale = 1.0 / jnp.sqrt(cfg.n_grids * jnp.maximum(deg, 1e-8))
            else:
                rowscale = jnp.full((n,), 1.0 / np.sqrt(cfg.n_grids),
                                    jnp.float32)
            rowscale = jax.block_until_ready(
                jax.lax.with_sharding_constraint(rowscale, scale_shard))
        return cls(mesh, idx, rowscale, deg, d=d, d_g=fm.d_g,
                   impl=plan.impl, chunk_size=plan.chunk_size,
                   compress=plan.collective_compress, counts=counts)

    # -- sharding helpers ---------------------------------------------------
    @staticmethod
    def _axes(mesh) -> Tuple[str, ...]:
        from repro.launch.mesh import data_axes
        return data_axes(mesh)

    @classmethod
    def _row_spec(cls, mesh) -> P:
        axes = cls._axes(mesh)
        return P(axes if len(axes) > 1 else axes[0], None)

    @classmethod
    def _row_sharding(cls, mesh) -> NamedSharding:
        return NamedSharding(mesh, cls._row_spec(mesh))

    @classmethod
    def _vec_sharding(cls, mesh) -> NamedSharding:
        axes = cls._axes(mesh)
        return NamedSharding(mesh, P(axes if len(axes) > 1 else axes[0]))

    @property
    def n(self) -> int:
        return self.idx.shape[0]

    @property
    def n_shards(self) -> int:
        return int(np.prod([self.mesh.shape[a] for a in self._axes(self.mesh)]))

    @property
    def deg(self) -> np.ndarray:
        return np.asarray(self.degrees)

    def degree_range(self) -> Tuple[float, float]:
        """Min/max reduced on-device (two scalar transfers, no O(N) gather
        of the sharded degrees)."""
        with self.mesh:
            return float(jnp.min(self.degrees)), float(jnp.max(self.degrees))

    def _gram_fn(self):
        # built once per representation: repeated eager calls (the
        # compressive Chebyshev recurrence applies it O(degree) times)
        # must hit one traced shard_map, not rebuild it per mat-vec
        if self._gram_cache is None:
            from repro.core.distributed import make_gram_matvec
            self._gram_cache = make_gram_matvec(
                self.mesh, self.idx, self.rowscale, self.d,
                self.d_g, self.impl, compress=self.compress,
                chunk_size=self.chunk_size)
        return self._gram_cache

    def matvec(self, v):
        spec = self._row_spec(self.mesh)
        zv = jax.shard_map(
            lambda i, vv, sc: ops.z_matmul(i, vv, sc, d_g=self.d_g,
                                           impl=self.impl),
            mesh=self.mesh, in_specs=(spec, P(), P(spec[0])),
            out_specs=spec, check_vma=False)
        return zv(self.idx, v, self.rowscale)

    def matvec_tall(self, v):
        return self.matvec(v)   # already row-sharded (idx carries the spec)

    def random_tall(self, key, width, dist="normal"):
        with self.mesh:
            if dist == "rademacher":
                r = jax.random.rademacher(key, (self.n, width), jnp.float32)
            else:
                r = jax.random.normal(key, (self.n, width), jnp.float32)
            return jax.device_put(r, self._row_sharding(self.mesh))

    def rmatvec(self, u):
        from repro.core.distributed import make_zt_matvec
        with self.mesh:
            return make_zt_matvec(self.mesh, self.idx, self.rowscale, self.d,
                                  self.d_g, self.impl,
                                  chunk_size=self.chunk_size)(u)

    def gram(self, u):
        # the cached closure hits shard_map's dispatch cache on repeat
        # applications; wrapping it in jax.jit would re-bake the sharded
        # idx/rowscale closures as constants (and can wedge the collective)
        with self.mesh:
            return self._gram_fn()(u)

    def map_row_chunks(self, fn, *tall):
        """Row-local map: GSPMD keeps it shard-local; the result is pinned
        back to the row sharding so downstream stages stay sharded."""
        with self.mesh:
            return jax.lax.with_sharding_constraint(
                fn(*tall), self._row_sharding(self.mesh))

    def reduce(self, fn, init, *tall):
        """Additive accumulator over row chunks: a within-shard lax.scan
        followed by a psum of the final accumulator (init must be the
        identity, e.g. zeros)."""
        from repro.core.distributed import make_sharded_reduce
        with self.mesh:
            return make_sharded_reduce(
                self.mesh, fn, chunk_size=self.chunk_size)(init, *tall)

    def degree_dual(self) -> np.ndarray:
        """Bin occupancies Zᵀ1, retained from the degree pass (no extra
        collective sweep) — only the (D,) dual leaves the mesh, never O(N)
        state. Falls back to one psum'd Ẑᵀ pass if not retained."""
        if self.counts is not None:
            return np.asarray(self.counts, np.float32)
        from repro.core.distributed import make_zt_matvec
        with self.mesh:
            ones_scale = jax.device_put(
                jnp.ones((self.n,), jnp.float32), self._vec_sharding(self.mesh))
            ones = jax.device_put(jnp.ones((self.n, 1), jnp.float32),
                                  self._row_sharding(self.mesh))
            counts = make_zt_matvec(self.mesh, self.idx, ones_scale, self.d,
                                    self.d_g, self.impl,
                                    chunk_size=self.chunk_size)(ones)
        return np.asarray(counts)[:, 0].astype(np.float32)

    def eigenpairs(self, k, key, cfg, x0=None) -> eigensolver.EigResult:
        so = cfg.solver_options
        precond = _solver_precond(cfg, self.deg)
        if so.solver in ("lobpcg", "lobpcg_host") and 3 * k <= self.n:
            from repro.core.distributed import make_gram_matvec
            b = eigensolver.lobpcg_block_width(self.n, k, so.buffer)

            # the ELL and row scales are arguments of the jitted solve: a
            # closure over them would embed the (N, R) ELL in the program
            def solve(xs, precond, idx, rowscale):
                matvec = make_gram_matvec(
                    self.mesh, idx, rowscale, self.d, self.d_g, self.impl,
                    compress=self.compress, chunk_size=self.chunk_size)
                return eigensolver.lobpcg(
                    matvec, xs, max_iters=so.iters, tol=so.tol,
                    precond=precond, stable_tol=so.stable_tol, stable_k=k,
                    conv_k=k)

            with self.mesh:
                if x0 is not None:
                    start = jnp.asarray(
                        eigensolver.prepare_start_block(x0, self.n, b, key))
                else:
                    start = jax.random.normal(key, (self.n, b), jnp.float32)
                x0s = jax.device_put(start, self._row_sharding(self.mesh))
                # the (N,) diagonal rides the row sharding
                tvec = None if precond is None else jax.device_put(
                    jnp.asarray(precond, jnp.float32),
                    self._vec_sharding(self.mesh))
                eig = jax.jit(solve)(x0s, tvec, self.idx, self.rowscale)
                u = jax.block_until_ready(eig.vectors[:, :k])
            return eigensolver.EigResult(eig.theta[:k], u, eig.resnorms[:k],
                                         eig.iterations)
        # lanczos / subspace (Fig. 3 study), randomized / auto (host-driven
        # meta-policy) and the n < 3k dense fallback: driven eagerly against
        # the shard_map'd Gram mat-vec — same collective schedule per
        # mat-vec; only the small Krylov/Ritz algebra differs.
        with self.mesh:
            eig = eigensolver.top_k_eigenpairs(
                self._gram_fn(), self.n, k, key, solver=so.solver,
                max_iters=so.iters, tol=so.tol,
                buffer=so.buffer, x0=x0, precond=precond,
                stable_tol=so.stable_tol)
            vectors = jax.block_until_ready(jax.device_put(
                eig.vectors, self._row_sharding(self.mesh)))
        return eigensolver.EigResult(eig.theta, vectors, eig.resnorms,
                                     eig.iterations)

    def cluster(self, key, u_hat, cfg) -> Tuple[Any, dict]:
        from repro.core.distributed import distributed_kmeans
        res, diag = distributed_kmeans(
            key, u_hat, cfg.n_clusters, self.mesh,
            n_iters=cfg.kmeans_iters, n_replicates=cfg.kmeans_replicates,
            impl=cfg.impl, chunk_size=self.chunk_size)
        return res, diag

    def residency_diagnostics(self, cfg) -> dict:
        shard_rows = -(-self.n // self.n_shards)
        chunk = min(self.chunk_size or shard_rows, shard_rows)
        return {
            "n_shards": self.n_shards,
            "shard_rows": shard_rows,
            # per-device temporary working set of a within-shard ELL sweep
            "ell_device_bytes_peak": chunk * self.idx.shape[1] * 4,
        }


# --------------------------------------------------------------------------
# Partitioned placement — the divide-and-conquer fit's aggregate handle.
# --------------------------------------------------------------------------

@dataclasses.dataclass
class PartitionedRows:
    """Union of per-partition representations (``placement="partitioned"``).

    Each partition's sub-fit built its own ``DeviceRows`` /
    ``HostChunkedRows`` under one shared fitted feature map, so all
    partitions live in a single feature space; this container is what the
    merge in ``repro.core.partitioned`` hands to ``SCRBModel.fit`` as the
    run's ``state["z"]``. It exposes the aggregate protocol surface the
    model/merge path needs — the summed degree dual, degree ranges and
    residency diagnostics — not the solver-facing mat-vec surface (the
    whole point of the partitioned fit is that no global solve happens).
    """

    kind = "partitioned"
    parts: Tuple[Any, ...]        # per-partition RowMatrix representations
    fmap: Any                     # the shared fitted feature map
    dual: np.ndarray              # (D,) summed Zᵀ1 across partitions

    @property
    def n(self) -> int:
        return sum(p.n for p in self.parts)

    @property
    def n_partitions(self) -> int:
        return len(self.parts)

    def degree_range(self) -> Tuple[float, float]:
        """Within-partition degree range (each partition normalizes against
        its own degrees — that is the divide-and-conquer approximation)."""
        ranges = [p.degree_range() for p in self.parts]
        return min(r[0] for r in ranges), max(r[1] for r in ranges)

    def degree_dual(self) -> np.ndarray:
        return self.dual

    def residency_diagnostics(self, cfg) -> dict:
        """Aggregate of the per-partition residency diagnostics: peak byte
        counts are max'd (partitions share the device sequentially or run on
        distinct devices), chunk counts are summed."""
        out = {"n_partitions": self.n_partitions}
        for diag in (p.residency_diagnostics(cfg) for p in self.parts):
            for key, val in diag.items():
                if key == "n_chunks":
                    out[key] = out.get(key, 0) + val
                elif isinstance(val, (int, float)) and not isinstance(val, bool):
                    out[key] = max(out.get(key, 0), val)
                else:
                    out[key] = val
        return out
