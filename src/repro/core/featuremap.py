"""FeatureMap — the pluggable stage-1 of the executor and of fitted models.

The paper's observation is that every sampling-based spectral-clustering
method is an instance of one pipeline: *feature map → (degree-normalize) →
embed → k-means* (Tremblay & Loukas, "Approximating Spectral Clustering via
Sampling"). This module makes that literal: a ``FeatureMap`` produces a
row-local feature representation Φ with Φ Φᵀ ≈ W, and everything downstream
(degrees, the eigensolve, the out-of-sample extension) is written against
the map, not against Random Binning specifically.

Protocol (all maps are frozen dataclasses registered as pytrees, so a
*fitted* map can be passed straight into ``jax.jit``):

  ``fit(key, x) -> fitted map``   draw/select the map's parameters; ``x``
                                  may be an array OR a sequence of host row
                                  chunks (the streaming input format) — fits
                                  never concatenate chunked data.
  ``transform(x) -> features``    row-local, jit-able. ``kind == "ell"``
                                  maps emit int32 ELL column indices (N, R);
                                  ``kind == "dense"`` maps emit float32
                                  feature matrices (N, m).
  ``n_features``                  total feature columns D.

plus the out-of-sample projection used by ``repro.core.model.SCRBModel``,
``oos_project`` (Ẑ_new · M: a *new* point's features, normalized by its
degree against the fitted training graph, read from the O(D) degree dual,
times the fitted (D, K) projection M).

Registered implementations (``FEATURE_MAPS``):

  rb       — Random Binning (Alg. 1, hashed ELL)        this paper
  rff      — Random Fourier Features                    SC_RF / SV_RF / KK_RF
  nystrom  — landmark Nyström features                  SC_Nys / KK_RS
  lsc      — bipartite s-NN anchor affinities           SC_LSC

``repro.core.baselines`` builds the paper's comparison methods as thin
``ExecutionPlan(feature_map=...)`` configurations over this registry.

The dense operand classes at the bottom (``NormalizedDenseFeatures``,
``ChunkedDenseFeatures``) are the dense analogues of
``graph.NormalizedAdjacency`` / ``streaming.ChunkedELL`` — same mat-vec
surface, so ``rowmatrix.DeviceRows`` / ``HostChunkedRows`` carry either.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Protocol, Sequence, Tuple, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import rb, rff, streaming
from repro.core.nystrom import pairwise_kernel
from repro.kernels import ops
from repro.utils import fold_key, prefetch_to_device


@runtime_checkable
class FeatureMap(Protocol):
    """A row-local feature generator with ΦΦᵀ ≈ W and an O(D) fitted state."""

    name: str
    kind: str       # "ell" | "dense"

    def fit(self, key: jax.Array, x) -> "FeatureMap": ...
    def transform(self, x: jax.Array) -> jax.Array: ...
    @property
    def n_features(self) -> int: ...
    # out-of-sample extension (jit-able; ``dual`` is the fitted degree dual)
    def oos_project(self, feats: jax.Array, dual: jax.Array, m: jax.Array,
                    *, laplacian: bool) -> jax.Array: ...


def _chunk_list(x) -> list:
    return list(x) if isinstance(x, (list, tuple)) else [x]


def _data_dim(x) -> int:
    return int(_chunk_list(x)[0].shape[1])


def _seed_from_key(key: jax.Array, *names: str) -> int:
    return int(jax.random.randint(fold_key(key, *names), (), 0, 2**31 - 1))


# --------------------------------------------------------------------------
# Random Binning (ELL) — the paper's map; stage-1 of SC_RB.
# --------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class RBMap:
    """Random Binning features (Alg. 1): hashed ELL indices, D = R·d_g."""

    name = "rb"
    kind = "ell"
    n_grids: int
    sigma: float
    d_g: Optional[int] = None     # None → auto-size at fit from the data
    impl: str = "auto"
    params: Optional[rb.RBParams] = None

    def fit(self, key: jax.Array, x) -> "RBMap":
        # Identical key folding to the pre-protocol pipeline, so fitted-map
        # runs stay bit-identical to the seed single-shot path.
        if self.params is not None:
            return self       # already fitted (shared across partitioned fits)
        d_g = self.d_g or rb.suggest_d_g(x, self.sigma,
                                         key=fold_key(key, "probe"))
        params = rb.make_rb_params(fold_key(key, "rb"), self.n_grids,
                                   _data_dim(x), self.sigma, d_g)
        return dataclasses.replace(self, d_g=d_g, params=params)

    def transform(self, x: jax.Array) -> jax.Array:
        return rb.rb_transform(x, self.params, impl=self.impl)

    @property
    def n_features(self) -> int:
        return self.params.n_features

    def oos_project(self, feats: jax.Array, dual: jax.Array, m: jax.Array,
                    *, laplacian: bool) -> jax.Array:
        """Ẑ_new · M (N, K) for ELL rows ``feats``.

        Under Laplacian normalization each row is scaled by 1/√(R·deg(x)),
        with deg(x) = (1/R) Σ_g dual[idx_g] — the fitted bin occupancies at
        the new point's bins (Eq. 6, one-sided). That is the projection's
        own sum over the same bins with ``dual`` in place of a column of M,
        so it rides in the projection's gather as one more column
        (``_sums_and_degrees``) instead of a gather of its own. Without
        normalization every row is scaled by the constant 1/√R.
        """
        r = self.n_grids
        if not laplacian:
            scale = jnp.full((feats.shape[0],), 1.0 / jnp.sqrt(jnp.float32(r)))
            return ops.z_matmul(feats, m, scale, d_g=self.d_g, impl=self.impl)
        sums, deg = self._sums_and_degrees(feats, dual, m)
        rowscale = 1.0 / jnp.sqrt(r * jnp.maximum(deg, 1e-8))
        return sums * rowscale[:, None]

    def _sums_and_degrees(self, feats, dual, m):
        """(Σ_g M[idx_g] (N, K), deg (N,)) from one ``z_matmul`` over
        [M | dual]. ``dual`` holds integer counts, and the degree column's
        partial sums are integers below 2^24, so deg is exact on every
        route, as ``graph.degrees_from_counts`` is."""
        mv = jnp.concatenate([m, dual[:, None].astype(m.dtype)], axis=1)
        unit = jnp.ones((feats.shape[0],), jnp.float32)
        s = ops.z_matmul(feats, mv, unit, d_g=self.d_g, impl=self.impl)
        return s[:, :-1], s[:, -1] / self.n_grids

    # -- (de)serialization / pytree ----------------------------------------
    def meta_dict(self) -> dict:
        return {"name": self.name, "n_grids": self.n_grids,
                "sigma": self.sigma, "d_g": self.d_g, "impl": self.impl}

    def state_dict(self) -> dict:
        p = self.params
        return {"widths": np.asarray(p.widths), "biases": np.asarray(p.biases),
                "hash_a": np.asarray(p.hash_a), "hash_c": np.asarray(p.hash_c)}

    @classmethod
    def from_state(cls, meta: dict, arrays: dict) -> "RBMap":
        params = rb.RBParams(
            jnp.asarray(arrays["widths"]), jnp.asarray(arrays["biases"]),
            jnp.asarray(arrays["hash_a"]), jnp.asarray(arrays["hash_c"]),
            d_g=int(meta["d_g"]))
        return cls(n_grids=int(meta["n_grids"]), sigma=float(meta["sigma"]),
                   d_g=int(meta["d_g"]), impl=meta["impl"], params=params)

    def tree_flatten(self):
        return (self.params,), (self.n_grids, self.sigma, self.d_g, self.impl)

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        n_grids, sigma, d_g, impl = aux
        return cls(n_grids=n_grids, sigma=sigma, d_g=d_g, impl=impl,
                   params=leaves[0])


# --------------------------------------------------------------------------
# Dense maps share the (N, m) float32 out-of-sample algebra.
# --------------------------------------------------------------------------

class _DenseOOS:
    kind = "dense"

    def oos_project(self, feats: jax.Array, dual: jax.Array, m: jax.Array,
                    *, laplacian: bool) -> jax.Array:
        """(D̂^{-1/2} Φ_new) · M, with deg(x) = φ(x) · (Φᵀ1) — the kernel
        degree of a new point against the training rows."""
        deg = feats @ dual
        rowscale = 1.0 / jnp.sqrt(jnp.maximum(deg, 1e-8)) if laplacian \
            else jnp.ones_like(deg)
        return (feats * rowscale[:, None]) @ m


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class RFFMap(_DenseOOS):
    """Random Fourier Features — the RF baseline family's map."""

    name = "rff"
    rank: int
    sigma: float
    kernel: str = "laplacian"
    params: Optional[rff.RFFParams] = None

    def fit(self, key: jax.Array, x) -> "RFFMap":
        if self.params is not None:
            return self       # already fitted (shared across partitioned fits)
        params = rff.make_rff_params(fold_key(key, "rff"), self.rank,
                                     _data_dim(x), self.sigma,
                                     kernel=self.kernel)
        return dataclasses.replace(self, params=params)

    def transform(self, x: jax.Array) -> jax.Array:
        return rff.rff_transform(x, self.params)

    @property
    def n_features(self) -> int:
        return self.params.n_features

    def meta_dict(self) -> dict:
        return {"name": self.name, "rank": self.rank, "sigma": self.sigma,
                "kernel": self.kernel}

    def state_dict(self) -> dict:
        return {"w": np.asarray(self.params.w), "b": np.asarray(self.params.b)}

    @classmethod
    def from_state(cls, meta: dict, arrays: dict) -> "RFFMap":
        params = rff.RFFParams(jnp.asarray(arrays["w"]),
                               jnp.asarray(arrays["b"]))
        return cls(rank=int(meta["rank"]), sigma=float(meta["sigma"]),
                   kernel=meta["kernel"], params=params)

    def tree_flatten(self):
        return (self.params,), (self.rank, self.sigma, self.kernel)

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        rank, sigma, kernel = aux
        return cls(rank=rank, sigma=sigma, kernel=kernel, params=leaves[0])


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class NystromMap(_DenseOOS):
    """Nyström landmark features Φ = K_nm·K_mm^{-1/2} (SC_Nys / KK_RS).

    ``fit`` samples landmarks uniformly (chunk-aware — rows are gathered by
    global index, never concatenating a chunked input) and whitens K_mm;
    ``transform`` is then row-local: kernel block against the landmarks
    times the fitted (m, m) whitener — the standard Nyström out-of-sample
    extension (Pourkamali-Anaraki).
    """

    name = "nystrom"
    rank: int
    sigma: float
    kernel: str = "laplacian"
    landmarks: Optional[jax.Array] = None    # (m, d)
    whiten: Optional[jax.Array] = None       # (m, m) = V Λ^{-1/2} Vᵀ

    def fit(self, key: jax.Array, x, eps: float = 1e-6) -> "NystromMap":
        if self.landmarks is not None:
            return self       # already fitted (shared across partitioned fits)
        chunks = _chunk_list(x)
        n = sum(int(c.shape[0]) for c in chunks)
        m = max(1, min(self.rank, n // 2))
        lm = rb._gather_sample(chunks, m, seed=_seed_from_key(key, "nystrom"))
        lm = jnp.asarray(lm, jnp.float32)
        k_mm = pairwise_kernel(lm, lm, self.sigma, self.kernel)
        lam, v = jnp.linalg.eigh(k_mm)
        inv_sqrt = jnp.where(lam > eps,
                             1.0 / jnp.sqrt(jnp.maximum(lam, eps)), 0.0)
        whiten = (v * inv_sqrt[None, :]) @ v.T
        return dataclasses.replace(self, landmarks=lm, whiten=whiten)

    def transform(self, x: jax.Array) -> jax.Array:
        return pairwise_kernel(x, self.landmarks, self.sigma,
                                self.kernel) @ self.whiten

    @property
    def n_features(self) -> int:
        return self.landmarks.shape[0]

    def meta_dict(self) -> dict:
        return {"name": self.name, "rank": self.rank, "sigma": self.sigma,
                "kernel": self.kernel}

    def state_dict(self) -> dict:
        return {"landmarks": np.asarray(self.landmarks),
                "whiten": np.asarray(self.whiten)}

    @classmethod
    def from_state(cls, meta: dict, arrays: dict) -> "NystromMap":
        return cls(rank=int(meta["rank"]), sigma=float(meta["sigma"]),
                   kernel=meta["kernel"],
                   landmarks=jnp.asarray(arrays["landmarks"]),
                   whiten=jnp.asarray(arrays["whiten"]))

    def tree_flatten(self):
        return ((self.landmarks, self.whiten),
                (self.rank, self.sigma, self.kernel))

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        rank, sigma, kernel = aux
        return cls(rank=rank, sigma=sigma, kernel=kernel,
                   landmarks=leaves[0], whiten=leaves[1])


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class LSCMap(_DenseOOS):
    """LSC bipartite affinities: s nearest anchors, row-stochastic (SC_LSC).

    ``fit`` picks anchors by a few numpy Lloyd refinements over a uniform
    row sample (chunk-aware); ``transform`` keeps the s largest kernel
    affinities per row and row-normalizes — row-local, so the same code is
    the out-of-sample extension.
    """

    name = "lsc"
    rank: int
    sigma: float
    kernel: str = "laplacian"
    n_nearest: int = 5
    anchors: Optional[jax.Array] = None      # (p, d)

    def fit(self, key: jax.Array, x, n_refine: int = 3,
            max_sample: int = 8192) -> "LSCMap":
        if self.anchors is not None:
            return self       # already fitted (shared across partitioned fits)
        chunks = _chunk_list(x)
        n = sum(int(c.shape[0]) for c in chunks)
        p = max(1, min(self.rank, n // 2))
        seed = _seed_from_key(key, "lsc")
        sample = np.asarray(
            rb._gather_sample(chunks, min(n, max(max_sample, 4 * p)),
                              seed=seed), np.float64)
        rng = np.random.default_rng(seed)
        anchors = sample[rng.choice(sample.shape[0], p, replace=False)]
        for _ in range(n_refine):
            d2 = ((sample[:, None, :] - anchors[None, :, :]) ** 2).sum(-1)
            lab = np.argmin(d2, -1)
            for c in range(p):
                sel = lab == c
                if np.any(sel):
                    anchors[c] = sample[sel].mean(0)
        return dataclasses.replace(
            self, anchors=jnp.asarray(anchors, jnp.float32))

    def transform(self, x: jax.Array) -> jax.Array:
        aff = pairwise_kernel(x, self.anchors, self.sigma, self.kernel)
        s = min(self.n_nearest, self.anchors.shape[0])
        thresh = jax.lax.top_k(aff, s)[0][:, -1]
        kept = jnp.where(aff >= thresh[:, None], aff, 0.0)
        return kept / jnp.maximum(jnp.sum(kept, -1, keepdims=True), 1e-12)

    @property
    def n_features(self) -> int:
        return self.anchors.shape[0]

    def meta_dict(self) -> dict:
        return {"name": self.name, "rank": self.rank, "sigma": self.sigma,
                "kernel": self.kernel, "n_nearest": self.n_nearest}

    def state_dict(self) -> dict:
        return {"anchors": np.asarray(self.anchors)}

    @classmethod
    def from_state(cls, meta: dict, arrays: dict) -> "LSCMap":
        return cls(rank=int(meta["rank"]), sigma=float(meta["sigma"]),
                   kernel=meta["kernel"], n_nearest=int(meta["n_nearest"]),
                   anchors=jnp.asarray(arrays["anchors"]))

    def tree_flatten(self):
        return ((self.anchors,),
                (self.rank, self.sigma, self.kernel, self.n_nearest))

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        rank, sigma, kernel, n_nearest = aux
        return cls(rank=rank, sigma=sigma, kernel=kernel,
                   n_nearest=n_nearest, anchors=leaves[0])


# --------------------------------------------------------------------------
# Registry
# --------------------------------------------------------------------------

FEATURE_MAPS = {
    "rb": RBMap,
    "rff": RFFMap,
    "nystrom": NystromMap,
    "lsc": LSCMap,
}


def make_feature_map(name: str, *, rank: int, sigma: float,
                     kernel: str = "laplacian", **kwargs) -> FeatureMap:
    """Build an unfitted feature map from the registry by name."""
    if name not in FEATURE_MAPS:
        raise ValueError(
            f"unknown feature map {name!r}; options {sorted(FEATURE_MAPS)}")
    if name == "rb":
        return RBMap(n_grids=rank, sigma=sigma, **kwargs)
    return FEATURE_MAPS[name](rank=rank, sigma=sigma, kernel=kernel, **kwargs)


def from_config(cfg, impl: str = "auto") -> RBMap:
    """The default stage-1 map of an ``SCRBConfig``: Random Binning."""
    return RBMap(n_grids=cfg.n_grids, sigma=cfg.sigma, d_g=cfg.d_g, impl=impl)


def fused_degree(fm, *, laplacian: bool) -> bool:
    """Whether ``fm.oos_project`` reads each row's degree from the
    projection's own gather: ELL maps under Laplacian normalization."""
    return laplacian and fm.kind == "ell"


def load_fitted(meta: dict, arrays: dict) -> FeatureMap:
    return FEATURE_MAPS[meta["name"]].from_state(meta, arrays)


# --------------------------------------------------------------------------
# Dense operands — the (N, m) analogues of NormalizedAdjacency / ChunkedELL,
# so the executor representations carry dense maps through the same stages.
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class NormalizedDenseFeatures:
    """Ẑ = D̂^{-1/2} Φ for a dense feature matrix, applied implicitly."""

    phi: jax.Array        # (N, m) float32
    rowscale: jax.Array   # (N,)
    deg: jax.Array        # (N,) kernel degrees (diagnostics + model dual)
    colsum: jax.Array     # (m,) = Φᵀ1 — the degree dual

    @property
    def n(self) -> int:
        return self.phi.shape[0]

    @property
    def width(self) -> int:
        return self.phi.shape[1]

    def rmatmat(self, u: jax.Array) -> jax.Array:
        return self.phi.T @ (u * self.rowscale[:, None])

    def matmat(self, v: jax.Array) -> jax.Array:
        return (self.phi @ v) * self.rowscale[:, None]

    def gram_matvec(self, u: jax.Array) -> jax.Array:
        return self.matmat(self.rmatmat(u))


def build_normalized_dense(phi: jax.Array, *, laplacian: bool = True,
                           eps: float = 1e-8) -> NormalizedDenseFeatures:
    phi = jnp.asarray(phi, jnp.float32)
    colsum = jnp.sum(phi, axis=0)
    deg = phi @ colsum
    if laplacian:
        rowscale = 1.0 / jnp.sqrt(jnp.maximum(deg, eps))
    else:
        rowscale = jnp.ones_like(deg)
    return NormalizedDenseFeatures(phi, rowscale, deg, colsum)


@dataclasses.dataclass(frozen=True)
class ChunkedDenseFeatures:
    """Host-chunked Ẑ = D̂^{-1/2} Φ — the dense twin of ``ChunkedELL``.

    Same streaming surface (prefetched chunk sweeps, one (m, K) accumulator
    for Ẑᵀ products, ``gram_matvec_chunked`` for the chunked LOBPCG), so
    ``rowmatrix.HostChunkedRows`` carries either storage unchanged.
    """

    phi_chunks: Tuple[np.ndarray, ...]       # each (rows_c, m) float32, host
    rowscale_chunks: Tuple[np.ndarray, ...]  # each (rows_c,) float32, host
    colsum: np.ndarray                       # (m,) = Φᵀ1 — the degree dual
    deg: np.ndarray                          # (N,)
    prefetch: bool = True
    h2d_stats: dict = dataclasses.field(default_factory=dict, compare=False)

    @property
    def n(self) -> int:
        return sum(c.shape[0] for c in self.phi_chunks)

    @property
    def width(self) -> int:
        return self.phi_chunks[0].shape[1]

    @property
    def n_chunks(self) -> int:
        return len(self.phi_chunks)

    @property
    def chunk_sizes(self) -> Tuple[int, ...]:
        return tuple(c.shape[0] for c in self.phi_chunks)

    @property
    def max_chunk_rows(self) -> int:
        return max(c.shape[0] for c in self.phi_chunks)

    @property
    def ell_device_bytes_peak(self) -> int:
        """Peak device residency of the feature matrix: one buffered chunk
        (same accounting as ``ChunkedELL`` so diagnostics stay uniform)."""
        return self.max_chunk_rows * self.width * 4

    def _stream(self, *extra_chunk_seqs):
        return prefetch_to_device(
            zip(self.phi_chunks, self.rowscale_chunks, *extra_chunk_seqs),
            enabled=self.prefetch, measure=self.h2d_stats)

    def rmatmat(self, u: jax.Array) -> jax.Array:
        q = jnp.zeros((self.width, u.shape[1]), jnp.float32)
        offsets = np.concatenate([[0], np.cumsum(self.chunk_sizes)])
        u_rows = (u[offsets[i]:offsets[i + 1]] for i in range(self.n_chunks))
        for pc, sc, uc in self._stream(u_rows):
            q = q + pc.T @ (uc * sc[:, None])
        return q

    def rmatmat_chunked(self, u: streaming.ChunkedDense) -> jax.Array:
        self._check_alignment(u)
        q = jnp.zeros((self.width, u.k), jnp.float32)
        for pc, sc, uc in self._stream(u.chunks):
            q = q + pc.T @ (uc * sc[:, None])
        return q

    def matmat(self, v: jax.Array) -> jax.Array:
        outs = [(pc @ v) * sc[:, None] for pc, sc in self._stream()]
        return jnp.concatenate(outs, axis=0)

    def matmat_chunked(self, v: jax.Array) -> streaming.ChunkedDense:
        """Ẑ v with host-chunked output (tall result never lives whole on
        device) — same surface as ``ChunkedELL.matmat_chunked``."""
        outs = [np.asarray((pc @ v) * sc[:, None])
                for pc, sc in self._stream()]
        return streaming.ChunkedDense(tuple(outs))

    def gram_matvec(self, u: jax.Array) -> jax.Array:
        return self.matmat(self.rmatmat(u))

    def _check_alignment(self, u: streaming.ChunkedDense):
        if u.chunk_sizes != self.chunk_sizes:
            raise ValueError(
                f"chunking mismatch: u has {u.chunk_sizes}, "
                f"features have {self.chunk_sizes}")

    def gram_matvec_chunked(
        self, u: streaming.ChunkedDense
    ) -> streaming.ChunkedDense:
        q = self.rmatmat_chunked(u)
        outs = [np.asarray((pc @ q) * sc[:, None])
                for pc, sc in self._stream()]
        return streaming.ChunkedDense(tuple(outs))


def build_chunked_dense(
    phi_chunks: Sequence[np.ndarray], *, laplacian: bool = True,
    prefetch: bool = True, eps: float = 1e-8,
) -> ChunkedDenseFeatures:
    """Two-pass streaming build: colsum accumulation, then row-local degrees
    (the dense analogue of ``streaming.build_chunked_adjacency``)."""
    phi_chunks = tuple(np.asarray(c, np.float32) for c in phi_chunks)
    h2d_stats: dict = {}
    colsum = jnp.zeros((phi_chunks[0].shape[1],), jnp.float32)
    for pc in prefetch_to_device(phi_chunks, enabled=prefetch,
                                 measure=h2d_stats):
        colsum = colsum + jnp.sum(pc, axis=0)
    deg_chunks, scale_chunks = [], []
    for pc in prefetch_to_device(phi_chunks, enabled=prefetch,
                                 measure=h2d_stats):
        deg_c = np.asarray(pc @ colsum)
        deg_chunks.append(deg_c)
        if laplacian:
            scale_chunks.append(
                (1.0 / np.sqrt(np.maximum(deg_c, eps))).astype(np.float32))
        else:
            scale_chunks.append(np.ones_like(deg_c, np.float32))
    return ChunkedDenseFeatures(
        phi_chunks, tuple(scale_chunks), colsum=np.asarray(colsum),
        deg=np.concatenate(deg_chunks), prefetch=prefetch,
        h2d_stats=h2d_stats)
