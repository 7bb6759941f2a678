"""Compressive spectral clustering — the ``solver="compressive"`` plan cell.

Every eigensolver in ``repro.core.eigensolver`` iterates a dense (N, K)
block — the last O(N·K) object in the fit path. Compressive SC (Tremblay,
Puy, Gribonval & Vandergheynst, ICML 2016) removes the eigendecomposition
entirely:

  1. **λ_K estimation by eigencount dichotomy** — one Chebyshev moment
     sweep against a small Rademacher probe block prices the Jackson-damped
     eigencount ``tr h_t(Â)`` at *every* threshold t (the count is a dot
     product of damped step coefficients with the cached moments), so the
     dichotomy locating λ_K / λ_{K+1} is free host arithmetic.
  2. **Jackson–Chebyshev filtering** — d = O(log K) random signals R are
     pushed through h(Â) ≈ the spectral projector onto span(U_K), where h
     is a damped degree-m Chebyshev step at the mid-gap cutoff. Each
     recurrence step is one Gram mat-vec ``(ẐẐᵀ)u`` — the exact operator
     the device / host_chunked / mesh representations already share — so
     the filter is chunk-streamable and psum-compatible for free.
  3. **Random-subset k-means** — centroids are located on an O(n_sub · d)
     row sample of the row-normalized filtered signals; the remaining rows
     get one nearest-centroid chunk sweep.
  4. **Out-of-sample factorization** — the filtered block is re-expressed
     through the feature space as E = Ẑ q with q = Ẑᵀ h(Â) R (a (D, d)
     matrix), so ``SCRBModel``'s Nyström-style serving path reproduces the
     in-sample embedding exactly: project-new-rows-onto-q IS the fit-time
     embedding rule, and ``predict`` on training rows returns fit labels.

The working set is the d-wide tall block (native type per representation:
``jax.Array``, ``streaming.ChunkedDense``, or a row-sharded array) — no
(N, K + buffer) LOBPCG iterate, no (N,) device vector, anywhere.

Requires ``laplacian_normalize=True``: the filter maps spec(Â) ⊂ [0, 1]
(λ_max = 1 under the degree normalization) onto [-1, 1] via y = 2λ − 1.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import streaming
from repro.core.kmeans import KMeansResult, kmeans as _kmeans
from repro.kernels import ops
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.utils import fold_key

_SOLVES_TOTAL = obs_metrics.REGISTRY.counter(
    "repro_eigensolves_total", "Completed top-k eigensolves.", ("solver",))
_SOLVER_ITERS = obs_metrics.REGISTRY.histogram(
    "repro_solver_iterations", "Block mat-vec iterations per eigensolve.",
    ("solver",), buckets=obs_metrics.log_buckets(1.0, 1e4))
_SOLVER_RESNORM = obs_metrics.REGISTRY.gauge(
    "repro_solver_resnorm_max", "Worst top-k residual of the last eigensolve.",
    ("solver",))

SOLVER_NAME = "compressive"

COUNT_DEGREE = 40    # Chebyshev degree of the eigencount moment sweep
# Rademacher probes behind the trace estimates. The Hutchinson error on the
# small plateau counts (≈ K) is systematic across thresholds for a given
# probe draw — the moments are shared — so the only lever against a
# mis-bracketed λ_K is probe count, not grid resolution; 32 keeps the
# plateau within ±½ w.h.p. while the sweep stays one (N, probes) block.
COUNT_PROBES = 32

# feature-space round trips after the filter (q, E, and the Ritz/residual
# Grams) — charged to the reported iteration count as Gram-equivalents
_PROJECTION_SWEEPS = 3


# ---------------------------------------------------------------------------
# Jackson-damped Chebyshev step filters
# ---------------------------------------------------------------------------

def jackson_damping(degree: int) -> np.ndarray:
    """Jackson smoothing factors g_0..g_degree (g_0 = 1, g_degree ≈ 0) —
    the damping that turns the truncated Chebyshev step into a monotone
    transition with no Gibbs overshoot (Weiße et al., KPM)."""
    mp1 = degree + 1
    j = np.arange(degree + 1, dtype=np.float64)
    alpha = np.pi / mp1
    return ((mp1 - j) * np.cos(j * alpha)
            + np.sin(j * alpha) / np.tan(alpha)) / mp1


def step_coeffs(cutoff: float, degree: int, *, damped: bool = True
                ) -> np.ndarray:
    """Chebyshev coefficients of the spectral step ``1{λ ≥ cutoff}`` for
    λ ∈ [0, 1], expanded in T_j(y) with y = 2λ − 1 (Jackson-damped by
    default). ``step_eval(coeffs, λ)`` evaluates the resulting filter."""
    a = float(np.clip(2.0 * cutoff - 1.0, -1.0, 1.0))
    th = float(np.arccos(a))
    j = np.arange(1, degree + 1, dtype=np.float64)
    c = np.empty(degree + 1, np.float64)
    c[0] = th / np.pi
    c[1:] = 2.0 * np.sin(j * th) / (np.pi * j)
    if damped:
        c = c * jackson_damping(degree)
    return c


def step_eval(coeffs: np.ndarray, lam) -> np.ndarray:
    """The filter's scalar response h(λ) (tests compare it against the
    exact indicator)."""
    y = 2.0 * np.asarray(lam, np.float64) - 1.0
    return np.polynomial.chebyshev.chebval(y, coeffs)


# ---------------------------------------------------------------------------
# representation-generic tall-block algebra
# ---------------------------------------------------------------------------

def _tall_scale(a: float, x):
    if isinstance(x, streaming.ChunkedDense):
        return streaming.ChunkedDense(
            tuple(np.asarray(a * c, np.float32) for c in x.chunks))
    return a * x


def _tall_axpby(a: float, x, b: float, y):
    """a·x + b·y on native tall operands (host chunks stay host-resident)."""
    if isinstance(x, streaming.ChunkedDense):
        return streaming.ChunkedDense(tuple(
            np.asarray(a * cx + b * cy, np.float32)
            for cx, cy in zip(x.chunks, y.chunks)))
    return a * x + b * y


def _tall_inner(x, y) -> float:
    """Σ_ij x_ij·y_ij over the whole tall block — host float64 accumulation
    for chunked operands, one replicated scalar on device/mesh."""
    if isinstance(x, streaming.ChunkedDense):
        return float(sum(np.vdot(cx.astype(np.float64), cy)
                         for cx, cy in zip(x.chunks, y.chunks)))
    return float(jnp.vdot(x, y))


# ---------------------------------------------------------------------------
# the Chebyshev recurrence (shared by the moment sweep and the filter)
# ---------------------------------------------------------------------------

def chebyshev_sweep(z, r, degree: int, *, coeffs: Optional[np.ndarray] = None,
                    moments: bool = False):
    """Three-term recurrence of T_j(2Â − I) against a native tall block,
    driven by the representation's shared Gram mat-vec ``z.gram``.

    Returns ``(filtered, mu, matvecs)``: ``filtered = Σ_j coeffs[j]·T_j r``
    when ``coeffs`` is given, ``mu[j] = ⟨r, T_j r⟩`` (summed over probe
    columns) when ``moments``. Exactly ``degree`` Gram mat-vecs; the only
    live state is three tall blocks regardless of the degree.
    """
    acc = _tall_scale(float(coeffs[0]), r) if coeffs is not None else None
    mu = np.zeros(degree + 1, np.float64) if moments else None
    if moments:
        mu[0] = _tall_inner(r, r)
    if degree == 0:
        return acc, mu, 0
    t_prev, t_cur = r, _tall_axpby(2.0, z.gram(r), -1.0, r)   # T_0 r, T_1 r
    nmv = 1
    for j in range(1, degree + 1):
        if coeffs is not None:
            acc = _tall_axpby(1.0, acc, float(coeffs[j]), t_cur)
        if moments:
            mu[j] = _tall_inner(r, t_cur)
        if j < degree:
            # T_{j+1} = 2(2Â − I)T_j − T_{j-1}
            nxt = _tall_axpby(4.0, z.gram(t_cur), -2.0, t_cur)
            t_prev, t_cur = t_cur, _tall_axpby(1.0, nxt, -1.0, t_prev)
            nmv += 1
    return acc, mu, nmv


# ---------------------------------------------------------------------------
# λ_K estimation — eigencount dichotomy over cached moments
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LambdaEstimate:
    lambda_k: float        # smoothed-count crossing of K − 1/2 (≈ λ_K)
    lambda_k1: float       # smoothed-count crossing of K + 1/2 (≈ λ_{K+1})
    cutoff: float          # mid-gap filter threshold
    moments: np.ndarray    # (degree+1,) raw probe moments ⟨r, T_j r⟩
    probes: int
    degree: int


def eigencount(moments: np.ndarray, probes: int, cutoff: float) -> float:
    """Jackson-damped estimate of #{λ_i(Â) ≥ cutoff} from cached moments —
    free host arithmetic per threshold query."""
    c = step_coeffs(cutoff, len(moments) - 1)
    return float(c @ moments) / probes


def _bisect_count(moments, probes, target: float, *, iters: int = 48) -> float:
    """Largest threshold whose smoothed eigencount still reaches ``target``
    (the count is decreasing in the threshold)."""
    lo, hi = 0.0, 1.0
    for _ in range(iters):
        mid = 0.5 * (lo + hi)
        if eigencount(moments, probes, mid) >= target:
            lo = mid
        else:
            hi = mid
    return 0.5 * (lo + hi)


def estimate_lambda_k(z, k: int, key, *, probes: int = COUNT_PROBES,
                      degree: int = COUNT_DEGREE
                      ) -> Tuple[LambdaEstimate, int]:
    """λ_K / λ_{K+1} by eigencount dichotomy using polynomial-filter traces.

    One moment sweep (``degree`` Gram mat-vecs against a ``probes``-wide
    Rademacher block) prices every threshold: the damped step is ≈ 1/2 at
    its own cutoff, so the smoothed count crosses K − 1/2 near λ_K and
    K + 1/2 near λ_{K+1}; the filter cutoff is their midpoint. Clustered or
    degenerate spectra collapse the two estimates toward each other — the
    midpoint stays inside (or at) the eigenvalue they share.
    """
    r = z.random_tall(key, probes, dist="rademacher")
    _, mu, nmv = chebyshev_sweep(z, r, degree, moments=True)
    lam_k = _bisect_count(mu, probes, k - 0.5)
    lam_k1 = _bisect_count(mu, probes, k + 0.5)
    est = LambdaEstimate(lambda_k=lam_k, lambda_k1=lam_k1,
                         cutoff=0.5 * (lam_k + lam_k1), moments=mu,
                         probes=probes, degree=degree)
    return est, nmv


def default_filter_degree(est: LambdaEstimate) -> int:
    """Filter degree from the estimated spectral gap: the Jackson
    transition width is O(1/m) in λ-units, so m ≈ 3/gap puts the
    pass-to-stop transition inside the gap (clamped to keep the mat-vec
    budget bounded on degenerate spectra)."""
    gap = max(est.lambda_k - est.lambda_k1, 1e-3)
    return int(np.clip(np.ceil(3.0 / gap), 24, 96))


def default_signals(k: int) -> int:
    """d = O(log K) filtered random signals (Tremblay et al. Thm. 3-style
    dimension: enough to preserve the K-cluster geometry w.h.p.)."""
    return int(max(4, np.ceil(4.0 * np.log2(k + 1))))


def default_subset(n: int, k: int) -> int:
    """Rows sampled for the compressive k-means: O(K log K) with a healthy
    constant, capped at N."""
    return int(min(n, max(64, 32 * k * max(1, int(np.ceil(np.log2(k + 1)))))))


# ---------------------------------------------------------------------------
# the embedding: filter d signals, factor through the feature space
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class CompressiveEmbedding:
    embedding: Any          # native tall (N, d) = Ẑ q, pre-row-normalize
    proj: np.ndarray        # (D, d) q = Ẑᵀ h(Â) R — the serving projection
    theta: np.ndarray       # (d,) Ritz values of Â on span(embedding), desc
    resnorms: np.ndarray    # (d,) ‖Â v − θ v‖ of the unit Ritz vectors
    iterations: int         # Gram mat-vecs consumed (count + filter + proj)
    estimate: LambdaEstimate
    filter_degree: int
    signals: int


def compressive_embed(z, k: int, key, cfg, *,
                      laplacian_normalize: bool = True
                      ) -> CompressiveEmbedding:
    """Observability wrapper over :func:`_compressive_embed_impl`: the solve
    runs under an ``eigensolve`` span (``solver="compressive"`` — one track
    with the iterative solvers, so solver bake-offs read off one metric) and
    feeds the same ``repro_eigensolves_total`` / ``repro_solver_iterations``
    / ``repro_solver_resnorm_max`` series."""
    with obs_trace.span("eigensolve", solver="compressive", n=z.n,
                        k=k) as sp:
        out = _compressive_embed_impl(
            z, k, key, cfg, laplacian_normalize=laplacian_normalize)
        res = np.asarray(out.resnorms[:k])
        resnorm_max = float(res.max()) if res.size else 0.0
        sp.set_metadata(iterations=int(out.iterations),
                        resnorm_max=resnorm_max,
                        filter_degree=out.filter_degree, signals=out.signals)
    _SOLVES_TOTAL.inc(solver="compressive")
    _SOLVER_ITERS.observe(int(out.iterations), solver="compressive")
    _SOLVER_RESNORM.set(resnorm_max, solver="compressive")
    return out


def _compressive_embed_impl(z, k: int, key, cfg, *,
                            laplacian_normalize: bool = True
                            ) -> CompressiveEmbedding:
    """The eigendecomposition-free spectral embedding (steps 1–2 + 4 of the
    module docstring); ``subset_cluster`` is step 3.

    ``cfg`` knobs: ``CompressiveOptions.probes`` / ``.degree`` /
    ``.signals`` (None → gap- and K-derived defaults). The
    working set is three d-wide tall blocks in the representation's native
    residency — no (N, K) iterate exists at any point.
    """
    if not laplacian_normalize:
        raise ValueError(
            "solver='compressive' requires laplacian_normalize=True: the "
            "Chebyshev filter maps spec(Â) onto [-1, 1] via y = 2λ - 1, "
            "which needs the degree normalization's λ ∈ [0, 1]")
    co = cfg.compressive_options
    if co.lambdas is not None:
        # warm start: a caller-supplied (λ_K, λ_{K+1}) bracket (typically a
        # previous fit on the same distribution — the spectrum of Â is
        # N-stable) replaces the eigencount sweep outright
        lam_k, lam_k1 = (float(v) for v in co.lambdas)
        est = LambdaEstimate(
            lambda_k=lam_k, lambda_k1=lam_k1,
            cutoff=0.5 * (lam_k + lam_k1), moments=None, probes=0, degree=0)
        nmv_count = 0
    else:
        est, nmv_count = estimate_lambda_k(
            z, k, fold_key(key, "count"), probes=co.probes)
    degree = co.degree or default_filter_degree(est)
    d = min(co.signals or default_signals(k), z.n)
    coeffs = step_coeffs(est.cutoff, degree)
    r = z.random_tall(fold_key(key, "signals"), d)
    s, _, nmv_filter = chebyshev_sweep(z, r, degree, coeffs=coeffs)
    # Factor the filtered block through the feature space: q = Ẑᵀ h(Â)R is
    # the (D, d) out-of-sample projection, and E = Ẑ q the in-sample
    # embedding — the same rule SCRBModel applies to new rows, so serving
    # training rows reproduces the fit embedding exactly.
    q = np.asarray(z.rmatvec(s), np.float32)
    e = z.matvec_tall(jnp.asarray(q))
    # Rayleigh–Ritz diagnostics from feature-space Grams: with qe = ẐᵀE,
    #   EᵀE = qᵀqe,  EᵀÂE = qeᵀqe,  ‖ÂE·‖² terms need qee = ẐᵀẐqe.
    qe = np.asarray(z.rmatvec(e), np.float64)
    qee = np.asarray(
        z.rmatvec(z.matvec_tall(jnp.asarray(qe, jnp.float32))), np.float64)
    gram_m = q.astype(np.float64).T @ qe
    gram_a = qe.T @ qe
    gram_h2 = 0.5 * (qe.T @ qee + qee.T @ qe)
    from repro.core import eigensolver
    theta, cvec = eigensolver._whitened_rayleigh_ritz_grams_np(
        gram_m, gram_a, min(d, gram_m.shape[0]))
    # residuals of the unit Ritz vectors v_i = E c_i (cᵀ(EᵀE)c = 1):
    # r_i² = cᵢᵀH₂cᵢ − 2θᵢ·cᵢᵀAcᵢ + θᵢ²
    r2 = (np.einsum("ji,jk,ki->i", cvec, gram_h2, cvec)
          - 2.0 * theta * np.einsum("ji,jk,ki->i", cvec, gram_a, cvec)
          + theta ** 2)
    resnorms = np.sqrt(np.maximum(r2, 0.0)).astype(np.float32)
    return CompressiveEmbedding(
        embedding=e, proj=q, theta=np.asarray(theta, np.float32),
        resnorms=resnorms,
        iterations=nmv_count + nmv_filter + _PROJECTION_SWEEPS,
        estimate=est, filter_degree=degree, signals=d)


# ---------------------------------------------------------------------------
# random-subset k-means + full-N streamed assignment
# ---------------------------------------------------------------------------

def _gather_rows(u_hat, idx: np.ndarray) -> jax.Array:
    """An O(n_sub · d) device block of the requested (sorted) rows."""
    if isinstance(u_hat, streaming.ChunkedDense):
        offsets = np.concatenate([[0], np.cumsum(u_hat.chunk_sizes)])
        parts = [c[idx[(idx >= lo) & (idx < hi)] - lo]
                 for c, lo, hi in zip(u_hat.chunks, offsets, offsets[1:])]
        return jnp.asarray(np.concatenate(parts, axis=0))
    return jnp.take(u_hat, jnp.asarray(idx), axis=0)


def subset_cluster(z, u_hat, key, cfg) -> Tuple[KMeansResult, dict]:
    """Step 3: k-means on a random row subset of the normalized filtered
    signals, then one nearest-centroid sweep labels every row.

    The assignment sweep runs through ``z.map_row_chunks`` so each
    representation keeps its residency guarantees (prefetched host chunks /
    row-sharded shards); only the (N, 2) label/distance table leaves."""
    n, k = z.n, cfg.n_clusters
    n_sub = int(min(n, max(k, cfg.compressive_options.subset
                           or default_subset(n, k))))
    seed = int(jax.random.randint(fold_key(key, "subset"), (), 0,
                                  np.iinfo(np.int32).max))
    idx = np.sort(np.random.default_rng(seed).choice(
        n, size=n_sub, replace=False))
    sub = _gather_rows(u_hat, idx)
    km = _kmeans(fold_key(key, "centroids"), sub, k,
                 n_iters=cfg.kmeans_iters,
                 n_replicates=cfg.kmeans_replicates, impl=cfg.impl)
    cents = jnp.asarray(km.centroids)

    def assign(u):
        labels, d2 = ops.kmeans_assign(u, cents, impl=cfg.impl)
        # 2-column output: mesh row maps must stay 2-D to keep the row
        # sharding spec; label ids are exact in float32 (k ≪ 2^24)
        return jnp.stack([labels.astype(jnp.float32), d2], axis=1)

    out = z.map_row_chunks(assign, u_hat)
    arr = (out.to_array() if isinstance(out, streaming.ChunkedDense)
           else np.asarray(out))
    res = KMeansResult(centroids=np.asarray(km.centroids, np.float32),
                       labels=arr[:, 0].astype(np.int32),
                       inertia=float(arr[:, 1].sum()))
    return res, {"kmeans_subset_rows": n_sub}
