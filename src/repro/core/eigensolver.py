"""Blocked iterative eigensolvers for the implicit operator Â = Ẑ Ẑᵀ.

``lobpcg`` is the production solver — the TPU-native analogue of PRIMME's
near-optimal blocked methods (DESIGN.md §3.3): fixed-shape [X|W|P] subspace,
SVQB-style whitened Rayleigh–Ritz (rank-deficiency safe), soft locking via
residual masking, one block mat-vec per iteration, ``lax.while_loop`` early
exit. Everything inside is dense GEMMs → MXU.

Convergence accelerators (all three LOBPCG drivers):

  - **diagonal preconditioning** — ``precond`` is a (N,) vector applied to
    the soft-locked residual block before re-projection. For the normalized
    operator, ``degree_precond(deg)`` is Jacobi on L̂ = I − Â whose diagonal
    is 1 − 1/deg_i (each RB row collides with itself in all R grids, so
    diag(ẐẐᵀ)_i = 1/deg_i exactly).
  - **warm starts** — ``top_k_eigenpairs(x0=...)`` accepts a prior
    ``EigResult`` / block (e.g. the previous R-sweep point's subspace), pads
    it to the working block width with random columns, and the solver's QR
    keeps the warm directions first. A converged ``x0`` exits at iteration 0.
  - **adaptive tolerance** — ``stable_tol`` stops when the leading Ritz
    subspace is k-means-stable between checks (principal angles of the
    leading ``stable_k`` columns + Ritz-value stagnation) rather than when
    every residual is tiny; residuals of a spectral embedding can stagnate
    orders of magnitude above ``tol`` without moving the clustering.

``lanczos`` (full-reorth symmetric Lanczos — the "Matlab svds" stand-in of
Fig. 3) and ``subspace_iteration`` (block power method) are the comparison
baselines for the paper's solver study. ``randomized`` is a one-pass block
Krylov sketch (S = [X, ÂX, Â²X] + one whitened Rayleigh–Ritz — three block
mat-vecs total); ``solver="auto"`` runs it first and finishes with a
warm-started, preconditioned LOBPCG only if the sketch's residuals miss
``tol`` — the bake-off-backed default for the benchmarks.

Three LOBPCG drivers back the executor's eigensolve stage, one per data
representation (``repro.core.rowmatrix``): ``lobpcg`` (device-resident
``lax.while_loop`` — also the jitted body of the mesh placement),
``lobpcg_host`` (host-driven loop over an eager streaming mat-vec; the
device→host convergence read happens once every ``check_every`` iterations
so the streaming path does not serialize on a scalar transfer per step),
and ``lobpcg_host_chunked`` (block iterates live as host row chunks;
``top_k_eigenpairs(chunk_sizes=...)`` selects it, and soft-locked columns
are physically compressed out of its mat-vecs). All share the residual /
Rayleigh–Ritz math.
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace

Matvec = Callable[[jax.Array], jax.Array]

_SOLVES_TOTAL = obs_metrics.REGISTRY.counter(
    "repro_eigensolves_total", "Completed top-k eigensolves.", ("solver",))
_SOLVER_ITERS = obs_metrics.REGISTRY.histogram(
    "repro_solver_iterations", "Block mat-vec iterations per eigensolve.",
    ("solver",), buckets=obs_metrics.log_buckets(1.0, 1e4))
_SOLVER_RESNORM = obs_metrics.REGISTRY.gauge(
    "repro_solver_resnorm_max", "Worst top-k residual of the last eigensolve.",
    ("solver",))


class EigResult(NamedTuple):
    theta: jax.Array      # (k,) eigenvalues, descending
    vectors: jax.Array    # (n, k) eigenvectors
    resnorms: jax.Array   # (k,) final residual norms
    iterations: jax.Array # scalar int32 — mat-vec blocks used


def _orthonormalize(x: jax.Array) -> jax.Array:
    q, _ = jnp.linalg.qr(x)
    return q


def degree_precond(deg) -> np.ndarray:
    """Jacobi preconditioner for L̂ = I − Â from the RB degrees.

    diag(Â)_i = 1/deg_i exactly (a point collides with itself in every
    grid), so diag(L̂)_i = 1 − 1/deg_i and the Jacobi weight is
    deg_i/(deg_i − 1). Degrees are ≥ 1 by the same self-collision argument;
    the clamp caps the boost isolated points (deg → 1) get, and the overall
    scale is irrelevant (the residual block is column-normalized after)."""
    deg = np.asarray(deg, np.float64)
    t = deg / np.maximum(deg - 1.0, 0.25)
    t = np.minimum(t, 10.0 * max(float(np.median(t)), 1e-12))
    return (t / np.max(t)).astype(np.float32)


def _whitened_rayleigh_ritz(s, a_s, k, rcond=3e-4):
    """Rayleigh–Ritz on span(S) robust to rank deficiency.

    Whitens with M = SᵀS via eigh, clamping directions with λ ≤ rcond·λmax to
    zero weight (they correspond to locked/zero columns), then solves the
    projected symmetric problem and returns the top-k combination C (m, k)
    with CᵀMC = I on the kept subspace.
    """
    m = s.shape[1]
    gram_m = s.T @ s
    gram_a = s.T @ a_s
    gram_a = 0.5 * (gram_a + gram_a.T)
    lam, v = jnp.linalg.eigh(gram_m)
    keep = lam > rcond * jnp.max(lam)
    inv_sqrt = jnp.where(keep, 1.0 / jnp.sqrt(jnp.maximum(lam, 1e-30)), 0.0)
    wh = v * inv_sqrt[None, :]                       # (m, m)
    t = wh.T @ gram_a @ wh
    t = 0.5 * (t + t.T)
    # Push dropped directions to the bottom of the spectrum so top-k never
    # selects them (operator is PSD ⇒ true eigenvalues ≥ 0 > -1).
    t = t - (1.0 - keep.astype(t.dtype))[:, None] * jnp.eye(m, dtype=t.dtype)
    evals, evecs = jnp.linalg.eigh(t)                # ascending
    top = jnp.arange(m - k, m)[::-1]
    theta = evals[top]
    c = wh @ evecs[:, top]                           # (m, k)
    return theta, c


def _lobpcg_residual_block(x, ax, tol, tvec):
    """Ritz values, relative residuals, and the soft-locked search block W.

    ``tvec`` is the optional (N,) diagonal preconditioner applied to the
    masked residual before the X-projection (W's columns are re-normalized
    afterwards, so only the relative row weights matter)."""
    theta = jnp.sum(x * ax, axis=0)               # Ritz values (diag XᵀAX)
    r = ax - x * theta[None, :]
    res = jnp.linalg.norm(r, axis=0) / jnp.maximum(theta, 1e-12)
    active = (res > tol).astype(x.dtype)
    w = r * active[None, :]                        # soft lock
    if tvec is not None:
        w = w * tvec[:, None].astype(w.dtype)
    # project W against X for stability, then normalize
    w = w - x @ (x.T @ w)
    wn = jnp.linalg.norm(w, axis=0)
    w = w / jnp.maximum(wn, 1e-12)[None, :] * (wn > 1e-10)
    return theta, res, w


def _lobpcg_rr_update(x, ax, p, ap, w, aw, k):
    """One [X|W|P] Rayleigh–Ritz step: new (X, AX, P, AP) — dense GEMMs only."""
    s = jnp.concatenate([x, w, p], axis=1)         # (n, 3k)
    a_s = jnp.concatenate([ax, aw, ap], axis=1)
    _, c = _whitened_rayleigh_ritz(s, a_s, k)
    x_new = s @ c
    ax_new = a_s @ c
    # float32 drift control: re-orthonormalize X by QR and keep AX
    # consistent through the triangular factor (X = QR ⇒ AQ = AX·R⁻¹).
    # The refresh is all-or-nothing: mixing QR columns with raw
    # Rayleigh–Ritz columns would break XᵀX = I block orthonormality
    # whenever any single diagonal of R is flagged unsafe.
    q, rfac = jnp.linalg.qr(x_new)
    rdiag = jnp.abs(jnp.diagonal(rfac))
    all_safe = jnp.all(rdiag > 1e-6 * jnp.max(rdiag))
    ax_q = jax.scipy.linalg.solve_triangular(
        rfac.T, ax_new.T, lower=True).T
    ax_q = jnp.where(jnp.isfinite(ax_q), ax_q, 0.0)
    x_new = jnp.where(all_safe, q, x_new)
    ax_new = jnp.where(all_safe, ax_q, ax_new)
    # implicit P: the W/P component of the update direction
    c_p = c.at[:k, :].set(0.0)
    p_new = s @ c_p
    ap_new = a_s @ c_p
    pn = jnp.linalg.norm(p_new, axis=0)
    pscale = jnp.where(pn > 1e-10, 1.0 / jnp.maximum(pn, 1e-12), 0.0)
    p_new = p_new * pscale[None, :]
    ap_new = ap_new * pscale[None, :]
    return x_new, ax_new, p_new, ap_new


# module-level jitted variants so repeated lobpcg_host calls at the same
# shapes hit the session jit cache instead of re-tracing per invocation
_lobpcg_residual_block_jit = jax.jit(_lobpcg_residual_block)
_lobpcg_rr_update_jit = jax.jit(_lobpcg_rr_update, static_argnames=("k",))


@functools.partial(jax.jit, static_argnames=("sk",))
def _subspace_alignment(x_prev, x_cur, sk: int):
    """cos of the largest principal angle between the leading-``sk`` column
    spans of two orthonormal blocks: min singular value of X_prevᵀX_cur,
    computed as √λmin of the (sk, sk) Gram — the embedding-stability proxy
    the adaptive stop checks."""
    g = x_prev[:, :sk].T @ x_cur[:, :sk]
    lam = jnp.linalg.eigvalsh(g.T @ g)
    return jnp.sqrt(jnp.maximum(lam[0], 0.0))


def _lobpcg_finalize(x, ax, it):
    theta = jnp.sum(x * ax, axis=0)
    order = jnp.argsort(-theta)
    r = ax - x * theta[None, :]
    res_final = jnp.linalg.norm(r, axis=0) / jnp.maximum(theta, 1e-12)
    return EigResult(theta[order], x[:, order], res_final[order], it)


def lobpcg(
    matvec: Matvec,
    x0: jax.Array,
    *,
    max_iters: int = 200,
    tol: float = 1e-5,
    precond: Optional[jax.Array] = None,
    stable_tol: Optional[float] = None,
    stable_k: Optional[int] = None,
    check_every: int = 4,
    conv_k: Optional[int] = None,
) -> EigResult:
    """Top-k eigenpairs of a symmetric PSD operator. x0: (n, k) start block.

    A converged ``x0`` exits with ``iterations == 0`` (the initial residual
    is computed before the loop). ``conv_k`` gates convergence on the
    leading ``conv_k`` Ritz columns only (the block is theta-descending
    after each Rayleigh–Ritz step) — the convergence-buffer columns then
    accelerate the wanted pairs without being obliged to converge
    themselves, which is what makes a warm start of the wanted pairs an
    immediate exit instead of a wait on freshly-randomized buffer columns.
    ``stable_tol`` adds the adaptive stop: every ``check_every`` iterations
    the leading ``stable_k`` Ritz columns are compared against the last
    checkpoint and the solve stops when 1 − cos(largest principal angle) <
    ``stable_tol``."""
    n, k = x0.shape
    if 3 * k > n:
        raise ValueError(f"block too large: need 3k ≤ n, got k={k}, n={n}")
    tvec = None if precond is None else jnp.asarray(precond, jnp.float32)
    sk = min(stable_k or k, k)
    ck = min(conv_k or k, k)
    adaptive = stable_tol is not None

    x = _orthonormalize(x0.astype(jnp.float32))
    ax = matvec(x)
    _, res0, _ = _lobpcg_residual_block(x, ax, tol, tvec)

    def cond(state):
        x, ax, p, ap, res, it, x_chk, done = state
        return jnp.logical_and(
            jnp.logical_and(it < max_iters, jnp.max(res[:ck]) > tol),
            jnp.logical_not(done))

    def body(state):
        x, ax, p, ap, _, it, x_chk, done = state
        theta, res, w = _lobpcg_residual_block(x, ax, tol, tvec)
        aw = matvec(w)
        x_new, ax_new, p_new, ap_new = _lobpcg_rr_update(x, ax, p, ap, w, aw, k)
        # periodic exact refresh of AX kills residual recombination drift
        ax_new = jax.lax.cond(
            (it + 1) % 16 == 0, lambda: matvec(x_new), lambda: ax_new)
        if adaptive:
            at_check = (it + 1) % check_every == 0
            align = _subspace_alignment(x_chk, x_new, sk)
            done = jnp.logical_and(at_check, (1.0 - align) < stable_tol)
            x_chk = jnp.where(at_check, x_new, x_chk)
        return x_new, ax_new, p_new, ap_new, res, it + 1, x_chk, done

    p0 = jnp.zeros_like(x)
    x, ax, _, _, res, it, _, _ = jax.lax.while_loop(
        cond, body,
        (x, ax, p0, jnp.zeros_like(x), res0, jnp.int32(0), x,
         jnp.asarray(False)),
    )
    return _lobpcg_finalize(x, ax, it)


def lobpcg_host(
    matvec: Matvec,
    x0: jax.Array,
    *,
    max_iters: int = 200,
    tol: float = 1e-5,
    precond: Optional[jax.Array] = None,
    stable_tol: Optional[float] = None,
    stable_k: Optional[int] = None,
    check_every: int = 4,
    conv_k: Optional[int] = None,
) -> EigResult:
    """LOBPCG driven by a host-side Python loop instead of ``lax.while_loop``.

    Same math as ``lobpcg`` (shared residual/Rayleigh–Ritz helpers), but
    ``matvec`` is called *eagerly* — it may stream over host-resident row
    chunks (``streaming.ChunkedELL.gram_matvec``) so the device only ever
    holds one chunk of Z. Tracing such a mat-vec into ``while_loop`` would
    embed every chunk as an on-device constant, defeating the point. The
    dense block algebra between mat-vecs is jitted once per shape.

    Convergence is read back to the host only every ``check_every``
    iterations (plus iteration 0, preserving the zero-iteration warm-start
    exit): the per-iteration ``float(jnp.max(res))`` of the old driver was a
    blocking device→host sync that serialized the streaming path on a
    scalar transfer.
    """
    n, k = x0.shape
    if 3 * k > n:
        raise ValueError(f"block too large: need 3k ≤ n, got k={k}, n={n}")
    tvec = None if precond is None else jnp.asarray(precond, jnp.float32)
    sk = min(stable_k or k, k)
    ck = min(conv_k or k, k)
    prepare = _lobpcg_residual_block_jit
    update = functools.partial(_lobpcg_rr_update_jit, k=k)

    x = _orthonormalize(jnp.asarray(x0, jnp.float32))
    ax = jnp.asarray(matvec(x))
    p = jnp.zeros_like(x)
    ap = jnp.zeros_like(x)
    it = 0
    x_chk = x
    while it < max_iters:
        theta, res, w = prepare(x, ax, tol, tvec)
        if it % check_every == 0 or it == 0:
            if float(jnp.max(res[:ck])) <= tol:
                break
            if stable_tol is not None and it > 0:
                align = float(_subspace_alignment(x_chk, x, sk))
                if (1.0 - align) < stable_tol:
                    break
            x_chk = x
        aw = jnp.asarray(matvec(w))
        x, ax, p, ap = update(x, ax, p, ap, w, aw)
        it += 1
        if it % 16 == 0:
            ax = jnp.asarray(matvec(x))
    return _lobpcg_finalize(x, ax, jnp.int32(it))


# --------------------------------------------------------------------------
# Chunked LOBPCG: block vectors live as host-resident row chunks
# (streaming.ChunkedDense); only the Gram mat-vec touches the device, one
# chunk at a time. The small (3b, 3b) block algebra runs in host float64.
# --------------------------------------------------------------------------

def _chunks_inner(a: Sequence[np.ndarray], b: Sequence[np.ndarray]) -> np.ndarray:
    """Σ_c A_cᵀ B_c in float64 — the tall-matrix inner products of LOBPCG."""
    out = None
    for ac, bc in zip(a, b):
        g = ac.astype(np.float64).T @ bc.astype(np.float64)
        out = g if out is None else out + g
    return out


def _chunks_col_dots(a: Sequence[np.ndarray], b: Sequence[np.ndarray]) -> np.ndarray:
    """diag(AᵀB) without forming the full Gram: Σ_c colsum(A_c ∘ B_c)."""
    return sum(
        np.sum(ac.astype(np.float64) * bc.astype(np.float64), axis=0)
        for ac, bc in zip(a, b))


def _chunks_resnorms(x, ax, theta) -> np.ndarray:
    """Relative residual norms ‖AX − XΘ‖_col / Θ, streamed over chunks."""
    rnorm2 = sum(
        np.sum((axc.astype(np.float64) - xc.astype(np.float64)
                * theta[None, :]) ** 2, axis=0)
        for xc, axc in zip(x, ax))
    return np.sqrt(rnorm2) / np.maximum(theta, 1e-12)


def _chunks_cholqr(
    x: Sequence[np.ndarray], ax: Optional[Sequence[np.ndarray]] = None
):
    """Cholesky-QR of a chunked tall-skinny block: X ← X·L⁻ᵀ (chunk-local),
    with AX kept consistent through the same triangular factor.

    X is (near-)orthonormal at every call site (random start block, or the
    output of a whitened Rayleigh–Ritz), so XᵀX is well conditioned and a
    single Cholesky pass suffices; on numerical breakdown the factorization
    is skipped (mirroring the dense path's unsafe-column guard).
    """
    m = _chunks_inner(x, x)
    m = 0.5 * (m + m.T)
    try:
        lfac = np.linalg.cholesky(
            m + 1e-12 * max(np.trace(m) / m.shape[0], 1.0) * np.eye(m.shape[0]))
    except np.linalg.LinAlgError:
        return list(x), None if ax is None else list(ax)
    xq = [np.linalg.solve(lfac, c.astype(np.float64).T).T.astype(np.float32)
          for c in x]
    if ax is None:
        return xq, None
    axq = [np.linalg.solve(lfac, c.astype(np.float64).T).T.astype(np.float32)
           for c in ax]
    return xq, axq


def _whitened_rayleigh_ritz_grams_np(gram_m, gram_a, k, rcond=3e-4):
    """Host-float64 twin of ``_whitened_rayleigh_ritz`` taking the (3b, 3b)
    Gram matrices directly (the chunked path accumulates them streamingly
    and never materializes S)."""
    m = gram_m.shape[0]
    gram_a = 0.5 * (gram_a + gram_a.T)
    lam, v = np.linalg.eigh(0.5 * (gram_m + gram_m.T))
    keep = lam > rcond * np.max(lam)
    inv_sqrt = np.where(keep, 1.0 / np.sqrt(np.maximum(lam, 1e-30)), 0.0)
    wh = v * inv_sqrt[None, :]
    t = wh.T @ gram_a @ wh
    t = 0.5 * (t + t.T)
    t = t - (1.0 - keep.astype(t.dtype))[:, None] * np.eye(m)
    evals, evecs = np.linalg.eigh(t)
    top = np.arange(m - k, m)[::-1]
    return evals[top], wh @ evecs[:, top]


def _split_chunks(vec: Optional[np.ndarray], sizes: Sequence[int]):
    """Split an (N,) host vector into row chunks aligned with ``sizes``."""
    if vec is None:
        return None
    offsets = np.concatenate([[0], np.cumsum(sizes)])
    v = np.asarray(vec, np.float32)
    return [v[offsets[i]:offsets[i + 1]] for i in range(len(sizes))]


def _compressed_width(n_active: int) -> int:
    """Bucket active-column counts to multiples of 4 so the compressed
    mat-vec hits a bounded set of jit shapes instead of retracing per
    newly-locked column."""
    return max(4, -(-n_active // 4) * 4)


def lobpcg_host_chunked(
    matvec: Callable,
    x0,
    *,
    max_iters: int = 200,
    tol: float = 1e-5,
    precond: Optional[np.ndarray] = None,
    stable_tol: Optional[float] = None,
    stable_k: Optional[int] = None,
    check_every: int = 4,
    conv_k: Optional[int] = None,
) -> EigResult:
    """LOBPCG whose block iterates never exist as O(N) device arrays.

    ``x0`` is a ``streaming.ChunkedDense`` start block; ``matvec`` maps a
    ``ChunkedDense`` to a ``ChunkedDense`` with the same chunking (e.g.
    ``ChunkedELL.gram_matvec_chunked`` — device residency one chunk + the
    (D, K) accumulator). All tall operands (X, AX, W, P, AP) stay on the
    host in row chunks; the O(b²)/O(b³) Rayleigh–Ritz algebra runs in host
    float64. Same math as ``lobpcg_host``; the Ritz *embedding is emitted as
    host-resident row chunks*, so downstream stages (row normalization,
    streaming k-means) can keep streaming.

    Soft locking is carried through physically here: converged columns of W
    are exactly zero (masked before the X-projection, which preserves
    zeros), so they are compressed out of the streamed mat-vec — the
    per-iteration O(N·R·b_active) cost shrinks as Ritz pairs lock — and
    scattered back as zero columns for the Rayleigh–Ritz algebra.
    """
    from repro.core.streaming import ChunkedDense

    n, k = x0.n, x0.k
    if 3 * k > n:
        raise ValueError(f"block too large: need 3k ≤ n, got k={k}, n={n}")
    wrap = lambda chunks: ChunkedDense(tuple(chunks))
    mv = lambda chunks: list(matvec(wrap(chunks)).chunks)
    tchunks = _split_chunks(precond, [c.shape[0] for c in x0.chunks])
    sk = min(stable_k or k, k)
    ck = min(conv_k or k, k)

    x, _ = _chunks_cholqr([c.astype(np.float32) for c in x0.chunks])
    ax = mv(x)
    p = [np.zeros_like(c) for c in x]
    ap = [np.zeros_like(c) for c in x]
    it = 0
    x_chk = None
    res = np.full((k,), np.inf)
    while it < max_iters:
        theta = _chunks_col_dots(x, ax)                  # Ritz values
        res = _chunks_resnorms(x, ax, theta)
        # convergence gated on the leading-theta conv_k columns only (the
        # buffer columns assist, they are not obliged to converge)
        if float(np.max(res[np.argsort(-theta)][:ck])) <= tol:
            break
        if stable_tol is not None and it % check_every == 0:
            if x_chk is not None:
                g = _chunks_inner(
                    [c[:, :sk] for c in x_chk], [c[:, :sk] for c in x])
                lam_min = float(np.linalg.eigvalsh(g.T @ g)[0])
                if 1.0 - np.sqrt(max(lam_min, 0.0)) < stable_tol:
                    break
            x_chk = [c.copy() for c in x]
        active = (res > tol).astype(np.float32)
        thetaf = theta.astype(np.float32)
        w = [(axc - xc * thetaf[None, :]) * active[None, :]
             for xc, axc in zip(x, ax)]
        proj = _chunks_inner(x, w).astype(np.float32)    # project W ⊥ X
        w = [wc - xc @ proj for xc, wc in zip(x, w)]
        if tchunks is not None:
            w = [wc * tc[:, None] for wc, tc in zip(w, tchunks)]
            # re-project: the preconditioner reintroduces X components
            proj = _chunks_inner(x, w).astype(np.float32)
            w = [wc - xc @ proj for xc, wc in zip(x, w)]
        wn = np.sqrt(np.maximum(_chunks_col_dots(w, w), 0.0))
        wscale = (np.where(wn > 1e-10, 1.0 / np.maximum(wn, 1e-12), 0.0)
                  .astype(np.float32))
        w = [wc * wscale[None, :] for wc in w]

        # soft-lock compression: stream only the still-active columns of W
        # through the mat-vec (locked columns are exactly zero), padded to a
        # bucketed width so jit shapes stay bounded
        act_idx = np.nonzero(wn > 1e-10)[0]
        if len(act_idx) < k:
            m = min(_compressed_width(len(act_idx)), k)
            w_cmp = [np.ascontiguousarray(
                np.pad(wc[:, act_idx], ((0, 0), (0, m - len(act_idx)))))
                for wc in w]
            aw_cmp = mv(w_cmp)
            aw = [np.zeros_like(wc) for wc in w]
            for awc, cc in zip(aw, aw_cmp):
                awc[:, act_idx] = cc[:, :len(act_idx)]
        else:
            aw = mv(w)

        # [X|W|P] Rayleigh–Ritz from streamed (3b, 3b) Gram accumulations,
        # assembled block-structured (3×3 of b×b) — no per-chunk concat copy
        gram_m = np.zeros((3 * k, 3 * k))
        gram_a = np.zeros((3 * k, 3 * k))
        s_blocks, a_blocks = (x, w, p), (ax, aw, ap)
        for i in range(3):
            for j in range(3):
                bi, bj = slice(i * k, (i + 1) * k), slice(j * k, (j + 1) * k)
                if i <= j:                               # SᵀS is symmetric
                    gram_m[bi, bj] = _chunks_inner(s_blocks[i], s_blocks[j])
                    gram_m[bj, bi] = gram_m[bi, bj].T
                gram_a[bi, bj] = _chunks_inner(s_blocks[i], a_blocks[j])
        _, c = _whitened_rayleigh_ritz_grams_np(gram_m, gram_a, k)
        cf = c.astype(np.float32)
        cx, cw, cp = cf[:k], cf[k:2 * k], cf[2 * k:]
        x_new, ax_new, p_new, ap_new = [], [], [], []
        for xc, wc, pc, axc, awc, apc in zip(x, w, p, ax, aw, ap):
            x_new.append(xc @ cx + wc @ cw + pc @ cp)
            ax_new.append(axc @ cx + awc @ cw + apc @ cp)
            # implicit P: the W/P component only (X rows of C zeroed)
            p_new.append(wc @ cw + pc @ cp)
            ap_new.append(awc @ cw + apc @ cp)
        # drift control: re-orthonormalize X, AX kept consistent (chol-QR)
        x, ax = _chunks_cholqr(x_new, ax_new)
        pn = np.sqrt(np.maximum(_chunks_col_dots(p_new, p_new), 0.0))
        pscale = (np.where(pn > 1e-10, 1.0 / np.maximum(pn, 1e-12), 0.0)
                  .astype(np.float32))
        p = [pc * pscale[None, :] for pc in p_new]
        ap = [apc * pscale[None, :] for apc in ap_new]
        it += 1
        if it % 16 == 0:
            # periodic exact refresh of AX kills recombination drift
            ax = mv(x)

    theta = _chunks_col_dots(x, ax)
    order = np.argsort(-theta)
    res_final = _chunks_resnorms(x, ax, theta)
    vectors = wrap([np.ascontiguousarray(c[:, order]) for c in x])
    return EigResult(
        jnp.asarray(theta[order], jnp.float32), vectors,
        jnp.asarray(res_final[order], jnp.float32), jnp.int32(it))


def lanczos(
    matvec: Matvec,
    v0: jax.Array,
    k: int,
    *,
    max_iters: int = 100,
    tol: float = 0.0,
) -> EigResult:
    """Symmetric Lanczos with full re-orthogonalization (svds stand-in).

    Single-vector Krylov; stores the (m, n) basis on the host and drives
    the mat-vec eagerly. ``iterations`` reports the **true basis size**: the
    recurrence exits early when the Krylov space exhausts (β → 0) or — with
    ``tol > 0`` — when the tridiagonal residual bounds β_j·|s_{j,i}| of the
    top-k Ritz pairs all drop below ``tol`` (checked every few steps). A
    convergence-buffer block does not apply to a single-vector Krylov
    method; ``top_k_eigenpairs`` documents ``buffer`` as ignored here.
    """
    n = v0.shape[0]
    m = min(max_iters, n)
    v = np.asarray(v0[:, 0] if v0.ndim == 2 else v0, np.float64)
    v = v / np.linalg.norm(v)
    basis = np.zeros((m, n), np.float64)
    alphas: list = []
    betas: list = []
    j = 0
    while j < m:
        av = np.asarray(
            matvec(jnp.asarray(v, jnp.float32)[:, None]), np.float64)[:, 0]
        alpha = float(v @ av)
        basis[j] = v
        # Full re-orthogonalization (twice) against the stored basis
        # replaces the three-term recurrence: after exhaustion w → 0 and
        # can never regrow, unlike the raw recurrence which feeds garbage
        # β back in multiplicatively.
        w = av - basis[:j + 1].T @ (basis[:j + 1] @ av)
        w = w - basis[:j + 1].T @ (basis[:j + 1] @ w)
        beta = float(np.linalg.norm(w))
        alphas.append(alpha)
        betas.append(beta)
        j += 1
        if beta <= 1e-6 * max(1.0, abs(alpha)):
            break                                   # Krylov space exhausted
        v = w / beta
        if tol > 0.0 and j >= k and (j % 5 == 0 or j == m):
            tmat = (np.diag(alphas) + np.diag(betas[:-1], 1)
                    + np.diag(betas[:-1], -1))
            evals_j, evecs_j = np.linalg.eigh(tmat)
            top = evals_j[::-1][:k]
            bottom_row = np.abs(evecs_j[-1, ::-1][:k])
            bounds = betas[-1] * bottom_row / np.maximum(top, 1e-12)
            if float(np.max(bounds)) <= tol:
                break
    tmat = np.diag(alphas)
    if j > 1:
        tmat += np.diag(betas[:j - 1], 1) + np.diag(betas[:j - 1], -1)
    evals_h, evecs_h = np.linalg.eigh(tmat)
    kk = min(k, j)
    evals = np.pad(evals_h[::-1][:kk], (0, k - kk))
    evecs = np.zeros((j, k))
    evecs[:, :kk] = evecs_h[:, ::-1][:, :kk]
    theta = jnp.asarray(evals, jnp.float32)
    vectors = jnp.asarray(basis[:j].T @ evecs, jnp.float32)
    av = matvec(vectors)
    res = jnp.linalg.norm(av - vectors * theta[None, :], axis=0) \
        / jnp.maximum(theta, 1e-12)
    return EigResult(theta, vectors, res, jnp.int32(j))


def randomized(
    matvec: Matvec,
    x0: jax.Array,
    *,
    depth: int = 2,
) -> EigResult:
    """One-pass randomized block-Krylov eigensolver (Musco–Musco style).

    Builds S = [X, ÂX, …, Â^depth X] with per-block column rescaling (the
    span is unchanged; the whitened Rayleigh–Ritz absorbs the rest of the
    ill-conditioning) and solves once on the (depth+1)·b subspace —
    ``depth + 1`` block mat-vecs total, no iteration. Exact when the
    spectrum decays fast; ``solver="auto"`` uses it as the first pass and
    falls through to warm-started LOBPCG when its residuals miss ``tol``.
    """
    b = x0.shape[1]
    x = _orthonormalize(x0.astype(jnp.float32))
    s_blocks = [x]
    a_of_s = []                       # a_of_s[i] = Â·s_blocks[i], exact
    cur = x
    for i in range(depth + 1):
        a_cur = matvec(cur)
        a_of_s.append(a_cur)
        if i < depth:
            nrm = jnp.linalg.norm(a_cur, axis=0)
            cur = a_cur / jnp.maximum(nrm, 1e-30)[None, :]
            s_blocks.append(cur)
    s = jnp.concatenate(s_blocks, axis=1)
    a_s = jnp.concatenate(a_of_s, axis=1)
    theta, c = _whitened_rayleigh_ritz(s, a_s, b)   # top-b, descending
    vectors = s @ c
    av = a_s @ c
    res = jnp.linalg.norm(av - vectors * theta[None, :], axis=0) \
        / jnp.maximum(theta, 1e-12)
    return EigResult(theta, vectors, res, jnp.int32(depth + 1))


def subspace_iteration(
    matvec: Matvec,
    x0: jax.Array,
    *,
    max_iters: int = 50,
    tol: float = 1e-5,
) -> EigResult:
    """Block power iteration with Rayleigh–Ritz — the simple baseline."""
    k = x0.shape[1]

    def cond(state):
        _, res, it = state
        return jnp.logical_and(it < max_iters, jnp.max(res) > tol)

    def body(state):
        x, _, it = state
        ax = matvec(x)
        q = _orthonormalize(ax)
        aq = matvec(q)
        theta, c = _whitened_rayleigh_ritz(q, aq, k)
        x_new = q @ c
        ax_new = aq @ c
        r = ax_new - x_new * theta[None, :]
        res = jnp.linalg.norm(r, axis=0) / jnp.maximum(theta, 1e-12)
        return x_new, res, it + 1

    x = _orthonormalize(x0.astype(jnp.float32))
    res0 = jnp.full((k,), jnp.inf, jnp.float32)
    x, res, it = jax.lax.while_loop(cond, body, (x, res0, jnp.int32(0)))
    ax = matvec(x)
    theta = jnp.sum(x * ax, axis=0)
    order = jnp.argsort(-theta)
    return EigResult(theta[order], x[:, order], res[order], it * 2)


SOLVERS = {
    "lobpcg": lobpcg,
    "lobpcg_host": lobpcg_host,
    "lanczos": lanczos,
    "subspace": subspace_iteration,
    "randomized": randomized,
}

# ``solver="auto"`` is a meta-policy, not a driver: the randomized one-pass
# sketch first, then (only if its residuals miss tol) a warm-started,
# preconditioned LOBPCG continuation with the adaptive stability stop.
AUTO_SOLVER = "auto"


def lobpcg_block_width(n: int, k: int, buffer: int) -> int:
    """Width of the LOBPCG iterate block X (k + convergence buffer, capped so
    [X|W|P] fits: 3·b ≤ n — ``top_k_eigenpairs`` falls back to a dense exact
    eigensolve when even b = k does not fit). Shared with the pipeline's
    residency diagnostics so the reported dense-chunk peak tracks the actual
    block size."""
    return max(1, min(k + buffer, n // 3))


def _dense_exact(matvec, n, k, chunk_sizes=None) -> EigResult:
    """Exact dense eigensolve fallback for n < 3k (blocked iteration cannot
    fit a [X|W|P] subspace). One mat-vec against the identity materializes
    the operator — n is tiny by construction here."""
    if chunk_sizes is not None:
        from repro.core.streaming import ChunkedDense
        eye = ChunkedDense.from_array(np.eye(n, dtype=np.float32),
                                      chunk_sizes)
        a = matvec(eye).to_array()
    else:
        a = np.asarray(matvec(jnp.eye(n, dtype=jnp.float32)))
    a = 0.5 * (a.astype(np.float64) + a.astype(np.float64).T)
    evals, evecs = np.linalg.eigh(a)
    kk = min(k, n)
    theta = np.pad(evals[::-1][:kk], (0, k - kk)).astype(np.float32)
    vecs = np.zeros((n, k), np.float32)
    vecs[:, :kk] = evecs[:, ::-1][:, :kk]
    res = np.zeros((k,), np.float32)
    vectors: object = jnp.asarray(vecs)
    if chunk_sizes is not None:
        from repro.core.streaming import ChunkedDense
        vectors = ChunkedDense.from_array(vecs, chunk_sizes)
    return EigResult(jnp.asarray(theta), vectors, jnp.asarray(res),
                     jnp.int32(1))


def prepare_start_block(
    x0, n: int, b: int, key: jax.Array
) -> np.ndarray:
    """Normalize a warm start to an (n, b) host block.

    ``x0`` may be an ``EigResult``, a dense (n, kx) block, or a
    ``ChunkedDense``; extra columns are truncated, missing columns are
    padded with fresh Gaussian directions (the drivers' QR keeps the warm
    columns first, so the padding only re-opens the search space)."""
    if hasattr(x0, "vectors"):                       # EigResult
        x0 = x0.vectors
    if hasattr(x0, "to_array"):                      # ChunkedDense
        x0 = x0.to_array()
    arr = np.asarray(x0, np.float32)
    if arr.ndim != 2 or arr.shape[0] != n:
        raise ValueError(
            f"warm start must be (n, k) with n={n}, got {arr.shape}")
    if arr.shape[1] >= b:
        return np.ascontiguousarray(arr[:, :b])
    pad = jax.random.normal(key, (n, b - arr.shape[1]), jnp.float32)
    return np.concatenate([arr, np.asarray(pad)], axis=1)


def _chunked_randomized_impl(matvec, x0c, *, depth: int = 2) -> EigResult:
    """``randomized`` over host-chunked iterates: the Krylov blocks live as
    row-chunk lists, the ((depth+1)b)² Gram matrices are accumulated
    streamingly, and the single Rayleigh–Ritz runs in host float64."""
    from repro.core.streaming import ChunkedDense
    b = x0c.k
    wrap = lambda chunks: ChunkedDense(tuple(chunks))
    mv = lambda chunks: list(matvec(wrap(chunks)).chunks)
    x, _ = _chunks_cholqr([c.astype(np.float32) for c in x0c.chunks])
    s_blocks = [x]
    a_of_s = []                       # Â applied to each stored block
    cur = x
    for i in range(depth + 1):
        a_cur = mv(cur)               # Â·s_blocks[i], exact
        a_of_s.append(a_cur)
        if i < depth:
            nrm = np.sqrt(np.maximum(_chunks_col_dots(a_cur, a_cur), 1e-60))
            scale = (1.0 / nrm).astype(np.float32)
            cur = [c * scale[None, :] for c in a_cur]
            s_blocks.append(cur)
    p = depth + 1
    m = p * b
    gram_m = np.zeros((m, m))
    gram_a = np.zeros((m, m))
    for i in range(p):
        for j in range(p):
            bi, bj = slice(i * b, (i + 1) * b), slice(j * b, (j + 1) * b)
            if i <= j:
                gram_m[bi, bj] = _chunks_inner(s_blocks[i], s_blocks[j])
                gram_m[bj, bi] = gram_m[bi, bj].T
            gram_a[bi, bj] = _chunks_inner(s_blocks[i], a_of_s[j])
    theta, c = _whitened_rayleigh_ritz_grams_np(gram_m, gram_a, b)
    cf = c.astype(np.float32)
    x_out, ax_out = [], []
    for chunk_parts in zip(*s_blocks):
        x_out.append(sum(chunk_parts[i] @ cf[i * b:(i + 1) * b]
                         for i in range(p)))
    for chunk_parts in zip(*a_of_s):
        ax_out.append(sum(chunk_parts[i] @ cf[i * b:(i + 1) * b]
                          for i in range(p)))
    order = np.argsort(-theta)
    res = _chunks_resnorms(x_out, ax_out, theta)
    vectors = wrap([np.ascontiguousarray(c[:, order]) for c in x_out])
    return EigResult(jnp.asarray(theta[order], jnp.float32), vectors,
                     jnp.asarray(res[order], jnp.float32),
                     jnp.int32(depth + 1))


def top_k_eigenpairs(
    matvec: Matvec,
    n: int,
    k: int,
    key: jax.Array,
    *,
    solver: str = "lobpcg",
    max_iters: int = 200,
    tol: float = 1e-5,
    buffer: int = 4,
    streaming: bool = False,
    chunk_sizes: Optional[Sequence[int]] = None,
    x0=None,
    precond=None,
    stable_tol: Optional[float] = None,
) -> EigResult:
    """Solve for the top-k eigenpairs (observability wrapper).

    Runs :func:`_top_k_eigenpairs_impl` (full semantics documented there)
    under an ``eigensolve`` span and records the solve on the metrics
    registry: ``repro_eigensolves_total{solver}``,
    ``repro_solver_iterations{solver}`` and
    ``repro_solver_resnorm_max{solver}``.
    """
    with obs_trace.span("eigensolve", solver=solver, n=n, k=k,
                        streaming=streaming) as sp:
        out = _top_k_eigenpairs_impl(
            matvec, n, k, key, solver=solver, max_iters=max_iters, tol=tol,
            buffer=buffer, streaming=streaming, chunk_sizes=chunk_sizes,
            x0=x0, precond=precond, stable_tol=stable_tol)
        iters = int(out.iterations)
        res = np.asarray(out.resnorms)
        resnorm_max = float(res.max()) if res.size else 0.0
        sp.set_metadata(iterations=iters, resnorm_max=resnorm_max)
    _SOLVES_TOTAL.inc(solver=solver)
    _SOLVER_ITERS.observe(iters, solver=solver)
    _SOLVER_RESNORM.set(resnorm_max, solver=solver)
    return out


def _top_k_eigenpairs_impl(
    matvec: Matvec,
    n: int,
    k: int,
    key: jax.Array,
    *,
    solver: str = "lobpcg",
    max_iters: int = 200,
    tol: float = 1e-5,
    buffer: int = 4,
    streaming: bool = False,
    chunk_sizes: Optional[Sequence[int]] = None,
    x0=None,
    precond=None,
    stable_tol: Optional[float] = None,
) -> EigResult:
    """Solve for the top-k eigenpairs with a small convergence buffer block.

    The buffer (extra Ritz pairs) accelerates convergence when the k-th and
    (k+1)-th eigenvalues are clustered — the covtype regime in the paper's
    Fig. 3 discussion. When n < 3k the blocked [X|W|P] iteration cannot fit
    even at b = k; the solve degrades to a dense exact eigendecomposition
    (one mat-vec against the identity) instead of raising.

    ``x0`` warm-starts the solve from a prior subspace (an ``EigResult``, a
    dense block, or a ``ChunkedDense``) — see :func:`prepare_start_block`;
    a converged warm start exits with ``iterations == 0``. ``precond`` is a
    (N,) diagonal (e.g. :func:`degree_precond`) applied inside the LOBPCG
    residual block. ``stable_tol`` enables the adaptive embedding-stability
    stop. All three apply to the LOBPCG family and ``solver="auto"`` only.

    ``solver="auto"``: one randomized block-Krylov pass (3 block mat-vecs);
    if its top-k residuals already meet ``tol`` that is the answer,
    otherwise LOBPCG continues warm-started from the sketch with the
    preconditioner and (by default) the adaptive stop — ``iterations``
    reports the total block mat-vecs across both phases.

    ``solver="lanczos"`` honors ``tol`` (tridiagonal residual bounds) and
    reports the true Krylov basis size as ``iterations``; ``buffer`` does
    not apply to a single-vector Krylov method and is ignored.

    ``streaming=True`` marks ``matvec`` as eager-only (it streams host
    chunks), so the iteration must be driven from the host; the LOBPCG
    host driver, ``randomized``, and ``auto`` support that.

    With ``chunk_sizes`` given, ``matvec`` must map ``ChunkedDense`` →
    ``ChunkedDense`` over that chunking, the start block is generated
    per-chunk (never an O(N) device array), and the returned ``vectors``
    are a host-chunked ``ChunkedDense``.
    """
    if solver == "compressive":
        raise ValueError(
            "solver='compressive' is not an iterative eigensolver — the "
            "executor routes it to repro.core.compressive before the "
            "eigensolve stage (Chebyshev-filtered random signals instead "
            "of eigenpairs); run it via executor.execute / SCRBModel.fit "
            "with SCRBConfig(solver='compressive')")
    valid = set(SOLVERS) | {AUTO_SOLVER}
    if solver not in valid:
        raise ValueError(f"unknown solver {solver!r}; options {sorted(valid)}")
    if 3 * k > n:
        # blocked iteration cannot fit a [X|W|P] subspace even at b = k
        return _dense_exact(matvec, n, k, chunk_sizes=chunk_sizes)
    b = lobpcg_block_width(n, k, buffer)
    auto_stable = stable_tol if stable_tol is not None else 1e-3
    trunc = lambda out: EigResult(
        out.theta[:k],
        out.vectors.take_cols(k) if hasattr(out.vectors, "take_cols")
        else out.vectors[:, :k],
        out.resnorms[:k], out.iterations)

    if chunk_sizes is not None:
        from repro.core.streaming import ChunkedDense
        if solver not in ("lobpcg", "lobpcg_host", "randomized", AUTO_SOLVER):
            raise ValueError(
                f"streaming mat-vecs require a host-driven solver "
                f"('lobpcg', 'randomized' or 'auto'), got {solver!r}")
        if x0 is not None:
            x0c = ChunkedDense.from_array(
                prepare_start_block(x0, n, b, key), chunk_sizes)
        else:
            x0c = ChunkedDense.random_normal(key, chunk_sizes, b)
        if solver == "randomized":
            return trunc(_chunked_randomized_impl(matvec, x0c, depth=2))
        if solver == AUTO_SOLVER:
            rnd = _chunked_randomized_impl(matvec, x0c, depth=2)
            if float(jnp.max(rnd.resnorms[:k])) <= tol:
                return trunc(rnd)
            out = lobpcg_host_chunked(
                matvec, rnd.vectors, max_iters=max_iters, tol=tol,
                precond=precond, stable_tol=auto_stable, stable_k=k,
                conv_k=k)
            return trunc(EigResult(out.theta, out.vectors, out.resnorms,
                                   out.iterations + rnd.iterations))
        out = lobpcg_host_chunked(
            matvec, x0c, max_iters=max_iters, tol=tol, precond=precond,
            stable_tol=stable_tol, stable_k=k, conv_k=k)
        return trunc(out)

    if x0 is not None:
        x0a = jnp.asarray(prepare_start_block(x0, n, b, key))
    else:
        x0a = jax.random.normal(key, (n, b), jnp.float32)
    if streaming and solver not in ("lobpcg", "lobpcg_host", "randomized",
                                    AUTO_SOLVER):
        raise ValueError(
            f"streaming mat-vecs require a host-driven solver "
            f"('lobpcg', 'randomized' or 'auto'), got {solver!r}")
    if solver == AUTO_SOLVER:
        rnd = randomized(matvec, x0a, depth=2)
        if float(jnp.max(rnd.resnorms[:k])) <= tol:
            return trunc(rnd)
        driver = lobpcg_host if streaming else lobpcg
        out = driver(matvec, rnd.vectors, max_iters=max_iters, tol=tol,
                     precond=precond, stable_tol=auto_stable, stable_k=k,
                     conv_k=k)
        return trunc(EigResult(out.theta, out.vectors, out.resnorms,
                               out.iterations + rnd.iterations))
    if solver == "randomized":
        return trunc(randomized(matvec, x0a, depth=2))
    if streaming or solver == "lobpcg_host":
        out = lobpcg_host(matvec, x0a, max_iters=max_iters, tol=tol,
                          precond=precond, stable_tol=stable_tol, stable_k=k,
                          conv_k=k)
    elif solver == "lobpcg":
        out = lobpcg(matvec, x0a, max_iters=max_iters, tol=tol,
                     precond=precond, stable_tol=stable_tol, stable_k=k,
                     conv_k=k)
    elif solver == "subspace":
        out = subspace_iteration(matvec, x0a, max_iters=max_iters, tol=tol)
    else:                                            # lanczos
        out = lanczos(matvec, x0a, k, max_iters=max_iters, tol=tol)
        return out
    return trunc(out)
