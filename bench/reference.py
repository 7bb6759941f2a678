"""Plain reference for SC_RB: the paper's Algorithm 1 and 2 and the
out-of-sample labelling, written from their definitions in ``jax.numpy``.

It imports nothing of the program. Every product with the RB feature matrix
is a gather or a segment sum over one grid at a time, the dense algebra runs
at ``Precision.HIGHEST``, and the eigensolver is JAX's own
``lobpcg_standard``. The definitions it follows:

- Random Binning (Alg. 1) with the Laplacian kernel: grid widths
  ω ~ σ·Gamma(2), offsets u ~ U[0, ω); a point's bin in grid g is
  ⌊(x − u_g) / ω_g⌋ per dimension, hashed into d_g columns by the
  multiply-shift hash h = ((Σ_j bin_j·a_gj + c_g)·2654435769 mod 2³²) >>
  (32 − log2 d_g). Z has one entry 1/√R per (row, grid).
- Degrees (Eq. 6): deg = Z Zᵀ 1, so deg_i = (1/R) Σ_g count(bin_ig);
  Ẑ = D^{-1/2} Z, i.e. a per-row scale 1/√(R·deg_i).
- Embedding: the top-K left singular vectors U of Ẑ (eigenvectors of
  Â = Ẑ Ẑᵀ), rows normalized; labels by Lloyd k-means (k-means++ seeds).
- Out of sample: V = Ẑᵀ U Σ⁻¹; a new row x gets deg(x) from the fitted bin
  counts, u(x) = D(x)^{-1/2} φ(x) V Σ⁻¹, normalized, and the nearest
  centroid.

``operand_dtype`` rounds the operands of every feature-matrix product to a
lower precision (accumulation stays float32) and runs the eigensolver's
dense algebra at the default matmul precision; ``bin_dtype`` rounds the
binning's operands (rows, widths, offsets). The controls of the correctness
check compute the reference that way.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.sparse.linalg import lobpcg_standard

HASH_MIX = np.uint32(2654435769)
HIGHEST = jax.lax.Precision.HIGHEST


def draw_grids(seed: int, n_grids: int, dim: int, sigma: float) -> dict:
    """R random grids for ``dim``-d rows and their hash constants, on the
    host from ``seed``."""
    rng = np.random.default_rng(int(seed))
    widths = np.maximum(sigma * rng.gamma(2.0, 1.0, (n_grids, dim)), 1e-6)
    biases = rng.uniform(0.0, 1.0, (n_grids, dim)) * widths
    hash_a = rng.integers(0, 2**31, (n_grids, dim), dtype=np.uint64) * 2 + 1
    hash_c = rng.integers(0, 2**31, (n_grids,), dtype=np.uint64)
    return {"widths": widths.astype(np.float32),
            "biases": biases.astype(np.float32),
            "hash_a": hash_a.astype(np.uint32),
            "hash_c": hash_c.astype(np.uint32)}


@jax.jit(static_argnames=("d_g",))
def rb_bins(x, widths, biases, hash_a, hash_c, *, d_g: int):
    """ELL column of every (row, grid): int32 (N, R), column = g·d_g + h."""
    if d_g & (d_g - 1):
        raise ValueError(f"d_g must be a power of two, got {d_g}")
    shift = jnp.uint32(33 - int(d_g).bit_length())
    x = x.astype(jnp.float32)

    def one_grid(_, grid):
        w, b, a, c, g = grid
        bins = jnp.floor((x - b[None, :]) / w[None, :])
        bins = bins.astype(jnp.int32).astype(jnp.uint32)
        h = jnp.sum(bins * a[None, :], axis=1, dtype=jnp.uint32)
        h = (h + c) * HASH_MIX
        return None, (h >> shift).astype(jnp.int32) + g * d_g

    r = widths.shape[0]
    _, cols = jax.lax.scan(
        one_grid, None, (widths, biases, hash_a, hash_c,
                         jnp.arange(r, dtype=jnp.int32)))
    return cols.T


@jax.jit(static_argnames=("n_features",))
def bin_counts(idx, *, n_features: int):
    """Rows in each of the D columns (Zᵀ1 · √R): int32 (D,)."""
    return jnp.zeros((n_features,), jnp.int32).at[idx.reshape(-1)].add(1)


@jax.jit
def degrees(idx, counts):
    """deg_i = (1/R) Σ_g count(idx_ig)."""
    r = idx.shape[1]
    return jnp.sum(counts[idx].astype(jnp.float32), axis=1) / r


def row_scale(deg, n_grids: int):
    """1/√(R·deg_i): the 1/√R entries and D^{-1/2} in one per-row factor."""
    return 1.0 / jnp.sqrt(n_grids * jnp.maximum(deg, 1e-8))


def _round(a, operand_dtype):
    return a if operand_dtype is None else a.astype(operand_dtype).astype(
        jnp.float32)


@jax.jit(static_argnames=("operand_dtype",))
def z_apply(idx, scale, v, *, operand_dtype=None):
    """Ẑ v: (D, k) → (N, k), one gather per grid."""
    v = _round(v, operand_dtype)

    def one_grid(acc, cols):
        return acc + v[cols], None

    acc, _ = jax.lax.scan(one_grid, jnp.zeros((idx.shape[0], v.shape[1]),
                                              jnp.float32), idx.T)
    return acc * scale[:, None]


@jax.jit(static_argnames=("d_g", "operand_dtype"))
def zt_apply(idx, scale, u, *, d_g: int, operand_dtype=None):
    """Ẑᵀ u: (N, k) → (D, k), one segment sum per grid."""
    us = _round(u * scale[:, None], operand_dtype)
    r = idx.shape[1]

    def one_grid(_, grid):
        cols, g = grid
        return None, jax.ops.segment_sum(us, cols - g * d_g,
                                         num_segments=d_g)

    _, q = jax.lax.scan(one_grid, None,
                        (idx.T, jnp.arange(r, dtype=jnp.int32)))
    return q.reshape(r * d_g, u.shape[1])


def gram_apply(idx, scale, u, *, d_g: int, operand_dtype=None):
    """Â u = Ẑ Ẑᵀ u."""
    q = zt_apply(idx, scale, u, d_g=d_g, operand_dtype=operand_dtype)
    return z_apply(idx, scale, q, operand_dtype=operand_dtype)


def row_normalize(u):
    return u / jnp.maximum(jnp.linalg.norm(u, axis=1, keepdims=True), 1e-12)


@jax.jit
def sq_dists(u, cents):
    """Squared distances (N, K) of rows to centroids, at HIGHEST."""
    return (jnp.sum(u * u, axis=1, keepdims=True)
            - 2.0 * jnp.matmul(u, cents.T, precision=HIGHEST)
            + jnp.sum(cents * cents, axis=1)[None, :])


@jax.jit(static_argnames=("k", "iters", "replicates"))
def kmeans(key, u, *, k: int, iters: int, replicates: int):
    """Best-of-``replicates`` Lloyd runs from k-means++ seeds:
    (centroids (k, dim), labels (N,))."""
    n = u.shape[0]

    def seed_one(key):
        k0, key = jax.random.split(key)
        c = jnp.zeros((k, u.shape[1]), u.dtype).at[0].set(
            u[jax.random.randint(k0, (), 0, n)])
        d2 = jnp.sum((u - c[0]) ** 2, axis=1)

        def pick(i, carry):
            c, d2, key = carry
            key, sk = jax.random.split(key)
            j = jax.random.choice(sk, n, p=d2 / jnp.sum(d2))
            c = c.at[i].set(u[j])
            return c, jnp.minimum(d2, jnp.sum((u - u[j]) ** 2, axis=1)), key

        c, _, _ = jax.lax.fori_loop(1, k, pick, (c, d2, key))
        return c

    def lloyd(c):
        def step(c, _):
            lab = jnp.argmin(sq_dists(u, c), axis=1)
            cnt = jax.ops.segment_sum(jnp.ones((n,), u.dtype), lab,
                                      num_segments=k)
            s = jax.ops.segment_sum(u, lab, num_segments=k)
            return jnp.where(cnt[:, None] > 0,
                             s / jnp.maximum(cnt, 1.0)[:, None], c), None

        c, _ = jax.lax.scan(step, c, None, length=iters)
        d2 = sq_dists(u, c)
        return c, jnp.argmin(d2, axis=1).astype(jnp.int32), jnp.sum(
            jnp.min(d2, axis=1))

    cs, labs, inertia = jax.lax.map(lambda kk: lloyd(seed_one(kk)),
                                    jax.random.split(key, replicates))
    best = jnp.argmin(inertia)
    return cs[best], labs[best]


@jax.jit(static_argnames=("d_g", "iters", "tol", "operand_dtype"))
def eigenpairs(idx, scale, x0, *, d_g: int, iters: int, tol: float,
               operand_dtype=None):
    """Top eigenpairs of Â by ``lobpcg_standard``, the rows' bins and scale
    passed in (not embedded in the program as constants)."""
    precision = "highest" if operand_dtype is None else "default"
    with jax.default_matmul_precision(precision):
        return lobpcg_standard(
            lambda v: gram_apply(idx, scale, v, d_g=d_g,
                                 operand_dtype=operand_dtype),
            x0, m=iters, tol=tol)


def fit(x, *, k: int, n_grids: int, sigma: float, d_g: int, seed: int,
        tol: float, iters: int, buffer: int = 4, kmeans_iters: int = 25,
        kmeans_replicates: int = 10, grids: dict | None = None,
        operand_dtype=None, bin_dtype=None) -> dict:
    """Algorithm 2 end to end. Returns the fitted model as host arrays
    (``grids``, ``d_g``, ``dual`` = bin counts, ``right_vectors`` V,
    ``singular_values`` Σ, ``centroids``) and the training ``labels``.

    ``tol`` is the relative residual ‖Âu − θu‖/θ every wanted pair must
    reach; ``lobpcg_standard`` states its test as ‖Âu − θu‖ <
    t·10·N·(θ + ‖Âu‖), so it is given t = tol / (20·N)."""
    x = jnp.asarray(x, jnp.float32)
    n, dim = x.shape
    grids = grids or draw_grids(seed, n_grids, dim, sigma)
    idx = bins(x, grids, d_g, operand_dtype=bin_dtype)
    counts = bin_counts(idx, n_features=n_grids * d_g)
    scale = row_scale(degrees(idx, counts), n_grids)
    key = jax.random.PRNGKey(seed)
    k_eig, k_km = jax.random.split(key)
    x0 = jax.random.normal(k_eig, (n, k + buffer), jnp.float32)
    theta, u, _ = eigenpairs(idx, scale, x0, d_g=d_g, iters=iters,
                             tol=tol / (20.0 * n),
                             operand_dtype=operand_dtype)
    theta, u = theta[:k], u[:, :k]
    sig = jnp.sqrt(jnp.maximum(theta, 0.0))
    v = zt_apply(idx, scale, u, d_g=d_g, operand_dtype=operand_dtype) \
        / sig[None, :]
    cents, labels = kmeans(k_km, row_normalize(u), k=k, iters=kmeans_iters,
                           replicates=kmeans_replicates)
    return {"grids": {name: np.asarray(a) for name, a in grids.items()},
            "d_g": int(d_g), "dual": np.asarray(counts, np.float32),
            "right_vectors": np.asarray(v, np.float32),
            "singular_values": np.asarray(sig, np.float32),
            "centroids": np.asarray(cents, np.float32),
            "labels": np.asarray(labels)}


def bins(x, grids: dict, d_g: int, *, operand_dtype=None):
    """``rb_bins`` of rows ``x`` under host ``grids``, its float operands
    first rounded to ``operand_dtype`` when one is given."""
    g = {name: jnp.asarray(a) for name, a in grids.items()}
    x, w, b = (_round(jnp.asarray(a, jnp.float32), operand_dtype)
               for a in (x, g["widths"], g["biases"]))
    return rb_bins(x, w, b, g["hash_a"], g["hash_c"], d_g=d_g)


def embed_new(x, model: dict, *, operand_dtype=None):
    """Out-of-sample embedding of new rows (N, K), rows normalized."""
    r = model["grids"]["widths"].shape[0]
    idx = bins(x, model["grids"], model["d_g"], operand_dtype=operand_dtype)
    dual = jnp.asarray(model["dual"], jnp.float32)
    deg = jnp.sum(dual[idx], axis=1) / r
    proj = jnp.asarray(model["right_vectors"]) / jnp.asarray(
        model["singular_values"])[None, :]
    u = z_apply(idx, row_scale(deg, r), proj, operand_dtype=operand_dtype)
    return row_normalize(u)


def label_gap(d2, labels) -> float:
    """Widest gap by which a given label's squared distance lies above the
    row's nearest centroid: 0 where every label is a nearest centroid."""
    d2 = np.asarray(d2, np.float64)
    got = d2[np.arange(d2.shape[0]), np.asarray(labels)]
    return float(np.max(got - d2.min(axis=1))) if d2.size else 0.0


def adjusted_rand_index(a, b) -> float:
    """ARI of two labelings (Hubert & Arabie)."""
    a, b = np.asarray(a), np.asarray(b)
    _, ai = np.unique(a, return_inverse=True)
    _, bi = np.unique(b, return_inverse=True)
    table = np.zeros((ai.max() + 1, bi.max() + 1), np.int64)
    np.add.at(table, (ai, bi), 1)
    comb = lambda t: float((t * (t - 1) // 2).sum())   # float: no overflow
    sum_ij = comb(table)
    sum_a, sum_b = comb(table.sum(1)), comb(table.sum(0))
    total = comb(np.array([a.size]))
    expected = sum_a * sum_b / total
    top = 0.5 * (sum_a + sum_b)
    return float((sum_ij - expected) / (top - expected)) if top != expected \
        else 1.0
