"""Property-based tests (hypothesis) over the system's core invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip(
    "hypothesis", reason="optional dependency: property tests need hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core import rb
from repro.core.metrics import accuracy, nmi, rand_index
from repro.kernels import ops

jax.config.update("jax_platform_name", "cpu")

_settings = dict(max_examples=15, deadline=None)


@settings(**_settings)
@given(
    n=st.integers(8, 120),
    d=st.integers(1, 6),
    r=st.integers(1, 24),
    seed=st.integers(0, 2**20),
    sigma=st.floats(0.05, 10.0),
)
def test_rb_idx_always_in_grid_range(n, d, r, seed, sigma):
    """Every hashed feature index lands inside its grid's column strip —
    for any data scale, any bandwidth, any grid count."""
    rng = np.random.default_rng(seed)
    x = (rng.normal(size=(n, d)) * rng.uniform(0.1, 100)).astype(np.float32)
    params = rb.make_rb_params(jax.random.PRNGKey(seed), r, d, sigma, d_g=256)
    idx = np.asarray(rb.rb_transform(jnp.asarray(x), params))
    grid = idx // 256
    assert idx.min() >= 0 and idx.max() < r * 256
    assert np.array_equal(grid, np.broadcast_to(np.arange(r), (n, r)))


@settings(**_settings)
@given(
    n=st.integers(4, 64),
    r=st.integers(1, 8),
    k=st.integers(1, 8),
    seed=st.integers(0, 2**20),
)
def test_spmm_adjoint_property(n, r, k, seed):
    """⟨Z·v, u⟩ = ⟨v, Zᵀ·u⟩ for random ELL patterns and scales."""
    d_g = 64
    d = r * d_g
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 4)
    idx = (jax.random.randint(ks[0], (n, r), 0, d_g)
           + jnp.arange(r, dtype=jnp.int32)[None] * d_g)
    s = jax.random.uniform(ks[1], (n,)) + 0.1
    u = jax.random.normal(ks[2], (n, k))
    v = jax.random.normal(ks[3], (d, k))
    zu = ops.z_matmul(idx, v, s, d_g=d_g, impl="xla")
    ztv = ops.zt_matmul(idx, u, s, d, d_g=d_g, impl="xla")
    lhs = float(jnp.vdot(zu, u))
    rhs = float(jnp.vdot(v, ztv))
    assert abs(lhs - rhs) <= 1e-3 * max(1.0, abs(lhs))


@settings(**_settings)
@given(
    n=st.integers(10, 200),
    k=st.integers(2, 8),
    seed=st.integers(0, 2**20),
)
def test_metric_bounds_and_perfect_invariance(n, k, seed):
    """All metrics ∈ [0,1]; permuting labels never changes any metric."""
    rng = np.random.default_rng(seed)
    y_true = rng.integers(0, k, size=n)
    y_pred = rng.integers(0, k, size=n)
    for fn in (accuracy, nmi, rand_index):
        v = fn(y_pred, y_true)
        assert 0.0 <= v <= 1.0 + 1e-9
    perm = rng.permutation(k)
    assert accuracy(perm[y_pred], y_true) == pytest.approx(
        accuracy(y_pred, y_true))


@settings(**_settings)
@given(
    n=st.integers(20, 100),
    seed=st.integers(0, 2**20),
    decay=st.floats(0.3, 0.95),
)
def test_lobpcg_eigenvalues_bounded_by_operator_norm(n, seed, decay):
    """Ritz values of a PSD operator always lie in [0, λmax]."""
    from repro.core.eigensolver import lobpcg
    key = jax.random.PRNGKey(seed)
    q, _ = jnp.linalg.qr(jax.random.normal(key, (n, n)))
    lam = decay ** jnp.arange(n)
    a = (q * lam[None]) @ q.T
    res = lobpcg(lambda u: a @ u,
                 jax.random.normal(jax.random.PRNGKey(seed + 1), (n, 4)),
                 max_iters=100, tol=1e-6)
    theta = np.asarray(res.theta)
    assert np.all(theta <= 1.0 + 1e-3)
    assert np.all(theta >= -1e-5)
