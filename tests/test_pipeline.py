"""Integration tests for the SC_RB pipeline (Alg. 2) and the paper's
qualitative claims at test scale."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import SCRBConfig, metrics, sc_rb, spectral_embed
from repro.core.baselines import METHODS, BaselineConfig
from repro.data.synthetic import make_blobs, make_moons, make_rings


@pytest.fixture(scope="module")
def rings():
    return make_rings(1200, 2, seed=0)


def test_scrb_smoke_fast():
    """Fast-tier pipeline smoke: two 2-d Gaussian blobs at reduced scale,
    with per-stage timings and deterministic output. The non-convex rings
    and the full-scale qualitative claims (vs exact SC, convergence in R,
    ...) run under --runslow: at this scale a two-ring RB graph is
    marginal — an exact dense eigendecomposition of the same RB graph
    splits the outer ring for roughly one grid draw in six — so a fast-tier
    accuracy gate on rings pins a lucky seed, not the pipeline.

    Deliberately the same (N, d, R, d_g, K) as tests/test_streaming's
    end-to-end case so the jitted stages compile once per pytest session.
    """
    x, y = make_blobs(600, 2, 2, seed=0)
    cfg = SCRBConfig(n_clusters=2, n_grids=96, sigma=0.15, d_g=4096,
                     solver_tol=1e-3, kmeans_replicates=2, seed=7)
    res = sc_rb(jnp.asarray(x), cfg)
    assert metrics.accuracy(res.labels, y) > 0.95
    for stage in ["rb_features", "degrees", "svd", "kmeans"]:
        assert stage in res.timer.times and res.timer.times[stage] > 0
    res2 = sc_rb(jnp.asarray(x), cfg)
    assert np.array_equal(res.labels, res2.labels)


@pytest.mark.slow
def test_scrb_recovers_rings(rings):
    """Non-convex geometry: k-means fails, SC_RB succeeds (paper §1)."""
    x, y = rings
    res = sc_rb(jnp.asarray(x), SCRBConfig(
        n_clusters=2, n_grids=192, sigma=0.15,
        kmeans_replicates=4, solver_iters=250))
    assert metrics.accuracy(res.labels, y) > 0.95
    km = METHODS["kmeans"](jnp.asarray(x), BaselineConfig(
        n_clusters=2, kmeans_replicates=4))
    assert metrics.accuracy(km.labels, y) < 0.8


@pytest.mark.slow
def test_scrb_matches_exact_sc(rings):
    """Alg. 2 converges to exact SC accuracy at moderate R (Thm 2)."""
    x, y = rings
    xj = jnp.asarray(x)
    exact = METHODS["sc"](xj, BaselineConfig(
        n_clusters=2, sigma=0.15, kmeans_replicates=4))
    acc_exact = metrics.accuracy(exact.labels, y)
    res = sc_rb(xj, SCRBConfig(
        n_clusters=2, n_grids=256, sigma=0.15, kmeans_replicates=4))
    assert metrics.accuracy(res.labels, y) >= acc_exact - 0.03


@pytest.mark.slow
def test_convergence_in_R(rings):
    """Accuracy is non-degrading as R grows (Fig. 2a trend)."""
    x, y = rings
    xj = jnp.asarray(x)
    accs = []
    for r in [16, 64, 256]:
        res = sc_rb(xj, SCRBConfig(
            n_clusters=2, n_grids=r, sigma=0.15, kmeans_replicates=4, seed=3))
        accs.append(metrics.accuracy(res.labels, y))
    assert accs[-1] >= accs[0] - 0.02
    assert accs[-1] > 0.95


@pytest.mark.slow
def test_blobs_high_dim():
    x, y = make_blobs(1500, 16, 8, seed=1)
    res = sc_rb(jnp.asarray(x), SCRBConfig(
        n_clusters=8, n_grids=192, sigma=2.0, kmeans_replicates=4))
    assert metrics.accuracy(res.labels, y) > 0.9


@pytest.mark.slow
def test_embedding_properties(rings):
    x, _ = rings
    u, sv = spectral_embed(jnp.asarray(x), SCRBConfig(
        n_clusters=2, n_grids=128, sigma=0.15))
    u = np.asarray(u)
    assert u.shape == (x.shape[0], 2)
    # rows are unit-normalized (Alg. 2 step 4)
    np.testing.assert_allclose(np.linalg.norm(u, axis=1), 1.0, atol=1e-4)
    svn = np.asarray(sv)
    # top singular value of the normalized adjacency is 1 (Perron)
    assert svn[0] == pytest.approx(1.0, abs=1e-3)
    assert np.all(svn[:-1] >= svn[1:] - 1e-5)       # descending


@pytest.mark.slow
def test_stage_timings_reported(rings):
    x, _ = rings
    res = sc_rb(jnp.asarray(x), SCRBConfig(
        n_clusters=2, n_grids=64, sigma=0.2, kmeans_replicates=2))
    for stage in ["rb_features", "degrees", "svd", "kmeans"]:
        assert stage in res.timer.times and res.timer.times[stage] > 0


@pytest.mark.slow
def test_deterministic_given_seed(rings):
    x, _ = rings
    cfg = SCRBConfig(n_clusters=2, n_grids=64, sigma=0.2,
                     kmeans_replicates=2, seed=11)
    r1 = sc_rb(jnp.asarray(x), cfg)
    r2 = sc_rb(jnp.asarray(x), cfg)
    assert np.array_equal(r1.labels, r2.labels)


@pytest.mark.slow
def test_moons():
    x, y = make_moons(1200, seed=2)
    res = sc_rb(jnp.asarray(x), SCRBConfig(
        n_clusters=2, n_grids=192, sigma=0.15, kmeans_replicates=4))
    assert metrics.accuracy(res.labels, y) > 0.9


@pytest.mark.slow
def test_minibatch_kmeans_quality():
    """Mini-batch k-means (the N ≫ 10⁷ path) lands near full Lloyd quality."""
    import jax
    from repro.core.kmeans import kmeans as full_kmeans, minibatch_kmeans
    from repro.data.synthetic import make_blobs
    x, y = make_blobs(4000, 8, 6, seed=4)
    xj = jnp.asarray(x)
    full = full_kmeans(jax.random.PRNGKey(0), xj, 6, n_replicates=4)
    mb = minibatch_kmeans(jax.random.PRNGKey(0), xj, 6,
                          batch_size=512, n_steps=80)
    acc_full = metrics.accuracy(np.asarray(full.labels), y)
    acc_mb = metrics.accuracy(np.asarray(mb.labels), y)
    assert acc_mb >= acc_full - 0.08, (acc_mb, acc_full)
    assert float(mb.inertia) <= float(full.inertia) * 1.5
