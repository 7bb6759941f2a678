"""Fitted-model API: out-of-sample consistency, serialization, and the
O(D·K)-state guarantee of ``repro.core.model.SCRBModel``."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import SCRBConfig, SCRBModel, graph, metrics, sc_rb
from repro.core.executor import ExecutionPlan
from repro.core.featuremap import RBMap
from repro.core.kmeans import row_normalize
from repro.core.model import BUCKET_GRID, round_to_bucket
from repro.data.synthetic import make_blobs
from repro.kernels import ops

DATA_DIR = os.path.join(os.path.dirname(__file__), "data")

# d_g pinned so the fitted state is shape-identical across fit sizes (the
# auto-probe would otherwise pick data-dependent hash widths)
BASE = dict(n_clusters=4, n_grids=64, sigma=1.5, d_g=1024,
            solver_tol=1e-3, kmeans_replicates=2, seed=0)


@pytest.fixture(scope="module")
def blobs():
    return make_blobs(800, 6, 4, seed=0)


CHUNKINGS = [pytest.param(None, id="device"),
             pytest.param(200, id="host_chunked")]


@pytest.mark.parametrize("chunk_size", CHUNKINGS)
def test_predict_matches_fit_labels(blobs, chunk_size):
    """predict(x_train) reproduces the fit labels ≥ 99% — the out-of-sample
    path (fitted degrees → V Σ⁻¹ projection → nearest centroid) agrees with
    the in-sample pipeline, for both residencies."""
    x, y = blobs
    model = SCRBModel.fit(x, SCRBConfig(**BASE, chunk_size=chunk_size))
    assert metrics.accuracy(model.fit_result.labels, y) > 0.95
    pred = model.predict(x, batch_size=chunk_size)
    assert metrics.accuracy(pred, model.fit_result.labels) >= 0.99
    # transform: row-normalized (n, K) embedding
    emb = model.transform(x[:64], batch_size=chunk_size)
    assert emb.shape == (64, BASE["n_clusters"])
    np.testing.assert_allclose(np.linalg.norm(emb, axis=1), 1.0, atol=1e-4)


@pytest.mark.parametrize("chunk_size", CHUNKINGS)
def test_save_load_roundtrip_bit_identical(blobs, chunk_size, tmp_path):
    x, _ = blobs
    model = SCRBModel.fit(x, SCRBConfig(**BASE, chunk_size=chunk_size))
    want = model.predict(x)
    path = str(tmp_path / "model.npz")
    model.save(path)
    loaded = SCRBModel.load(path)
    assert loaded.config == model.config
    np.testing.assert_array_equal(loaded.predict(x), want)
    np.testing.assert_array_equal(loaded.transform(x[:32]),
                                  model.transform(x[:32]))


@pytest.mark.parametrize("chunk_size", CHUNKINGS)
def test_out_of_sample_holdout_matches_refit(blobs, chunk_size):
    """Acceptance: fit on half, label the held-out half out-of-sample — ARI
    within 0.05 of what a full refit assigns the same rows, under both
    device and host_chunked residency."""
    x, y = blobs
    n_fit = x.shape[0] // 2
    cfg = SCRBConfig(**BASE, chunk_size=chunk_size)
    model = SCRBModel.fit(x[:n_fit], cfg)
    pred = model.predict(x[n_fit:], batch_size=chunk_size)
    full = sc_rb(jnp.asarray(x), SCRBConfig(**BASE))
    ari_refit = metrics.adjusted_rand_index(full.labels[n_fit:], y[n_fit:])
    ari_oos = metrics.adjusted_rand_index(pred, y[n_fit:])
    assert ari_oos >= ari_refit - 0.05, (ari_oos, ari_refit)


def test_model_state_independent_of_train_size(blobs):
    """Acceptance: predict allocates no O(N_train) arrays — the fitted state
    (feature params, degree dual, V, centroids) is byte-identical in size
    across fit sizes, and serializes to the same footprint."""
    x, _ = blobs
    small = SCRBModel.fit(x[:400], SCRBConfig(**BASE))
    large = SCRBModel.fit(x, SCRBConfig(**BASE))
    assert small.nbytes == large.nbytes
    shapes = lambda m: {
        "dual": m.degree_dual.shape, "v": m.right_vectors.shape,
        "sv": m.singular_values.shape, "cents": m.centroids.shape}
    assert shapes(small) == shapes(large)
    # the O(N) train-run result is deliberately NOT part of the artifact
    assert large.fit_result is not None
    assert large.predict(x[:16]).shape == (16,)


def test_spectral_embed_model_has_no_centroids(blobs):
    x, _ = blobs
    model = SCRBModel.fit(x, SCRBConfig(**BASE), final_stage="normalize")
    assert model.centroids is None
    with pytest.raises(ValueError, match="no centroids"):
        model.predict(x[:8])
    emb = model.transform(x[:8])
    assert emb.shape == (8, BASE["n_clusters"])


def test_fit_accepts_explicit_plans(blobs):
    """SCRBModel.fit under an explicit host_chunked plan matches the
    config-derived plan (same executor path, same labels)."""
    x, _ = blobs
    cfg = SCRBConfig(**BASE, chunk_size=200)
    plan = ExecutionPlan(residency="host_chunked", chunk_size=200)
    via_plan = SCRBModel.fit(x, cfg, plan=plan)
    via_cfg = SCRBModel.fit(x, cfg)
    np.testing.assert_array_equal(via_plan.fit_result.labels,
                                  via_cfg.fit_result.labels)
    np.testing.assert_array_equal(via_plan.predict(x), via_cfg.predict(x))


def test_round_to_bucket_grid():
    assert round_to_bucket(1) == BUCKET_GRID[0]
    for b in BUCKET_GRID:
        assert round_to_bucket(b) == b          # exact sizes stay put
        assert round_to_bucket(b - 1) == b
    top = BUCKET_GRID[-1]
    assert round_to_bucket(top + 1) == 2 * top  # above the grid: top-multiples
    assert round_to_bucket(3 * top - 1) == 3 * top
    # multiple_of lifts for mesh sharding
    assert round_to_bucket(100, multiple_of=3) % 3 == 0
    assert round_to_bucket(100, multiple_of=3) >= round_to_bucket(100)
    with pytest.raises(ValueError):
        round_to_bucket(0)


def test_bucket_padded_predict_bit_identical(blobs):
    """The serving satellite: any ``batch_size`` is rounded to the bucket
    grid and chunks are zero-padded to their bucket — every OOS op is
    row-local, so labels AND embeddings must be *bit*-identical to the
    unpadded exact-shape path, ragged tail included."""
    x, _ = blobs
    model = SCRBModel.fit(x, SCRBConfig(**BASE))
    want = model.predict(x)                       # legacy unpadded path
    want_emb = model.transform(x)
    for bs in (64, 100, 300, 799):                # off-grid sizes round up
        np.testing.assert_array_equal(model.predict(x, batch_size=bs), want)
    np.testing.assert_array_equal(model.transform(x, batch_size=100),
                                  want_emb)
    # ragged single chunk smaller than any bucket
    np.testing.assert_array_equal(model.predict(x[:17], batch_size=64),
                                  want[:17])


def _three_step_oos(fm, feats, dual, m, laplacian):
    """The out-of-sample projection as three separate steps: the degree by
    its own gather of the bin counts, the row scale, then the projection."""
    deg = graph.degrees_from_counts(feats, dual)
    if laplacian:
        scale = 1.0 / jnp.sqrt(fm.n_grids * jnp.maximum(deg, 1e-8))
    else:
        scale = jnp.full_like(deg, 1.0 / jnp.sqrt(jnp.float32(fm.n_grids)))
    return ops.z_matmul(feats, m, scale, d_g=fm.d_g, impl=fm.impl), deg


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("laplacian", [True, False], ids=["lap", "plain"])
@pytest.mark.parametrize("k", [1, 10, 15, 16])
def test_oos_project_fused_degree_matches_take_sum(k, laplacian, impl):
    """``RBMap.oos_project`` reads each row's degree from the projection's
    gather ([M | dual] in one ``z_matmul``). Against the three-step formula
    it gives exactly the take-sum degree, the same embedding bit for bit on
    the same route (float32 rounding across routes) and the same labels —
    K + 1 inside the 8-row pad (K = 10, 15) or one tile past it (K = 16),
    with a row whose training bins were all empty (degree 0, clamped)."""
    r, d_g, n = 8, 32, 40
    rng = np.random.default_rng(k)
    feats = jnp.asarray(np.arange(r) * d_g
                        + rng.integers(1, d_g, (n, r)), jnp.int32)
    feats = feats.at[0].set(jnp.arange(r) * d_g)        # bin 0 of each grid
    counts = rng.integers(0, 70_000, r * d_g)
    counts[::d_g] = 0                                   # ... is empty
    dual = jnp.asarray(counts, jnp.float32)
    m = jnp.asarray(rng.normal(size=(r * d_g, k)), jnp.float32)
    fm = RBMap(n_grids=r, sigma=1.0, d_g=d_g, impl=impl)

    fused = jax.jit(fm.oos_project, static_argnames="laplacian")
    old = jax.jit(_three_step_oos, static_argnums=(0, 4))
    got = fused(feats, dual, m, laplacian=laplacian)
    want, want_deg = old(fm, feats, dual, m, laplacian)
    deg = jax.jit(fm._sums_and_degrees)(feats, dual, m)[1]
    assert float(want_deg[0]) == 0.0
    np.testing.assert_array_equal(deg, want_deg)
    np.testing.assert_array_equal(got, want)

    emb = row_normalize(got)
    if impl != "xla":
        xla = RBMap(n_grids=r, sigma=1.0, d_g=d_g, impl="xla")
        np.testing.assert_allclose(
            emb, row_normalize(xla.oos_project(feats, dual, m,
                                               laplacian=laplacian)),
            rtol=1e-6, atol=1e-6)
    cents = row_normalize(jnp.asarray(rng.normal(size=(5, k)), jnp.float32))
    np.testing.assert_array_equal(
        ops.kmeans_assign(emb, cents, impl=impl)[0],
        ops.kmeans_assign(row_normalize(want), cents, impl=impl)[0])


def test_load_v1_artifact_compat():
    """A checked-in format_version=1 (int-stamped) artifact keeps loading
    and reproduces its recorded labels — guards the artifact contract
    across format minors and the CI jax-version matrix."""
    model = SCRBModel.load(os.path.join(DATA_DIR, "tiny_model_v1.npz"))
    xq = np.load(os.path.join(DATA_DIR, "tiny_model_v1_x.npy"))
    want = np.load(os.path.join(DATA_DIR, "tiny_model_v1_labels.npy"))
    np.testing.assert_array_equal(model.predict(xq), want)
    assert model.data_dim == xq.shape[1]


def test_load_rejects_unknown_major(blobs, tmp_path):
    x, _ = blobs
    model = SCRBModel.fit(x[:400], SCRBConfig(**BASE))
    path = str(tmp_path / "m.npz")
    model.save(path)
    with np.load(path, allow_pickle=False) as npz:
        arrays = {k: npz[k] for k in npz.files}
    meta = json.loads(bytes(arrays["_meta"].tobytes()).decode("utf-8"))
    assert meta["format_version"].startswith("1.")   # current stamp
    assert meta["data_dim"] == x.shape[1]
    meta["format_version"] = "2.0"
    arrays["_meta"] = np.frombuffer(json.dumps(meta).encode("utf-8"),
                                    np.uint8)
    with open(path, "wb") as f:
        np.savez(f, **arrays)
    with pytest.raises(ValueError, match="format_version='2.0'"):
        SCRBModel.load(path)


@pytest.mark.parametrize("chunk_size", CHUNKINGS)
def test_fit_k_auto_picks_eigengap(blobs, chunk_size):
    """k="auto": n_clusters acts as K_max, the eigengap over the computed
    spectrum picks K (4 well-separated blobs ⇒ 4), and the model/centroids/
    result are all consistently truncated to the chosen K — under both
    residencies."""
    x, y = blobs
    cfg = SCRBConfig(**{**BASE, "n_clusters": 8}, chunk_size=chunk_size)
    model = SCRBModel.fit(x, cfg, k="auto")
    diag = model.fit_result.diagnostics["k_auto"]
    assert diag["k"] == 4 and diag["k_max"] == 8
    assert len(diag["spectrum"]) == 8 and len(diag["gaps"]) == 7
    assert model.config.n_clusters == 4
    assert model.centroids.shape == (4, 4)
    assert np.asarray(model.fit_result.embedding).shape == (x.shape[0], 4)
    assert metrics.accuracy(model.fit_result.labels, y) > 0.95
    pred = model.predict(x, batch_size=chunk_size)
    assert metrics.accuracy(pred, model.fit_result.labels) >= 0.99


def test_fit_k_overrides_and_auto_validation(blobs):
    x, _ = blobs
    m = SCRBModel.fit(x, SCRBConfig(**BASE), k=3)
    assert m.config.n_clusters == 3
    assert m.centroids.shape[0] == 3
    with pytest.raises(ValueError, match="k must be"):
        SCRBModel.fit(x, SCRBConfig(**BASE), k="anto")
    with pytest.raises(ValueError, match="K_max"):
        SCRBModel.fit(x, SCRBConfig(**{**BASE, "n_clusters": 2}), k="auto")
    with pytest.raises(ValueError, match="compressive"):
        SCRBModel.fit(x, SCRBConfig(**{**BASE, "n_clusters": 8},
                                    solver="compressive"), k="auto")
    from repro.core import PartitionOptions
    with pytest.raises(ValueError, match="partitioned"):
        SCRBModel.fit(x, SCRBConfig(**{**BASE, "n_clusters": 8},
                                    partition=PartitionOptions(
                                        n_partitions=2)), k="auto")


def test_dense_feature_map_model_roundtrip(blobs, tmp_path):
    """The fitted-model API is registry-generic: a Nyström-map model (the
    standard Nyström out-of-sample extension) predicts its own fit labels
    and round-trips through save/load bit-identically."""
    from repro.core import featuremap
    x, y = blobs
    cfg = SCRBConfig(n_clusters=4, n_grids=128, sigma=1.5,
                     kmeans_replicates=2, seed=0)
    fm = featuremap.make_feature_map("nystrom", rank=128, sigma=1.5)
    model = SCRBModel.fit(x, cfg, plan=ExecutionPlan(feature_map=fm))
    assert metrics.accuracy(model.fit_result.labels, y) > 0.9
    pred = model.predict(x)
    assert metrics.accuracy(pred, model.fit_result.labels) >= 0.99
    path = str(tmp_path / "nys.npz")
    model.save(path)
    np.testing.assert_array_equal(SCRBModel.load(path).predict(x), pred)
