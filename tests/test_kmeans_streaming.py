"""Out-of-core k-means stages: chunked row normalization, streaming k-means
parity against the in-core solver, the mini-batch seed-pool clamp, and the
fused assignment-statistics kernel wrapper.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import metrics, streaming
from repro.core.kmeans import (
    kmeans, minibatch_kmeans, row_normalize, row_normalize_chunks,
    streaming_kmeans,
)
from repro.data.synthetic import make_blobs
from repro.kernels import ops


def test_row_normalize_chunks_bit_identical():
    """Row normalization is row-local ⇒ the chunked result equals the
    single-shot one for any chunking, prefetch on or off. XLA may pick a
    different reduction order for the row norm at a different row count,
    so equality is to a few float32 ulp, not bitwise."""
    rng = np.random.default_rng(0)
    u = rng.normal(size=(503, 6)).astype(np.float32)
    want = np.asarray(row_normalize(jnp.asarray(u)))
    for sizes in (64, 100, 503, (200, 200, 103)):
        for prefetch in (True, False):
            cd = streaming.ChunkedDense.from_array(u, sizes)
            got = row_normalize_chunks(cd, prefetch=prefetch)
            assert got.chunk_sizes == cd.chunk_sizes
            np.testing.assert_array_max_ulp(got.to_array(), want, maxulp=4)


def test_streaming_kmeans_agrees_with_kmeans_on_blobs():
    """Label agreement (ARI ≥ 0.95) between the chunk-streamed k-means and
    the in-core Lloyd solver on well-separated blobs."""
    x, y = make_blobs(2000, 8, 5, seed=3, spread=0.08)
    ref = kmeans(jax.random.PRNGKey(0), jnp.asarray(x), 5, n_replicates=4)
    cd = streaming.ChunkedDense.from_array(x, 512)
    res = streaming_kmeans(jax.random.PRNGKey(0), cd, 5,
                           n_steps=40, n_replicates=4, impl="xla")
    assert res.labels.shape == (2000,)
    assert res.labels.dtype == np.int32
    ari = metrics.adjusted_rand_index(res.labels, np.asarray(ref.labels))
    assert ari >= 0.95
    assert metrics.adjusted_rand_index(res.labels, y) >= 0.95


def test_streaming_kmeans_accepts_plain_chunk_list():
    x, y = make_blobs(600, 4, 3, seed=1, spread=0.05)
    res = streaming_kmeans(jax.random.PRNGKey(2), [x[:250], x[250:]], 3,
                           n_steps=20, n_replicates=2, impl="xla")
    assert metrics.adjusted_rand_index(res.labels, y) >= 0.95
    assert res.centroids.shape == (3, 4)
    assert float(res.inertia) >= 0.0


def test_streaming_kmeans_rejects_k_above_n():
    with pytest.raises(ValueError, match="exceeds"):
        streaming_kmeans(jax.random.PRNGKey(0),
                         [np.zeros((4, 2), np.float32)], 9)


def test_minibatch_kmeans_tiny_input_pool_clamp():
    """The k-means++ seed pool is clamped to n: tiny inputs where
    max(4k, 64) > n must not crash choice(replace=False)."""
    x, _ = make_blobs(20, 3, 3, seed=0, spread=0.05)
    res = minibatch_kmeans(jax.random.PRNGKey(0), jnp.asarray(x), 3,
                           batch_size=8, n_steps=10, impl="xla")
    assert res.labels.shape == (20,)
    assert int(jnp.max(res.labels)) < 3


def test_reservoir_sample_covers_stream():
    """Reservoir pool rows all come from the stream; a pool as large as the
    stream reproduces it exactly (up to order)."""
    from repro.core.kmeans import _reservoir_sample_chunks
    rng = np.random.default_rng(7)
    chunks = [rng.normal(size=(s, 3)).astype(np.float32) for s in (40, 35, 25)]
    allrows = np.concatenate(chunks)
    pool = _reservoir_sample_chunks(chunks, 100, np.random.default_rng(0))
    np.testing.assert_array_equal(np.sort(pool, axis=0),
                                  np.sort(allrows, axis=0))
    small = _reservoir_sample_chunks(chunks, 16, np.random.default_rng(1))
    # every sampled row is a row of the stream
    matches = (small[:, None, :] == allrows[None, :, :]).all(-1).any(1)
    assert matches.all()


def test_kmeans_assign_stats_matches_assign():
    """The fused stats helper agrees with kmeans_assign + segment reductions."""
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(200, 5)).astype(np.float32))
    cents = jnp.asarray(rng.normal(size=(4, 5)).astype(np.float32))
    labels, counts, sums, inertia = ops.kmeans_assign_stats(x, cents,
                                                            impl="xla")
    want_labels, want_dists = ops.kmeans_assign(x, cents, impl="xla")
    assert np.array_equal(np.asarray(labels), np.asarray(want_labels))
    np.testing.assert_allclose(float(inertia), float(jnp.sum(want_dists)),
                               rtol=1e-6)
    for c in range(4):
        sel = np.asarray(labels) == c
        assert counts[c] == sel.sum()
        np.testing.assert_allclose(np.asarray(sums)[c],
                                   np.asarray(x)[sel].sum(0),
                                   rtol=1e-5, atol=1e-5)
