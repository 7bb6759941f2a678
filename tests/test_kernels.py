"""Per-kernel allclose sweeps: Pallas (interpret=True) and the XLA
production fallback against the pure-jnp oracles in kernels/ref.py."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def _rb_inputs(key, n, d, r, d_g):
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (n, d), jnp.float32) * 2.0
    widths = jax.random.gamma(ks[1], 2.0, (r, d), dtype=jnp.float32) * 0.5 + 1e-3
    biases = jax.random.uniform(ks[2], (r, d), jnp.float32) * widths
    hash_a = (
        jax.random.randint(ks[3], (r, d), 0, 2**31 - 1).astype(jnp.uint32)
        * jnp.uint32(2) + jnp.uint32(1))
    hash_c = jax.random.randint(ks[4], (r,), 0, 2**31 - 1).astype(jnp.uint32)
    return x, widths, biases, hash_a, hash_c


@pytest.mark.parametrize("n,d,r,d_g", [
    (64, 2, 8, 64),
    (100, 3, 16, 128),     # n not divisible by tile
    (256, 7, 4, 256),
    (513, 16, 32, 512),    # odd n, wide d
    (40, 150, 8, 64),      # d over one 128-wide block: hashes accumulate
])
@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_rb_binning_matches_ref(n, d, r, d_g, impl):
    inputs = _rb_inputs(jax.random.PRNGKey(n + r), n, d, r, d_g)
    want = ref.rb_binning_ref(*inputs, d_g)
    got = ops.rb_binning(*inputs, d_g=d_g, impl=impl)
    assert got.shape == (n, r) and got.dtype == jnp.int32
    assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("n,r,d_g,k", [
    (64, 4, 64, 8),
    (100, 8, 128, 3),      # ragged n
    (256, 16, 64, 32),
    # no multiple of 8 divides r -> one block of all R grids
    (300, 12, 256, 5),
])
@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_z_matmul_matches_ref(n, r, d_g, k, impl, dtype):
    key = jax.random.PRNGKey(n * r + k)
    d = r * d_g
    idx = (
        jax.random.randint(key, (n, r), 0, d_g)
        + jnp.arange(r, dtype=jnp.int32)[None, :] * d_g)
    v = jax.random.normal(jax.random.PRNGKey(1), (d, k), jnp.float32).astype(dtype)
    s = jax.random.uniform(jax.random.PRNGKey(2), (n,), jnp.float32) + 0.5
    want = ref.z_matmul_ref(idx, v.astype(jnp.float32), s)
    got = ops.z_matmul(idx, v, s, d_g=d_g, impl=impl)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want), rtol=tol, atol=tol * r)


def _wide_values(key, shape):
    """Values of both signs whose magnitudes span 1e-6..1e6."""
    k1, k2, k3 = jax.random.split(key, 3)
    mag = 10.0 ** jax.random.uniform(k1, shape, jnp.float32, -6.0, 6.0)
    sign = jnp.where(jax.random.bernoulli(k2, 0.5, shape), 1.0, -1.0)
    return mag * sign * jax.random.uniform(k3, shape, jnp.float32, 1.0, 2.0)


def test_bf16_terms_sum_back_exactly():
    """The kernel's three-term bf16 split: every term is a bf16 value and
    (hi + mid) + lo rebuilds v bit for bit, but for −0, which comes back as
    +0 (v + 0)."""
    from repro.kernels import ell_spmm
    v = jnp.concatenate([
        _wide_values(jax.random.PRNGKey(0), (1 << 16,)),
        jnp.arange(1 << 17, dtype=jnp.float32),
        jnp.array([0.0, -0.0], jnp.float32)])
    terms = [np.asarray(t) for t in jax.jit(ell_spmm.bf16_terms)(v)]
    for t in terms:
        assert t.dtype == np.float32
        assert np.array_equal(t.astype(jnp.bfloat16).astype(np.float32)
                              .view(np.uint32), t.view(np.uint32))
    hi, mid, lo = terms
    assert np.array_equal(((hi + mid) + lo).view(np.uint32),
                          (np.asarray(v) + np.float32(0)).view(np.uint32))


def _z_matmul_highest(idx, v, s, d_g):
    """The projection as the one-hot kernel formed it at Precision.HIGHEST:
    per grid and bin chunk an f32 one-hot contracted in f32, summed over a
    block's grids, then over (grid block, bin chunk) in the kernel's order,
    and scaled last."""
    from repro.kernels import ell_spmm
    n, r = idx.shape
    dc, block_r = ell_spmm.dg_chunk(d_g), ell_spmm.pick_block_r(r)
    y = jnp.zeros((n, v.shape[1]), jnp.float32)
    for g in range(0, r, block_r):
        for c in range(0, d_g, dc):
            acc = jnp.zeros_like(y)
            for j in range(g, g + block_r):
                base = j * d_g + c
                onehot = (idx[:, j:j + 1] - base == jnp.arange(dc)).astype(
                    jnp.float32)
                acc = acc + jnp.dot(onehot, v[base:base + dc],
                                    precision=jax.lax.Precision.HIGHEST)
            y = y + acc
    return y * s[:, None]


# K + 1 columns (the factor and the degree dual): 4 and 8 fill one 8-row
# tile, 11 two; d_g 512, 1024, 2048 and 4096 are 1, 2, 4 and 8 bin chunks,
# the last two the widths the zoo serves (mnist, covtype-mult; acoustic)
@pytest.mark.parametrize("k1", [4, 8, 11])
@pytest.mark.parametrize("d_g", [512, 1024, 2048, 4096])
def test_z_matmul_bit_identical_to_highest(k1, d_g):
    """The bf16 split gather equals the f32 HIGHEST one-hot product bit for
    bit on a factor spanning 1e-6..1e6 with an integer dual column."""
    n, r = 256, 8
    idx = (jax.random.randint(jax.random.PRNGKey(k1), (n, r), 0, d_g)
           + jnp.arange(r, dtype=jnp.int32)[None, :] * d_g)
    dual = jax.random.randint(jax.random.PRNGKey(1), (r * d_g, 1), 0,
                              1 << 17).astype(jnp.float32)
    v = jnp.concatenate(
        [_wide_values(jax.random.PRNGKey(2), (r * d_g, k1 - 1)), dual], 1)
    s = jax.random.uniform(jax.random.PRNGKey(3), (n,), jnp.float32) + 0.5
    want = np.asarray(_z_matmul_highest(idx, v, s, d_g))
    got = np.asarray(ops.z_matmul(idx, v, s, d_g=d_g, impl="pallas"))
    assert np.array_equal(got.view(np.uint32), want.view(np.uint32))


def test_z_matmul_gather_stays_one_pass_under_highest():
    """Under the fit's default_matmul_precision("highest") the gather is
    still one DEFAULT-precision pass of the stacked terms: 3 × 16 rows."""
    from repro.kernels import ell_spmm
    args = (jnp.zeros((8, 128), jnp.int32), jnp.zeros((8, 1, 16, 512)),
            jnp.ones((1, 128)))
    with jax.default_matmul_precision("highest"):
        text = str(jax.make_jaxpr(functools.partial(
            ell_spmm.z_matmul_pallas, d_g=512, interpret=True))(*args))
    dots = [d.split("preferred_element_type")[0]
            for d in text.split(" = dot_general[")[1:]]
    assert len(dots) == 1 and ":f32[48,128] = dot_general[" in text
    assert "precision=(Precision.DEFAULT, Precision.DEFAULT)" in dots[0]


@pytest.mark.parametrize("n,r,d_g,k", [
    (64, 4, 64, 8),
    (100, 8, 128, 3),
    (256, 16, 64, 32),
])
@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_zt_matmul_matches_ref(n, r, d_g, k, impl):
    key = jax.random.PRNGKey(n + r + k)
    d = r * d_g
    idx = (
        jax.random.randint(key, (n, r), 0, d_g)
        + jnp.arange(r, dtype=jnp.int32)[None, :] * d_g)
    u = jax.random.normal(jax.random.PRNGKey(3), (n, k), jnp.float32)
    s = jax.random.uniform(jax.random.PRNGKey(4), (n,), jnp.float32) + 0.5
    want = ref.zt_matmul_ref(idx, u, s, d)
    got = ops.zt_matmul(idx, u, s, d, d_g=d_g, impl=impl)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("n,r,d_g", [
    (64, 4, 64),
    (101, 8, 2),           # non-divisible N, minimal d_g
    (100, 8, 1024),        # ragged N, wide d_g
])
@pytest.mark.parametrize("impl", ["xla", "pallas", "auto"])
def test_bin_counts_matches_exact(n, r, d_g, impl):
    """Exact int32 occupancies on every dispatch path (auto falls back to
    xla on CPU CI; pallas runs in interpret mode)."""
    key = jax.random.PRNGKey(n + r)
    d = r * d_g
    idx = (
        jax.random.randint(key, (n, r), 0, d_g)
        + jnp.arange(r, dtype=jnp.int32)[None, :] * d_g)
    want = np.bincount(np.asarray(idx).reshape(-1), minlength=d)
    got = ops.bin_counts(idx, d=d, d_g=d_g, impl=impl)
    assert got.dtype == jnp.int32
    assert np.array_equal(np.asarray(got), want)


@pytest.mark.parametrize("n,r,d_g,k,chunk", [
    (101, 8, 2, 3, 32),    # non-divisible N, minimal d_g, ragged chunks
    (100, 4, 128, 5, 64),  # ragged last chunk
    (256, 8, 512, 4, 256),  # single chunk == whole matrix
    (130, 4, 64, 2, 7),    # many tiny ragged chunks
])
@pytest.mark.parametrize("impl", ["xla", "pallas", "auto"])
def test_chunked_matvecs_impl_parity(n, r, d_g, k, chunk, impl):
    """The traceable chunked products match the references through every
    dispatch path, so streaming + impl="auto" fallback is covered on CPU."""
    from repro.core import streaming
    key = jax.random.PRNGKey(n * r + chunk)
    d = r * d_g
    idx = (
        jax.random.randint(key, (n, r), 0, d_g)
        + jnp.arange(r, dtype=jnp.int32)[None, :] * d_g)
    s = jax.random.uniform(jax.random.PRNGKey(1), (n,), jnp.float32) + 0.5
    u = jax.random.normal(jax.random.PRNGKey(2), (n, k), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(3), (d, k), jnp.float32)
    want_q = ref.zt_matmul_ref(idx, u, s, d)
    got_q = streaming.chunked_zt_matmul(idx, u, s, d=d, d_g=d_g,
                                        chunk_size=chunk, impl=impl)
    np.testing.assert_allclose(np.asarray(got_q), np.asarray(want_q),
                               rtol=3e-5, atol=3e-5)
    want_y = ref.z_matmul_ref(idx, v, s)
    got_y = streaming.chunked_z_matmul(idx, v, s, d_g=d_g,
                                       chunk_size=chunk, impl=impl)
    np.testing.assert_allclose(np.asarray(got_y), np.asarray(want_y),
                               rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_chunked_ell_host_path_impl_parity(impl):
    """The host-streaming ChunkedELL gram mat-vec agrees across kernel
    dispatch paths (pallas interpret vs xla) on a ragged chunking."""
    from repro.core import streaming
    n, r, d_g, k = 120, 4, 128, 3
    d = r * d_g
    idx = (
        jax.random.randint(jax.random.PRNGKey(9), (n, r), 0, d_g)
        + jnp.arange(r, dtype=jnp.int32)[None, :] * d_g)
    s = jax.random.uniform(jax.random.PRNGKey(10), (n,), jnp.float32) + 0.5
    u = jax.random.normal(jax.random.PRNGKey(11), (n, k), jnp.float32)
    chunked = streaming.ChunkedELL.from_dense(
        np.asarray(idx), np.asarray(s), 50, d=d, d_g=d_g, impl=impl)
    want = ref.z_matmul_ref(idx, ref.zt_matmul_ref(idx, u, s, d), s)
    np.testing.assert_allclose(np.asarray(chunked.gram_matvec(u)),
                               np.asarray(want), rtol=3e-5, atol=3e-5)


def test_zt_z_adjoint():
    """⟨Z u, v⟩ == ⟨u, Zᵀ v⟩ — the two kernels implement adjoint maps."""
    key = jax.random.PRNGKey(0)
    n, r, d_g, k = 128, 8, 64, 4
    d = r * d_g
    idx = (
        jax.random.randint(key, (n, r), 0, d_g)
        + jnp.arange(r, dtype=jnp.int32)[None, :] * d_g)
    s = jax.random.uniform(jax.random.PRNGKey(1), (n,)) + 0.1
    u = jax.random.normal(jax.random.PRNGKey(2), (n, k))
    v = jax.random.normal(jax.random.PRNGKey(3), (d, k))
    zu = ops.z_matmul(idx, v, s, d_g=d_g, impl="xla")     # (n, k)
    ztu = ops.zt_matmul(idx, u, s, d, d_g=d_g, impl="xla")  # (d, k)
    lhs = float(jnp.sum(zu * u))
    rhs = float(jnp.sum(ztu * v))
    assert abs(lhs - rhs) < 1e-2 * max(abs(lhs), 1.0)


@pytest.mark.parametrize("n,d,k", [
    (64, 2, 3),
    (1000, 8, 16),
    (1025, 16, 7),
])
@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_kmeans_assign_matches_ref(n, d, k, impl):
    x = jax.random.normal(jax.random.PRNGKey(n), (n, d), jnp.float32)
    c = jax.random.normal(jax.random.PRNGKey(d), (k, d), jnp.float32)
    want_l, want_d = ref.kmeans_assign_ref(x, c)
    got_l, got_d = ops.kmeans_assign(x, c, impl=impl)
    assert np.array_equal(np.asarray(got_l), np.asarray(want_l))
    np.testing.assert_allclose(np.asarray(got_d), np.asarray(want_d), rtol=1e-4, atol=1e-4)


# --------------------------------------------------------------------------
# Shared tile-size picker + per-op block overrides (ExecutionPlan.block_rows)
# --------------------------------------------------------------------------

def test_pick_block_rows_respects_n_and_cap():
    """The picker returns a power of two ≤ the cap that never tiles far past
    the data (the old stub ignored n entirely and returned the cap)."""
    assert ops.pick_block_rows("rb_binning", 1_000_000) == 256    # cap wins
    assert ops.pick_block_rows("rb_binning", 100) == 128          # next pow2
    assert ops.pick_block_rows("rb_binning", 3) == 8              # sublane min
    assert ops.pick_block_rows("kmeans_assign", 20) == 32         # not 1024
    assert ops.pick_block_rows("ell_spmm", 500, override=64) == 64
    with pytest.raises(ValueError, match="power of two"):
        ops.pick_block_rows("ell_spmm", 100, override=100)


def test_block_rows_override_context():
    with ops.block_rows_overrides({"ell_spmm": 32}):
        assert ops.pick_block_rows("ell_spmm", 10_000) == 32
        assert ops.pick_block_rows("rb_binning", 10_000) == 256   # untouched
    assert ops.pick_block_rows("ell_spmm", 10_000) == 128         # restored


def test_block_rows_change_tiling_not_results():
    """Pallas wrappers produce identical results under any block cap —
    padding makes every tile size valid."""
    key = jax.random.PRNGKey(5)
    r, d_g, k = 8, 64, 3
    d = r * d_g
    idx = (jax.random.randint(key, (100, r), 0, d_g)
           + jnp.arange(r, dtype=jnp.int32)[None, :] * d_g)
    v = jax.random.normal(jax.random.PRNGKey(1), (d, k), jnp.float32)
    s = jax.random.uniform(jax.random.PRNGKey(2), (100,), jnp.float32) + 0.5
    want = np.asarray(ops.z_matmul(idx, v, s, d_g=d_g, impl="pallas"))
    got = np.asarray(ops.z_matmul(idx, v, s, d_g=d_g, impl="pallas",
                                  block_rows=16))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    with ops.block_rows_overrides({"ell_spmm": 16}):
        got_ctx = np.asarray(ops.z_matmul(idx, v, s, d_g=d_g, impl="pallas"))
    np.testing.assert_allclose(got_ctx, want, rtol=1e-6, atol=1e-6)


def test_bin_counts_pallas_is_eager_only():
    """The Pallas bin_counts route slices rows in a host loop; under jit it
    must fail loudly instead of silently unrolling (impl='xla' traces)."""
    idx = jnp.zeros((16, 4), jnp.int32)
    with pytest.raises(TypeError, match="eager-only"):
        jax.jit(lambda i: ops.bin_counts(i, d=64, d_g=16, impl="pallas"))(idx)
    out = jax.jit(lambda i: ops.bin_counts(i, d=64, d_g=16, impl="xla"))(idx)
    assert int(out[0]) == 64


def _gram_inputs(n, r, d_g, k, seed=0):
    d = r * d_g
    key = jax.random.PRNGKey(seed)
    idx = (
        jax.random.randint(key, (n, r), 0, d_g)
        + jnp.arange(r, dtype=jnp.int32)[None, :] * d_g)
    u = jax.random.normal(jax.random.PRNGKey(seed + 1), (n, k), jnp.float32)
    s = jax.random.uniform(jax.random.PRNGKey(seed + 2), (n,), jnp.float32) + 0.5
    return idx, u, s, d


@pytest.mark.parametrize("n,r,d_g,k", [
    (64, 4, 64, 8),
    (100, 8, 128, 3),      # ragged n -> padded tiles
    (300, 12, 64, 5),      # r % 4 != 0
])
@pytest.mark.parametrize("impl", ["xla", "pallas", "auto"])
def test_gram_matmul_matches_ref(n, r, d_g, k, impl):
    """The fused Ẑ(Ẑᵀu) Gram mat-vec agrees with the composed oracles on
    every dispatch route (xla composition, fused Pallas, auto)."""
    idx, u, s, d = _gram_inputs(n, r, d_g, k, seed=n + r + k)
    want = ref.z_matmul_ref(idx, ref.zt_matmul_ref(idx, u, s, d), s)
    got = ops.gram_matmul(idx, u, s, d, d_g=d_g, impl=impl)
    assert got.shape == (n, k)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5 * r)


def test_gram_matmul_vmem_fallback(monkeypatch):
    """When D·K·4 blows the VMEM budget the Pallas route must silently
    compose the two single-pass kernels — identical math."""
    idx, u, s, d = _gram_inputs(64, 4, 64, 8, seed=7)
    want = np.asarray(ops.gram_matmul(idx, u, s, d, d_g=64, impl="xla"))
    monkeypatch.setattr(ops, "GRAM_FUSE_VMEM_BYTES", 16)
    got = np.asarray(ops.gram_matmul(idx, u, s, d, d_g=64, impl="pallas"))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)
