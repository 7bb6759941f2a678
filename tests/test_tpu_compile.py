"""Ahead-of-time compiles of the Pallas kernels for a TPU v5e chip that is
described, not attached, at the widths real fits use.

Interpret mode (every other kernel test) accepts tilings and VMEM budgets
the chip's compiler refuses; compiling here catches those without a chip.
The topology is described inside a module fixture — never at import —
because only one process may load the TPU compiler library at a time.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

POKER = (1_025_010, 10)      # (N, d) of paper Table 1 poker
MNIST = (70_000, 780)        # the widest Table 1 input
COVTYPE = (581_012, 54)      # Table 1 covtype-mult (K 7)
ACOUSTIC = (98_528, 50)      # Table 1 acoustic (K 3)
R, K = 256, 16


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")    # no compiler logs in /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # compiles for a described chip cannot be read back from the
        # persistent cache, so keep them out of it
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture
def chip_route(monkeypatch):
    """Steer the ops wrappers to lower Mosaic kernels, as they do when the
    process runs on a TPU (here JAX's own backend is the CPU)."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)


def _compile(sharding, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


@pytest.mark.parametrize("n,d", [POKER, MNIST, COVTYPE, ACOUSTIC],
                         ids=["poker", "mnist", "covtype-mult", "acoustic"])
def test_rb_binning_compiles(one_chip, chip_route, n, d):
    def fn(x, w, b, a, c):
        return ops.rb_binning(x, w, b, a, c, d_g=4096, impl="pallas")
    assert _compile(one_chip, fn, ((n, d), jnp.float32),
                    ((R, d), jnp.float32), ((R, d), jnp.float32),
                    ((R, d), jnp.uint32), ((R,), jnp.uint32)) == 1


# "oos" is the serving projection with the degree fused into its gather
# (``RBMap.oos_project``), at the serve cells' top bucket and hash widths
# (poker d_g 512, mnist d_g 2048) and K = 10, so the kernel gathers K + 1
# columns. The z gather stacks an f32 factor's three bf16 terms on the row
# axis: "z_k48" takes them past one 128-row MXU tile (144 rows), and
# "z_bf16" gathers a bf16 factor as it is.
@pytest.mark.parametrize("product,d_g", [
    (p, w) for p in ("z", "zt", "gram") for w in (256, 4096)] + [
    ("oos", 512), ("oos", 2048), ("z_k48", 512), ("z_bf16", 2048)],
    ids=lambda v: str(v))
def test_ell_products_compile(one_chip, chip_route, product, d_g):
    n = POKER[0]
    d = R * d_g
    idx = ((n, R), jnp.int32)
    scale = ((n,), jnp.float32)
    if product == "oos":
        count = _compile_oos(one_chip, d_g, 10)
    elif product in ("z", "z_k48", "z_bf16"):
        k = 48 if product == "z_k48" else K
        dtype = jnp.bfloat16 if product == "z_bf16" else jnp.float32
        count = _compile(one_chip, lambda i, v, s: ops.z_matmul(
            i, v, s, d_g=d_g, impl="pallas"), idx, ((d, k), dtype), scale)
    elif product == "zt":
        count = _compile(one_chip, lambda i, u, s: ops.zt_matmul(
            i, u, s, d, d_g=d_g, impl="pallas"), idx, ((n, K), jnp.float32),
            scale)
    else:
        count = _compile(one_chip, lambda i, u, s: ops.gram_matmul(
            i, u, s, d, d_g=d_g, impl="pallas"), idx, ((n, K), jnp.float32),
            scale)
    # the Gram mat-vec is one fused kernel while its (D, K) intermediate
    # fits GRAM_FUSE_VMEM_BYTES (d_g = 256), else the zt + z pair
    want = 2 if product == "gram" and d_g == 4096 else 1
    assert count == want


def _compile_oos(sharding, d_g: int, k: int) -> int:
    """The fused serving projection over [M | dual] (K + 1 columns) at the
    top bucket; returns its kernel count."""
    from repro.core.featuremap import RBMap
    fm = RBMap(n_grids=R, sigma=1.0, d_g=d_g, impl="pallas")
    d = R * d_g
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in
            (((4096, R), jnp.int32), ((d,), jnp.float32),
             ((d, k), jnp.float32))]
    text = jax.jit(lambda i, dual, m: fm.oos_project(
        i, dual, m, laplacian=True)).lower(*args).compile().as_text()
    assert " gather(" not in text     # no XLA gather of the degree dual
    return text.count('custom_call_target="tpu_custom_call"')


# the zoo's other members: K + 1 = 4 (acoustic, d_g 4096) is part of one
# 8-row tile, K + 1 = 8 (covtype-mult, d_g 2048) exactly one
@pytest.mark.parametrize("k,d_g", [(3, 4096), (7, 2048)],
                         ids=["acoustic", "covtype-mult"])
def test_fused_projection_compiles_at_small_k(one_chip, chip_route, k, d_g):
    assert _compile_oos(one_chip, d_g, k) == 1


def test_fused_gram_kernel_compiles_at_vmem_budget(one_chip):
    """The largest fused intermediate the budget admits at R = 256, K = 16
    (d_g = 512, 8 MiB) fits the compiler's scoped VMEM."""
    from repro.kernels import ell_spmm
    d_g = 512
    assert ell_spmm.gram_vmem_bytes(R, K, d_g) <= ops.GRAM_FUSE_VMEM_BYTES
    assert ell_spmm.gram_vmem_bytes(R, K, 2 * d_g) > ops.GRAM_FUSE_VMEM_BYTES
    n = 1_025_024
    assert _compile(one_chip, lambda i, u, s: ell_spmm.gram_matmul_pallas(
        i, u, s, d_g=d_g, block_n=128, block_r=ell_spmm.pick_block_r(R)),
        ((R, n), jnp.int32), ((K, n), jnp.float32),
        ((1, n), jnp.float32)) == 1


@pytest.mark.parametrize("n,k", [(POKER[0], 10), (COVTYPE[0], 7),
                                 (ACOUSTIC[0], 3)],
                         ids=["poker", "covtype-mult", "acoustic"])
def test_kmeans_assign_compiles(one_chip, chip_route, n, k):
    assert _compile(one_chip, lambda x, c: ops.kmeans_assign(
        x, c, impl="pallas"), ((n, k), jnp.float32),
        ((k, k), jnp.float32)) == 1
