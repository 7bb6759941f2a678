"""Parity suite over the ExecutionPlan grid.

One stage-graph executor drives all three entry points; these tests pin that
every plan point — {single-shot, host-chunked} × {xla, pallas-interpret} ×
{prefetch on/off}, plus mesh plans on 2 forced CPU devices — produces the
same labels (up to permutation) and the same embedding (up to per-column
sign) as the seed single-shot reference, and that the mesh k-means consumes
the embedding shard-chunk-wise (peak device residency O(shard_chunk), not
O(N/shards)).
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import SCRBConfig, executor, metrics, sc_rb, spectral_embed
from repro.core.executor import ExecutionPlan, plan_from_config
from repro.core.rowmatrix import DeviceRows, HostChunkedRows
from repro.data.synthetic import make_rings

# Same (N, R, d_g) as tests/test_pipeline.test_scrb_smoke_fast and the
# streaming e2e case so the jitted stages compile once per pytest session.
BASE = dict(n_clusters=2, n_grids=96, sigma=0.15, d_g=4096,
            solver_tol=1e-3, kmeans_replicates=2, seed=0)


@pytest.fixture(scope="module")
def data():
    return make_rings(600, 2, seed=0)


@pytest.fixture(scope="module")
def reference(data):
    """Seed single-shot reference: placement=single, residency=device, xla."""
    x, y = data
    res = sc_rb(jnp.asarray(x), SCRBConfig(**BASE, impl="xla"))
    assert metrics.accuracy(res.labels, y) > 0.95
    return res


def _embeddings_match(ref, got, atol=5e-2):
    """Column-wise equality up to sign (eigenvector gauge freedom)."""
    for j in range(ref.shape[1]):
        dot = float(np.dot(ref[:, j], got[:, j]))
        np.testing.assert_allclose(np.sign(dot) * got[:, j], ref[:, j],
                                   atol=atol)


_GRID = []
for _residency in ("device", "host_chunked"):
    for _prefetch in (True, False):
        if _residency == "device" and not _prefetch:
            continue            # prefetch is a no-op without chunk streaming
        _GRID.append(pytest.param(
            _residency, _prefetch,
            id=f"{_residency}-prefetch{int(_prefetch)}"))


@pytest.mark.parametrize("residency,prefetch", _GRID)
def test_plan_grid_matches_reference(data, reference, residency, prefetch):
    x, y = data
    cfg = SCRBConfig(
        **BASE, impl="xla", prefetch=prefetch,
        chunk_size=256 if residency == "host_chunked" else None)
    res = sc_rb(jnp.asarray(x), cfg)
    assert res.diagnostics["plan"]["residency"] == residency
    assert metrics.accuracy(res.labels, reference.labels) >= 0.99
    assert metrics.accuracy(res.labels, y) > 0.95
    _embeddings_match(reference.embedding, res.embedding)
    np.testing.assert_allclose(res.singular_values,
                               reference.singular_values, atol=1e-3)
    if residency == "host_chunked":
        # the streaming plan's integer-count degrees agree with the
        # single-shot float path (the chunk-invariance guarantee)
        np.testing.assert_allclose(
            [res.diagnostics["degrees_min"], res.diagnostics["degrees_max"]],
            [reference.diagnostics["degrees_min"],
             reference.diagnostics["degrees_max"]], rtol=1e-5)


# pallas-interpret cells run at reduced scale (interpret mode pays per-row
# python overhead at d_g=4096) against their own same-size xla reference
SMALL = dict(n_clusters=2, n_grids=32, sigma=0.15, d_g=512,
             solver_tol=1e-3, kmeans_replicates=2, seed=0)


@pytest.fixture(scope="module")
def small_reference():
    x, _ = make_rings(256, 2, seed=0)
    return x, sc_rb(jnp.asarray(x), SCRBConfig(**SMALL, impl="xla"))


@pytest.mark.parametrize("residency,prefetch", _GRID)
def test_plan_grid_pallas_interpret(small_reference, residency, prefetch):
    """The pallas rows of the plan grid: kernel dispatch is orthogonal to
    placement/residency — identical labels, matching embeddings."""
    x, ref = small_reference
    cfg = SCRBConfig(
        **SMALL, impl="pallas", prefetch=prefetch,
        chunk_size=128 if residency == "host_chunked" else None)
    res = sc_rb(jnp.asarray(x), cfg)
    assert res.diagnostics["plan"]["impl"] == "pallas"
    assert metrics.accuracy(res.labels, ref.labels) >= 0.99
    _embeddings_match(ref.embedding, res.embedding)


def test_device_plan_is_deterministic(data, reference):
    """chunk_size=None re-runs are bit-identical (seed single-shot parity)."""
    x, _ = data
    again = sc_rb(jnp.asarray(x), SCRBConfig(**BASE, impl="xla"))
    assert np.array_equal(again.labels, reference.labels)
    np.testing.assert_array_equal(again.embedding, reference.embedding)


def test_spectral_embed_shares_the_executor_path(data, reference):
    """spectral_embed is the same run stopped at the normalize stage: its
    embedding equals sc_rb's bit-for-bit, it reports stage timings, and it
    still unpacks as the historical (embedding, singular_values) pair."""
    x, _ = data
    cfg = SCRBConfig(**BASE, impl="xla")
    out = spectral_embed(jnp.asarray(x), cfg)
    u, sv = out                                     # tuple-unpack compat
    np.testing.assert_array_equal(np.asarray(u), reference.embedding)
    np.testing.assert_allclose(np.asarray(sv), reference.singular_values)
    for stage in ("rb_features", "degrees", "svd", "normalize"):
        assert stage in out.timer.times and out.timer.times[stage] > 0
    assert "kmeans" not in out.timer.times


def test_plan_validation():
    with pytest.raises(ValueError, match="placement='mesh' requires"):
        ExecutionPlan(placement="mesh")
    with pytest.raises(ValueError, match="requires chunk_size"):
        ExecutionPlan(residency="host_chunked")
    with pytest.raises(ValueError, match="unknown placement"):
        ExecutionPlan(placement="tpu")
    with pytest.raises(ValueError, match="streaming"):
        plan_from_config(SCRBConfig(n_clusters=2, chunk_size=64,
                                    solver="lanczos"))


def test_plan_representation_mapping():
    assert executor.representation(ExecutionPlan()) is DeviceRows
    assert executor.representation(
        ExecutionPlan(residency="host_chunked", chunk_size=8)) \
        is HostChunkedRows
    plan = plan_from_config(SCRBConfig(n_clusters=2))
    assert (plan.placement, plan.residency) == ("single", "device")


def test_rowmatrix_map_reduce_parity(data):
    """map_row_chunks / reduce agree between the device and host-chunked
    representations (the contract the shared stages are written against)."""
    from repro.core import featuremap
    from repro.core.kmeans import row_normalize
    x, _ = data
    cfg = SCRBConfig(**BASE, impl="xla")
    dev_plan = plan_from_config(cfg)
    ch_cfg = SCRBConfig(**BASE, impl="xla", chunk_size=256)
    ch_plan = plan_from_config(ch_cfg)
    import jax
    key = jax.random.PRNGKey(0)
    fm = featuremap.from_config(cfg, impl="xla")
    feats_d = DeviceRows.fit_transform(jnp.asarray(x), fm, cfg, dev_plan, key)
    z_d = DeviceRows.from_features(feats_d, cfg, dev_plan)
    feats_c = HostChunkedRows.fit_transform(np.asarray(x), fm, ch_cfg,
                                            ch_plan, key)
    z_c = HostChunkedRows.from_features(feats_c, ch_cfg, ch_plan)

    u = np.asarray(jax.random.normal(key, (x.shape[0], 3), jnp.float32))
    from repro.core.streaming import ChunkedDense
    uc = ChunkedDense.from_array(u, z_c.store.chunk_sizes)

    # the representations agree on the fitted-model degree dual: the device
    # path keeps float Zᵀ1 from the degree pass (±ulp of the chunked path's
    # exact integer counts)
    np.testing.assert_allclose(z_d.degree_dual(), z_c.degree_dual(),
                               rtol=1e-5)
    # rmatvec with a host-chunked tall operand matches the device rmatvec
    # (the pass SCRBModel.fit materializes the right subspace with)
    np.testing.assert_allclose(
        np.asarray(z_c.rmatvec(uc)), np.asarray(z_d.rmatvec(jnp.asarray(u))),
        rtol=1e-4, atol=1e-5)

    want = np.asarray(z_d.map_row_chunks(row_normalize, jnp.asarray(u)))
    got = z_c.map_row_chunks(row_normalize, uc).to_array()
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)

    sq = lambda acc, c: acc + jnp.sum(c.astype(jnp.float32) ** 2, axis=0)
    want_r = np.asarray(z_d.reduce(sq, jnp.zeros((3,)), jnp.asarray(u)))
    got_r = np.asarray(z_c.reduce(sq, jnp.zeros((3,)), uc))
    np.testing.assert_allclose(got_r, want_r, rtol=1e-5)


# --------------------------------------------------------------------------
# Mesh plans: 2 forced CPU devices in a subprocess (the XLA device-count
# flag must be set before jax initializes and must not leak into other
# tests). Small N keeps this in the fast tier; the full-scale distributed
# quality case stays in tests/test_distributed.py (slow tier).
# --------------------------------------------------------------------------

_MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
os.environ.setdefault("JAX_PLATFORM_NAME", "cpu")
import json
import jax.numpy as jnp, numpy as np
from repro.core import SCRBConfig, executor, metrics, sc_rb
from repro.core.distributed import sc_rb_distributed
from repro.data.synthetic import make_rings
from repro.launch.mesh import make_host_mesh

mesh = make_host_mesh()
x, y = make_rings(512, 2, seed=0)
base = dict(n_clusters=2, n_grids=64, sigma=0.15, d_g=1024,
            kmeans_replicates=2, solver_tol=1e-3, seed=0)
ref = sc_rb(jnp.asarray(x), SCRBConfig(**base))

labels, timer = sc_rb_distributed(x, SCRBConfig(**base), mesh)

cfg_c = SCRBConfig(**base, chunk_size=64)
res = executor.execute(x, cfg_c, executor.plan_from_config(cfg_c, mesh=mesh))

# solver routing: lanczos/subspace/compressive run through the mesh plan
# too (the eager drivers against the shard_map'd Gram mat-vec) and agree
# with the single-device run of the same solver. compressive pins a small
# filter degree: at this deliberately weak config the auto degree clamps
# to its ceiling, and same-solver parity is degree-independent (both
# placements draw identical random signals from the same key).
solver_parity = {}
for solver, extra in (("subspace", {}), ("lanczos", {}),
                      ("compressive", {"compressive_degree": 32})):
    cfg_s = SCRBConfig(**base, solver=solver, solver_iters=60, **extra)
    ref_s = sc_rb(jnp.asarray(x), cfg_s)
    res_s = executor.execute(x, cfg_s,
                             executor.plan_from_config(cfg_s, mesh=mesh))
    solver_parity[solver] = metrics.accuracy(res_s.labels, ref_s.labels)

emb_dots = [float(np.dot(ref.embedding[:, j], res.embedding[:, j]))
            for j in range(ref.embedding.shape[1])]
emb_err = max(
    float(np.abs(np.sign(d) * res.embedding[:, j] - ref.embedding[:, j]).max())
    for j, d in enumerate(emb_dots))

# mesh-placement serving: SCRBModel.predict/transform with mesh= replicates
# the O(D.K) state and row-shards batches; must agree with the single-device
# serving path on the same fitted model
from repro.core import SCRBModel
model = SCRBModel.fit(x, SCRBConfig(**base))
pred_single = model.predict(x)
pred_mesh = model.predict(x, mesh=mesh, batch_size=100)
emb_serve_err = float(np.abs(model.transform(x[:65], mesh=mesh)
                             - model.transform(x[:65])).max())

# partitioned placement over the mesh: plan_from_config routes to the
# divide-and-conquer fit, one partition per data-axis device. An easy blob
# mixture (not the rings) because partitioned is an approximation, not a
# parity-preserving placement — quality is judged against ground truth.
from repro.core import PartitionOptions
from repro.data.synthetic import make_blobs
xb, yb = make_blobs(600, 8, 4, seed=0)
cfg_p = SCRBConfig(n_clusters=4, n_grids=64, sigma=1.0, d_g=1024,
                   kmeans_replicates=2, seed=0,
                   partition=PartitionOptions(n_partitions=2))
plan_p = executor.plan_from_config(cfg_p, mesh=mesh)
res_p = executor.execute(xb, cfg_p, plan_p)
part_diag = res_p.diagnostics["partitioned"]
print(json.dumps({
    "devices": len(__import__("jax").devices()),
    "agree_mesh": metrics.accuracy(labels, ref.labels),
    "agree_chunked": metrics.accuracy(res.labels, ref.labels),
    "emb_err": emb_err,
    "serve_mesh_agree": metrics.accuracy(pred_mesh, pred_single),
    "serve_mesh_exact": bool(np.array_equal(pred_mesh, pred_single)),
    "serve_mesh_emb_err": emb_serve_err,
    "stages": sorted(timer.times),
    "solver_parity": solver_parity,
    "diag": {k: v for k, v in res.diagnostics.items()
             if k.startswith(("kmeans_", "shard", "n_shards", "ell_"))},
    "plan": res.diagnostics["plan"],
    "part_placement": plan_p.placement,
    "part_acc": metrics.accuracy(res_p.labels, yb),
    "part_devices": part_diag["devices"],
    "part_workers": part_diag["workers"],
    "part_n": part_diag["n_partitions"],
    "part_stages": sorted(res_p.timer.times),
}))
"""


@pytest.fixture(scope="module")
def mesh_result():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run([sys.executable, "-c", _MESH_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_mesh_plans_match_single_shot(mesh_result):
    r = mesh_result
    assert r["devices"] == 2
    assert r["plan"] == {"placement": "mesh", "residency": "host_chunked",
                         "chunk_size": 64, "prefetch": True, "impl": "auto"}
    assert r["agree_mesh"] >= 0.99
    assert r["agree_chunked"] >= 0.99
    assert r["emb_err"] < 5e-2
    # sc_rb_distributed is SCRBModel.fit-backed now: the five Alg.-2 stages
    # plus the O(NR) out-of-sample state pass
    assert set(r["stages"]) == {"rb_features", "degrees", "svd",
                                "normalize", "kmeans", "oos_state"}


def test_mesh_partitioned_cell(mesh_result):
    """placement='partitioned' under a mesh: one partition per data-axis
    device, both thread-pool workers active, full stage set, and near-exact
    labels on the easy blob mixture."""
    r = mesh_result
    assert r["part_placement"] == "partitioned"
    assert (r["part_devices"], r["part_workers"], r["part_n"]) == (2, 2, 2)
    assert r["part_acc"] >= 0.95
    assert set(r["part_stages"]) == {"partition", "rb_features",
                                     "partition_fits", "merge", "kmeans"}


def test_mesh_routes_all_solvers(mesh_result):
    """cfg.solver lanczos/subspace/compressive route through the mesh plan
    (ROADMAP item) and reproduce the single-device labels for the same
    solver."""
    assert set(mesh_result["solver_parity"]) == {
        "subspace", "lanczos", "compressive"}
    for solver, agree in mesh_result["solver_parity"].items():
        assert agree >= 0.97, (solver, agree)


def test_mesh_serving_parity(mesh_result):
    """SCRBModel.predict/transform accept mesh=: the replicated-state,
    row-sharded serving path reproduces the single-device labels (exactly,
    on CPU) and embedding within float tolerance — the sharded-fit →
    replicated-predict lifecycle of ROADMAP items 3/4."""
    r = mesh_result
    assert r["serve_mesh_agree"] >= 0.99
    assert r["serve_mesh_emb_err"] < 5e-4
    assert r["serve_mesh_exact"]    # row-local ops: exact on forced-CPU mesh


def test_mesh_kmeans_residency_is_o_shard_chunk(mesh_result):
    """The distributed k-means consumes the embedding shard-chunk-wise: its
    per-device working set is O(chunk), strictly below one shard's."""
    d = mesh_result["diag"]
    assert d["n_shards"] == 2
    assert d["shard_rows"] == 256
    assert d["kmeans_chunk_rows"] == 64
    k = emb_cols = 2
    assert d["kmeans_device_bytes_peak"] == 64 * (emb_cols + k) * 4
    assert d["kmeans_single_shard_bytes"] == 256 * (emb_cols + k) * 4
    assert d["kmeans_device_bytes_peak"] < d["kmeans_single_shard_bytes"]
    # within-shard ELL sweeps are chunk-bounded too
    assert d["ell_device_bytes_peak"] == 64 * 64 * 4


def test_mesh_fit_programs_take_the_ell_as_an_argument(monkeypatch):
    """Every program the mesh fit jits receives the (N, R) ELL as an
    argument. A closure over it embeds it as a constant: on a four-chip v5e
    host that made a 2.3 GB executable whose compile dominated the fit."""
    from repro.data.synthetic import make_blobs
    from repro.utils import make_auto_mesh
    n, r = 1000, 16
    x, _ = make_blobs(n, 2, 2, seed=0)
    cfg = SCRBConfig(n_clusters=2, n_grids=r, sigma=0.5, d_g=64,
                     kmeans_replicates=1, seed=0)
    texts, real_jit = [], jax.jit

    def lowering_jit(fn, **kw):
        jitted = real_jit(fn, **kw)

        def call(*args):
            texts.append(jitted.lower(*args).as_text())
            return jitted(*args)
        return call

    mesh = make_auto_mesh((1,), ("data",))
    monkeypatch.setattr(jax, "jit", lowering_jit)
    executor.execute(x, cfg, plan_from_config(cfg, mesh=mesh))
    ell = f"tensor<{n}x{r}xi32>"
    assert len(texts) >= 3          # transform, degree pass, eigensolve, ...
    assert not [line for t in texts for line in t.splitlines()
                if "constant" in line and ell in line]
