"""Distributed SC_RB tests: run in a subprocess with 8 forced host devices
(the XLA device-count flag must not leak into other tests)."""
import json
import os
import subprocess
import sys

import pytest

# the 8-device subprocess re-runs the full pipeline three ways — minutes on
# CPU; tier-1 covers the chunked/sharded matvec math via tests/test_streaming
pytestmark = pytest.mark.slow

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp, numpy as np
from repro.core import SCRBConfig, metrics, sc_rb
from repro.core.distributed import sc_rb_distributed, make_gram_matvec
from repro.core import rb, graph
from repro.data.synthetic import make_rings
from repro.utils import fold_key

from repro.utils import make_auto_mesh
mesh = make_auto_mesh((8,), ("data",))
x, y = make_rings(1024, 2, seed=0)
cfg = SCRBConfig(n_clusters=2, n_grids=128, sigma=0.15, d_g=4096,
                 kmeans_replicates=2, seed=0)

# 1) distributed matvec == single-device matvec
key = jax.random.PRNGKey(0)
params = rb.make_rb_params(fold_key(key, "rb"), cfg.n_grids, 2, cfg.sigma, cfg.d_g)
idx = rb.rb_transform(jnp.asarray(x), params)
adj = graph.build_normalized_adjacency(idx, d=params.n_features, d_g=cfg.d_g)
u = jax.random.normal(jax.random.PRNGKey(1), (1024, 4))
want = adj.gram_matvec(u)
from jax.sharding import NamedSharding, PartitionSpec as P
row = NamedSharding(mesh, P("data", None))
with mesh:
    mv = make_gram_matvec(mesh, jax.device_put(idx, row),
                          jax.device_put(adj.rowscale, NamedSharding(mesh, P("data"))),
                          params.n_features, cfg.d_g, impl="xla")
    got = jax.jit(mv)(jax.device_put(u, row))
    # chunked-within-shard variant (streaming composes with the mesh)
    mv_c = make_gram_matvec(mesh, jax.device_put(idx, row),
                            jax.device_put(adj.rowscale, NamedSharding(mesh, P("data"))),
                            params.n_features, cfg.d_g, impl="xla",
                            chunk_size=48)
    got_c = jax.jit(mv_c)(jax.device_put(u, row))
err = float(jnp.abs(want - got).max())
err_chunked = float(jnp.abs(want - got_c).max())

# 2) end-to-end distributed clustering quality — chunked-within-shard plan
#    (the streaming × distributed composition), with residency diagnostics
from repro.core import executor
cfg_c = SCRBConfig(n_clusters=2, n_grids=128, sigma=0.15, d_g=4096,
                   kmeans_replicates=2, seed=0, chunk_size=64)
res = executor.execute(x, cfg_c, executor.plan_from_config(cfg_c, mesh=mesh),
                       keep_embedding=False)
acc = metrics.accuracy(res.labels, y)

# 3) single-device reference
ref = sc_rb(jnp.asarray(x), cfg)
acc_ref = metrics.accuracy(ref.labels, y)

print(json.dumps({"matvec_err": err, "matvec_err_chunked": err_chunked,
                  "acc": acc, "acc_ref": acc_ref,
                  "kmeans_device_bytes_peak":
                      res.diagnostics["kmeans_device_bytes_peak"],
                  "kmeans_single_shard_bytes":
                      res.diagnostics["kmeans_single_shard_bytes"],
                  "kmeans_chunk_rows": res.diagnostics["kmeans_chunk_rows"],
                  "devices": len(jax.devices())}))
"""


@pytest.fixture(scope="module")
def result():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_runs_on_8_devices(result):
    assert result["devices"] == 8


def test_distributed_matvec_matches_single_device(result):
    assert result["matvec_err"] < 1e-4


def test_distributed_chunked_matvec_matches_single_device(result):
    """Chunking within each row shard changes nothing but peak memory."""
    assert result["matvec_err_chunked"] < 1e-4


def test_distributed_clustering_quality(result):
    """The chunked-within-shard plan clusters as well as single-device."""
    assert result["acc"] > 0.95
    assert result["acc"] >= result["acc_ref"] - 0.05


def test_distributed_kmeans_residency_o_shard_chunk(result):
    """The mesh k-means never holds more than a chunk of derived state per
    device: O(shard_chunk), not O(N/shards) = 128 rows/shard here."""
    assert result["kmeans_chunk_rows"] == 64
    assert result["kmeans_device_bytes_peak"] \
        < result["kmeans_single_shard_bytes"]
