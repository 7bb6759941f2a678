"""placement="partitioned" — the divide-and-conquer fit (PR 9 tentpole).

Covers: merge parity vs the single-shot solve (ARI on well-separated data),
the partition-count sweep, host_chunked residency (each partition streams
its own chunks), block-list inputs, and save/load/serve of the merged
model (predict(x_train) must reproduce the fit labels — the global
labeling pass *is* the serving path).
"""
import os
import tempfile

import numpy as np
import pytest

from repro.core import (
    PartitionOptions, SCRBConfig, SCRBModel, executor, metrics,
)
from repro.core.partitioned import partition_rows
from repro.core.rowmatrix import PartitionedRows
from repro.data.synthetic import make_blobs

BASE = dict(n_clusters=4, n_grids=64, sigma=1.0, d_g=1024,
            kmeans_replicates=2, seed=0)


@pytest.fixture(scope="module")
def data():
    return make_blobs(1200, 8, 4, seed=0)


@pytest.fixture(scope="module")
def reference(data):
    x, y = data
    res = executor.execute(x, SCRBConfig(**BASE))
    assert metrics.accuracy(res.labels, y) > 0.97
    return res


def _pcfg(n_partitions=3, **kw):
    base = dict(BASE)
    base.update(kw.pop("base", {}))
    return SCRBConfig(**base, partition=PartitionOptions(
        n_partitions=n_partitions, **kw))


def test_partitioned_matches_single_shot(data, reference):
    """Merge parity: the divide-and-conquer labels agree with the global
    LOBPCG solve on well-separated clusters."""
    x, y = data
    res = executor.execute(x, _pcfg())
    assert metrics.accuracy(res.labels, reference.labels) >= 0.97
    assert metrics.accuracy(res.labels, y) >= 0.97
    # the partitioned stage set replaces the global solve stages
    assert set(res.timer.times) == {"partition", "rb_features",
                                    "partition_fits", "merge", "kmeans"}
    d = res.diagnostics["partitioned"]
    assert d["n_partitions"] == 3
    assert sum(d["partition_rows"]) == x.shape[0]
    assert d["representatives"] >= BASE["n_clusters"]
    assert len(d["partition_fit_s"]) == 3


@pytest.mark.parametrize("n_partitions", [2, 4, 6])
def test_partition_count_sweep(data, n_partitions):
    x, y = data
    res = executor.execute(x, _pcfg(n_partitions))
    assert metrics.accuracy(res.labels, y) >= 0.95, n_partitions
    assert res.diagnostics["partitioned"]["n_partitions"] == n_partitions


def test_partitioned_host_chunked(data, reference):
    """host_chunked residency composes: each partition streams its own
    chunks, and the result still matches the single-shot labels."""
    x, y = data
    cfg = _pcfg(base=dict(chunk_size=128))
    plan = executor.plan_from_config(cfg)
    assert (plan.placement, plan.residency) == ("partitioned",
                                                "host_chunked")
    res = executor.execute(x, cfg, plan)
    assert metrics.accuracy(res.labels, reference.labels) >= 0.97
    assert res.diagnostics["n_chunks"] >= 3      # summed over partitions


def test_partitioned_block_list_input(data):
    """A block-list input partitions by whole blocks — never concatenated —
    and labels land back in input row order."""
    x, y = data
    blocks = [x[i:i + 200] for i in range(0, x.shape[0], 200)]
    cfg = _pcfg(base=dict(chunk_size=200), shuffle=False)
    res = executor.execute(blocks, cfg)
    assert metrics.accuracy(res.labels, y) >= 0.95


def test_partition_rows_covers_all_rows():
    x = np.arange(103 * 2, dtype=np.float32).reshape(103, 2)
    parts = partition_rows(x, 4, shuffle=True, seed=0)
    got = np.sort(np.concatenate([p[:, 0] for p in parts]))
    np.testing.assert_array_equal(got, x[:, 0])
    sizes = [p.shape[0] for p in parts]
    assert max(sizes) - min(sizes) <= max(sizes)  # near-equal + tail
    # shuffled slices must not be the contiguous split
    assert any(np.any(np.diff(p[:, 0]) != 2) for p in parts)


def test_partitioned_rejects_tiny_partitions(data):
    x, _ = data
    with pytest.raises(ValueError, match="local_clusters"):
        executor.execute(x[:9], _pcfg(4, local_clusters=8))


def test_partitioned_state_and_rowmatrix(data):
    x, _ = data
    res = executor.execute(x, _pcfg(), keep_state=True)
    st = res.state
    assert isinstance(st["z"], PartitionedRows)
    assert st["z"].n == x.shape[0]
    assert st["z"].n_partitions == 3
    ps = st["partitioned"]
    assert ps["right_vectors"].shape[1] == BASE["n_clusters"]
    assert ps["degree_dual"].shape == (st["z"].parts[0].degree_dual().shape)


def test_merged_model_save_load_serve(data, tmp_path):
    """The merged model is the same one-npz artifact: predict(x_train)
    reproduces the fit labels and survives a save/load round-trip."""
    x, y = data
    model = SCRBModel.fit(x, _pcfg())
    res = model.fit_result
    assert metrics.accuracy(res.labels, y) >= 0.95
    np.testing.assert_array_equal(model.predict(x), res.labels)

    path = os.path.join(tmp_path, "merged.npz")
    model.save(path)
    loaded = SCRBModel.load(path)
    assert loaded.config == model.config
    assert loaded.config.partition.n_partitions == 3
    np.testing.assert_array_equal(loaded.predict(x), res.labels)
    emb = loaded.transform(x[:100])
    np.testing.assert_allclose(emb, model.transform(x[:100]), atol=1e-6)


def test_merged_model_serves_through_engine(data):
    """ClusterEngine serves a partitioned-fit model unchanged."""
    from repro.serve.cluster_engine import ClusterEngine
    x, y = data
    model = SCRBModel.fit(x, _pcfg())
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "m.npz")
        model.save(path)
        eng = ClusterEngine()
        eng.load_model("m", path)
        out = eng.predict("m", x[:257])
        np.testing.assert_array_equal(out, model.fit_result.labels[:257])


def test_partition_devices_mesh_slice():
    """partition_devices picks one device per data-axis shard."""
    import jax

    from repro.launch.mesh import partition_devices
    from repro.utils import make_auto_mesh
    n = len(jax.devices())
    mesh = make_auto_mesh((n, 1), ("data", "model"))
    devs = partition_devices(mesh)
    assert len(devs) == n
