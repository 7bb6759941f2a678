"""Serving engine tests: the cluster predict engine (bucketed jit cache,
coalescing, LRU, hot-swap, HTTP front end)."""
import glob
import importlib.util
import json
import os
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import SCRBConfig, SCRBModel, rb
from repro.data.synthetic import make_blobs, make_rings
from repro.serve.cluster_engine import ClusterEngine, EngineConfig
from repro.serve.server import ClusterServer


# -- ClusterEngine ---------------------------------------------------------

BUCKETS = (32, 64, 128)


@pytest.fixture(scope="module")
def fitted():
    """Two small fitted models with different dims/K (multi-model routing
    needs genuinely different cells and staging shapes)."""
    xb, _ = make_blobs(300, 6, 4, seed=0)
    xr, _ = make_rings(300, 2, seed=1)
    mb = SCRBModel.fit(xb, SCRBConfig(
        n_clusters=4, n_grids=16, sigma=1.5, d_g=128, solver_tol=1e-2,
        kmeans_replicates=1, seed=0))
    mr = SCRBModel.fit(xr, SCRBConfig(
        n_clusters=2, n_grids=16, sigma=0.15, d_g=128, solver_tol=1e-2,
        kmeans_replicates=1, seed=1))
    return {"blobs": (mb, xb), "rings": (mr, xr)}


def _engine(fitted, **kw):
    eng = ClusterEngine(EngineConfig(buckets=BUCKETS, **kw))
    for name, (mdl, _) in fitted.items():
        eng.load_model(name, mdl)
    return eng


def test_engine_bucket_padding_parity(fitted):
    """Engine outputs are bit-identical to direct model.predict/transform
    for ragged sizes that land in every bucket (pad rows never leak)."""
    eng = _engine(fitted)
    for name, (mdl, x) in fitted.items():
        for n in (1, 17, 32, 33, 64, 100, 128):
            np.testing.assert_array_equal(eng.predict(name, x[:n]),
                                          mdl.predict(x[:n]))
        np.testing.assert_array_equal(eng.transform(name, x[:50]),
                                      mdl.transform(x[:50]))


def test_engine_jit_cache_accounting(fitted):
    """Second request in the same bucket compiles nothing; a new bucket
    compiles exactly one cell; warmup precovers the whole grid."""
    eng = _engine(fitted)
    _, x = fitted["blobs"]
    eng.predict("blobs", x[:40])                  # bucket 64
    assert eng.total_compiles == 1
    eng.predict("blobs", x[:60])                  # same bucket → cache hit
    assert eng.total_compiles == 1
    assert eng.stats("blobs")["cache_hits"] == 1
    eng.predict("blobs", x[:100])                 # bucket 128 → one compile
    assert eng.total_compiles == 2
    n_new = eng.warmup("blobs", modes=("predict", "transform"))
    assert n_new == 2 * len(BUCKETS) - 2          # grid minus the two above
    before = eng.total_compiles
    eng.predict("blobs", x[:10])
    eng.transform("blobs", x[:90])
    assert eng.total_compiles == before           # fully warm


def test_engine_lru_eviction_and_cell_survival(fitted):
    """One resident slot, two models interleaved: every switch evicts, the
    results stay bit-identical, and compiled cells survive eviction (the
    re-fault pays H2D only, never a recompile)."""
    eng = _engine(fitted, max_resident_models=1)
    for name in fitted:
        eng.warmup(name, modes=("predict", "transform"))
    compiles = eng.total_compiles
    for rep in range(3):
        for name, (mdl, x) in fitted.items():
            sl = slice(10 * rep, 10 * rep + 45)
            np.testing.assert_array_equal(eng.predict(name, x[sl]),
                                          mdl.predict(x[sl]))
    s = eng.stats()
    assert s["evictions"] >= 5                    # every switch evicts
    assert len(s["resident"]) == 1
    assert eng.total_compiles == compiles         # cells survived


def test_engine_hot_swap(fitted):
    """Re-loading a name swaps the artifact: old cells/state are dropped
    and traffic immediately reflects the new model."""
    mb, xb = fitted["blobs"]
    mr, xr = fitted["rings"]
    eng = ClusterEngine(EngineConfig(buckets=BUCKETS))
    eng.load_model("m", mb)
    np.testing.assert_array_equal(eng.predict("m", xb[:20]),
                                  mb.predict(xb[:20]))
    eng.load_model("m", mr)                       # hot-swap, different dim
    with pytest.raises(ValueError, match="expects 2-d rows"):
        eng.predict("m", xb[:20])
    np.testing.assert_array_equal(eng.predict("m", xr[:20]),
                                  mr.predict(xr[:20]))


def test_engine_coalesces_and_splits(fitted):
    """Many small requests coalesce into one batch; a request bigger than
    the coalescing cap is split across steps with correct reassembly."""
    mdl, x = fitted["blobs"]
    eng = _engine(fitted)
    tickets = [eng.submit("blobs", x[i * 10:(i + 1) * 10]) for i in range(5)]
    assert eng.step() == 50                       # one batch, five requests
    assert eng.stats("blobs")["batches"] == 1
    for i, t in enumerate(tickets):
        np.testing.assert_array_equal(
            eng.take(t).values, mdl.predict(x[i * 10:(i + 1) * 10]))
    big = np.vstack([x, x])[:290]                 # > top bucket (128) → split
    t = eng.submit("blobs", big)
    served = eng.drain()
    assert served == 290
    assert eng.stats("blobs")["batches"] >= 1 + 3
    np.testing.assert_array_equal(eng.take(t).values, mdl.predict(big))


def test_engine_fused_degree_counter(fitted):
    """Every batch of an RB model reads its degree from the projection's
    gather, in both modes; a dense-map (RFF) model's batches never do."""
    from repro.core import featuremap
    from repro.core.executor import ExecutionPlan
    _, x = fitted["blobs"]
    rff = SCRBModel.fit(x, SCRBConfig(
        n_clusters=4, n_grids=16, sigma=1.5,
        kmeans_replicates=1, seed=0), plan=ExecutionPlan(
            feature_map=featuremap.make_feature_map("rff", rank=64,
                                                    sigma=1.5)))
    eng = _engine(fitted)
    eng.load_model("rff", rff)
    eng.predict("blobs", x[:40])
    eng.transform("blobs", x[:100])
    eng.predict("blobs", x[:200])                 # > top bucket: two batches
    np.testing.assert_array_equal(eng.predict("rff", x[:50]),
                                  rff.predict(x[:50]))
    rb, dense = eng.stats("blobs"), eng.stats("rff")
    assert rb["fused_degree_batches"] == rb["batches"] == 4
    assert dense["batches"] == 1 and dense["fused_degree_batches"] == 0
    assert eng.stats()["fused_degree_batches"] == 4
    text = eng.metrics_text()
    for model, mode, value in (("blobs", "predict", 3),
                               ("blobs", "transform", 1),
                               ("rff", "predict", 0), ("rff", "transform", 0)):
        assert (f'engine_fused_degree_batches_total{{model="{model}",'
                f'mode="{mode}"}} {value}') in text


@pytest.fixture(scope="module")
def zoo():
    """Three tiny fitted models of different d and K."""
    out = {}
    for name, d, k, seed in (("d4k3", 4, 3, 10), ("d16k7", 16, 7, 11),
                             ("d10k10", 10, 10, 12)):
        x, _ = make_blobs(400, d, k, seed=seed)
        out[name] = (SCRBModel.fit(x, SCRBConfig(
            n_clusters=k, n_grids=16, sigma=float(rb.suggest_sigma(x)),
            d_g=128, solver_tol=1e-2, kmeans_replicates=1, seed=seed)), x)
    return out


def _reference_labels(mdl, x):
    """Nearest centroid of the plain reference's out-of-sample embedding
    (``bench/reference.py``, which imports nothing of the program; loaded
    from its file, so that neither ``sys.path`` nor ``sys.modules`` keeps
    it)."""
    spec = importlib.util.spec_from_file_location(
        "_bench_reference", os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "bench", "reference.py"))
    reference = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reference)
    model = {"grids": mdl.feature_map.state_dict(),
             "d_g": mdl.feature_map.d_g, "dual": mdl.degree_dual,
             "right_vectors": mdl.right_vectors,
             "singular_values": mdl.singular_values}
    emb = reference.embed_new(x, model)
    return np.asarray(reference.sq_dists(
        emb, jnp.asarray(mdl.centroids))).argmin(1)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_engine_interleaved_models(zoo, tmp_path, seed):
    """Requests for three resident models of different d and K, submitted
    in a seeded order with sizes across the bucket edges and served a few
    steps at a time: every label is the model's own (its ``predict`` alone
    and the reference's nearest centroid), nothing is evicted, and
    ``model_switches`` and each ``engine.batch`` span's ``switched`` count
    the model changes between consecutive batches."""
    from jax.profiler import ProfileData
    rng = np.random.default_rng(seed)
    names = list(zoo)
    eng = ClusterEngine(EngineConfig(buckets=BUCKETS))
    for name in names:
        eng.load_model(name, zoo[name][0])
        eng.warmup(name)
    sizes = (1, 31, 32, 33, 63, 64, 65, 127, 128, 129, 200)
    reqs = [(names[i], int(rng.choice(sizes)),
             int(rng.integers(0, 150))) for i in rng.integers(0, 3, 40)]
    tickets = []
    jax.profiler.start_trace(str(tmp_path))
    try:
        for name, n, off in reqs:
            tickets.append(eng.submit(name, zoo[name][1][off:off + n]))
            for _ in range(int(rng.integers(0, 3))):
                eng.step()
        eng.drain()
    finally:
        jax.profiler.stop_trace()
    served = {name: ([], []) for name in names}
    for (name, n, off), t in zip(reqs, tickets):
        served[name][0].append(zoo[name][1][off:off + n])
        served[name][1].append(eng.take(t).values)
    for name, (rows, labels) in served.items():
        mdl = zoo[name][0]
        rows, labels = np.concatenate(rows), np.concatenate(labels)
        np.testing.assert_array_equal(labels, mdl.predict(rows))
        np.testing.assert_array_equal(labels, _reference_labels(mdl, rows))
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    batches = sorted((int(e.start_ns), dict(e.stats))
                     for plane in ProfileData.from_file(path).planes
                     for line in plane.lines for e in line.events
                     if e.name == "engine.batch")
    order = [attrs["model"] for _, attrs in batches]
    changes = [int(a != b) for a, b in zip(order, order[1:])]
    assert len(order) == eng.stats()["batches"]
    assert [attrs["switched"] for _, attrs in batches] == [0] + changes
    s = eng.stats()
    assert s["model_switches"] == sum(changes) > 0
    for name in names:         # counted under the model switched to
        assert s["models"][name]["model_switches"] == sum(
            c for c, m in zip(changes, order[1:]) if m == name)
    assert s["evictions"] == 0 and len(s["resident"]) == 3
    assert (f'engine_model_switches_total{{model="{names[0]}"}} '
            f'{s["models"][names[0]]["model_switches"]}') in eng.metrics_text()


def test_engine_edge_requests(fitted):
    eng = _engine(fitted)
    # empty request completes without device work
    t = eng.submit("blobs", np.empty((0, 6), np.float32))
    res = eng.take(t)
    assert res.values.shape == (0,) and res.latency == 0.0
    assert eng.total_compiles == 0
    # validation errors
    with pytest.raises(KeyError, match="unknown model"):
        eng.submit("nope", np.zeros((1, 6), np.float32))
    with pytest.raises(ValueError, match="mode"):
        eng.submit("blobs", np.zeros((1, 6), np.float32), "embed")
    with pytest.raises(ValueError, match=r"\(n, d\)"):
        eng.submit("blobs", np.zeros((6,), np.float32).reshape(1, 2, 3))
    with pytest.raises(ValueError, match="expects 6-d"):
        eng.submit("blobs", np.zeros((3, 5), np.float32))
    with pytest.raises(KeyError, match="not finished"):
        eng.take(12345)
    # transform-only model rejects predict submissions
    _, x = fitted["blobs"]
    emb_only = SCRBModel.fit(x, SCRBConfig(
        n_clusters=4, n_grids=16, sigma=1.5, d_g=128, solver_tol=1e-2,
        seed=0), final_stage="normalize")
    eng.load_model("emb", emb_only)
    with pytest.raises(ValueError, match="no centroids"):
        eng.submit("emb", x[:4])
    assert eng.transform("emb", x[:4]).shape == (4, 4)


def test_engine_device_budget_eviction(fitted):
    """device_budget_bytes evicts by size, but never the newest entry."""
    eng = _engine(fitted, device_budget_bytes=1)   # absurdly small budget
    for name, (mdl, x) in fitted.items():
        np.testing.assert_array_equal(eng.predict(name, x[:8]),
                                      mdl.predict(x[:8]))
    assert len(eng.resident_models) == 1           # newest always kept
    assert eng.stats()["evictions"] == 1


def test_cluster_server_http_roundtrip(fitted, tmp_path):
    """The stdlib front end serves the same engine loop: load via POST,
    predict/transform parity, stats, and error codes."""
    mdl, x = fitted["blobs"]
    path = str(tmp_path / "m.npz")
    mdl.save(path)
    eng = ClusterEngine(EngineConfig(buckets=BUCKETS))
    with ClusterServer(eng) as srv:
        def post(route, body):
            req = urllib.request.Request(
                srv.url + route, json.dumps(body).encode(),
                {"Content-Type": "application/json"})
            try:
                with urllib.request.urlopen(req) as r:
                    return r.status, json.loads(r.read())
            except urllib.error.HTTPError as e:
                return e.code, json.loads(e.read())

        code, out = post("/v1/models", {"name": "m", "path": path})
        assert code == 200 and out["data_dim"] == 6
        code, out = post("/v1/predict", {"model": "m",
                                         "rows": x[:9].tolist()})
        assert code == 200
        np.testing.assert_array_equal(out["labels"], mdl.predict(x[:9]))
        code, out = post("/v1/transform", {"model": "m",
                                           "rows": x[:3].tolist()})
        assert code == 200 and np.asarray(out["embedding"]).shape == (3, 4)
        code, out = post("/v1/predict", {"model": "ghost", "rows": [[0] * 6]})
        assert code == 400 and "ghost" in out["error"]
        with urllib.request.urlopen(srv.url + "/v1/stats") as r:
            stats = json.loads(r.read())
        assert stats["rows_served"] == 12
