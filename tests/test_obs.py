"""Observability subsystem: program spans on the profiler's clock,
metrics registry, memory watermarks, and their wiring through fit/serve.

Covers: spans read back from a ``jax.profiler`` trace (``ProfileData``) —
nesting and attributes, worker threads on their own lines, the scoped
``tracing()`` and its reentrance, nothing recorded with no session live —
log-bucket histogram quantile accuracy, Prometheus text exposition,
registry snapshot/reset isolation, the StageTimer-over-spans shim
(``timer.times`` semantics unchanged), the ``prefetch_to_device``
``stats=`` → ``measure=`` rename, traced fits (span names and attributes,
memory diagnostics, fit counters; the partitioned fit's worker lanes),
the engine's batch spans and queue wait, engine stats parity + latency
quantiles, and the ``GET /metrics`` HTTP round-trip.

Every profiler session a test starts is stopped in a finally block; tests
against the process-global ``REGISTRY`` assert *deltas* so ordering
against other test files cannot matter.
"""
import collections
import contextlib
import glob
import gzip
import json
import os
import threading
import time
import urllib.request

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import PartitionOptions, SCRBConfig, SCRBModel, executor
from repro.data.synthetic import make_blobs
from repro.obs import memory as obs_memory
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.serve.cluster_engine import (
    STAT_KEYS, ClusterEngine, EngineConfig,
)
from repro.serve.server import ClusterServer
from repro.utils import StageTimer, prefetch_to_device

FAST = dict(n_clusters=4, n_grids=16, sigma=1.5, d_g=128, solver_tol=1e-2,
            kmeans_replicates=1, seed=0)

Event = collections.namedtuple("Event", "line name start end attrs")


def _profile_dirs(trace_dir) -> list:
    return sorted(glob.glob(os.path.join(str(trace_dir), "plugins",
                                         "profile", "*")))


def _events(trace_dir, names=None) -> list:
    """The host plane's events (optionally only those named in ``names``)
    from the one profiler trace written under ``trace_dir``, in start
    order; ``line`` tells the host threads apart."""
    path, = glob.glob(os.path.join(str(trace_dir), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for li, line in enumerate(plane.lines):
            for e in line.events:
                if names is None or e.name in names:
                    out.append(Event(li, e.name, int(e.start_ns),
                                     int(e.end_ns), dict(e.stats)))
    return sorted(out, key=lambda e: e.start)


@contextlib.contextmanager
def _session(trace_dir):
    """A plain ``jax.profiler`` session for one test, always stopped."""
    jax.profiler.start_trace(str(trace_dir))
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def _inside(inner: Event, outer: Event) -> bool:
    return outer.start <= inner.start and inner.end <= outer.end


# -- trace -----------------------------------------------------------------

def test_span_disabled_is_null():
    assert not obs_trace.active()
    with obs_trace.span("nope", k=1) as sp:
        assert sp is obs_trace.NULL_SPAN
        sp.set_metadata(anything="goes")  # no-op, no error


def test_span_without_a_session_records_nothing(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    with obs_trace.span("early", k=1):    # opened with no session live
        assert os.listdir(tmp_path) == []     # and wrote nothing
        with _session(tmp_path / "t"):
            with obs_trace.span("late"):
                pass
    assert os.listdir(tmp_path) == ["t"]
    names = {e.name for e in _events(tmp_path / "t", {"early", "late"})}
    assert names == {"late"}


def test_span_nesting_and_chrome_export(tmp_path):
    with obs_trace.tracing(str(tmp_path)):
        with obs_trace.span("outer", stage="a"):
            with obs_trace.span("inner") as sp:
                sp.set_metadata(rows=7)
                time.sleep(0.002)
    outer, inner = _events(tmp_path, {"outer", "inner"})
    assert (outer.name, inner.name) == ("outer", "inner")
    assert outer.line == inner.line and _inside(inner, outer)
    assert inner.end - inner.start >= 2_000_000
    assert outer.attrs == {"stage": "a"} and inner.attrs == {"rows": 7}

    # the session also writes Chrome-trace JSON that Perfetto opens
    path, = glob.glob(os.path.join(_profile_dirs(tmp_path)[0],
                                   "perfetto_trace.json.gz"))
    with gzip.open(path) as f:
        doc = json.load(f)
    xs = [e for e in doc["traceEvents"] if e.get("ph") == "X"
          and e["name"] in ("outer", "inner")]
    assert sorted(e["name"] for e in xs) == ["inner", "outer"]
    for e in xs:                          # Chrome trace required fields
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        assert e["ts"] >= 0 and e["dur"] >= 0


def test_spans_closed_on_other_threads_get_own_tracks(tmp_path):
    def work(i):
        with obs_trace.span("job", i=i):
            time.sleep(0.005)

    with _session(tmp_path):
        with obs_trace.span("main"):
            threads = [threading.Thread(target=work, args=(i,),
                                        name=f"wk{i}") for i in range(2)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
    main, = _events(tmp_path, {"main"})
    jobs = _events(tmp_path, {"job"})
    assert len(jobs) == 2
    assert {e.attrs["i"] for e in jobs} == {0, 1}
    lines = {e.line for e in jobs}
    assert len(lines) == 2 and main.line not in lines   # a lane per thread
    assert all(_inside(e, main) for e in jobs)


def test_tracing_contextmanager_scopes_and_is_reentrant(tmp_path):
    path = tmp_path / "scoped"
    with obs_trace.tracing(str(path)):
        assert obs_trace.active()
        with obs_trace.tracing(str(tmp_path / "ignored")):  # reentrant:
            with obs_trace.span("s"):                       # no-op layer
                pass
        assert obs_trace.active()
    assert not obs_trace.active()
    assert [e.name for e in _events(path, {"s"})] == ["s"]
    assert not (tmp_path / "ignored").exists()
    with obs_trace.tracing(None):                     # None → plain no-op
        assert not obs_trace.active()


# -- metrics ---------------------------------------------------------------

def test_counter_and_gauge_basics():
    reg = obs_metrics.MetricsRegistry()
    c = reg.counter("c_total", "help", ("model",))
    c.inc(model="a")
    c.inc(2.5, model="a")
    c.inc(model="b")
    assert c.get(model="a") == 3.5 and c.get(model="b") == 1.0
    assert c.get(model="never") == 0.0
    with pytest.raises(ValueError, match="only go up"):
        c.inc(-1, model="a")
    with pytest.raises(ValueError, match="label"):
        c.inc(wrong="a")
    g = reg.gauge("g", "help")
    g.set(4.0)
    g.inc(-1.5)
    assert g.get() == 2.5
    # same name+kind+labels → same instrument; conflicting redefinition raises
    assert reg.counter("c_total", "help", ("model",)) is c
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("c_total", "help", ("model",))


def test_histogram_quantiles_close_to_exact():
    reg = obs_metrics.MetricsRegistry()
    h = reg.histogram("lat_seconds", "help",
                      buckets=obs_metrics.log_buckets(1e-4, 10.0))
    rng = np.random.default_rng(0)
    xs = np.exp(rng.normal(-3.0, 1.0, size=5000))      # lognormal latencies
    for v in xs:
        h.observe(float(v))
    assert h.count() == 5000
    assert h.sum() == pytest.approx(float(xs.sum()), rel=1e-6)
    factor = 10 ** 0.25                                # one log-bucket width
    for q in (0.5, 0.9, 0.99):
        exact = float(np.quantile(xs, q))
        est = h.quantile(q)
        assert exact / factor <= est <= exact * factor
    assert reg.histogram("empty_seconds", "h").quantile(0.5) is None


def test_prometheus_exposition_format():
    reg = obs_metrics.MetricsRegistry()
    reg.counter("req_total", "requests", ("model", "mode")).inc(
        3, model='a"b\\c', mode="p")
    reg.gauge("temp", "gauge").set(1.5)
    h = reg.histogram("lat_seconds", "latency", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    text = reg.to_prometheus()
    assert "# TYPE req_total counter" in text
    assert r'req_total{model="a\"b\\c",mode="p"} 3' in text
    assert "# TYPE lat_seconds histogram" in text
    assert 'lat_seconds_bucket{le="0.1"} 1' in text
    assert 'lat_seconds_bucket{le="1"} 2' in text
    assert 'lat_seconds_bucket{le="+Inf"} 3' in text   # cumulative
    assert "lat_seconds_count 3" in text
    assert "lat_seconds_sum 5.55" in text
    assert "temp 1.5" in text
    # invalid metric names rejected at registration
    with pytest.raises(ValueError, match="metric name"):
        reg.counter("bad-name", "h")


def test_registry_snapshot_and_reset_isolation():
    reg = obs_metrics.MetricsRegistry()
    c = reg.counter("n_total", "h", ("k",))
    c.inc(4, k="x")
    snap = reg.snapshot()
    c.inc(k="x")                                      # snapshot is a copy
    assert snap["n_total"][("x",)] == 4.0
    assert reg.snapshot()["n_total"][("x",)] == 5.0
    reg.reset()                                       # zeroes, keeps schema
    assert c.get(k="x") == 0.0
    assert reg.counter("n_total", "h", ("k",)) is c
    # global REGISTRY is a separate object entirely
    assert obs_metrics.REGISTRY.get("n_total") is None


def test_render_prometheus_dedups_registries():
    reg = obs_metrics.MetricsRegistry()
    reg.counter("one_total", "h").inc()
    text = obs_metrics.render_prometheus([reg, reg, obs_metrics.REGISTRY])
    assert text.count("# TYPE one_total counter") == 1


# -- memory ----------------------------------------------------------------

def test_memory_sample_and_watermark():
    s = obs_memory.sample()
    assert s["rss_bytes"] > 0
    assert s["peak_rss_bytes"] >= s["rss_bytes"] // 2  # same order of magnitude
    with obs_memory.Watermark() as wm:
        ballast = np.ones(2_000_000, np.float64)       # ~16 MB
        assert ballast.sum() > 0
    d = wm.as_dict()
    assert set(d) >= {"rss_delta_bytes", "peak_rss_delta_bytes"}
    assert wm.peak_rss_delta_bytes >= 0


# -- StageTimer shim + prefetch rename -------------------------------------

def test_stage_timer_times_semantics_unchanged():
    timer = StageTimer()
    with timer.stage("a"):
        time.sleep(0.01)
    with timer.stage("a"):                            # accumulates
        time.sleep(0.01)
    with timer.stage("b"):
        pass
    assert set(timer.times) == {"a", "b"}
    assert timer.times["a"] >= 0.02
    # stage durations also feed the global histogram
    h = obs_metrics.REGISTRY.get("repro_stage_seconds")
    assert h.count(stage="a") >= 2


def test_stage_timer_emits_spans_when_tracing(tmp_path):
    with _session(tmp_path):
        timer = StageTimer()
        with timer.stage("mystage"):
            time.sleep(0.001)
    stage, = _events(tmp_path, {"mystage"})
    assert "mystage" in timer.times
    # the span and the timer's own clock time the same stage
    assert stage.end - stage.start == pytest.approx(
        timer.times["mystage"] * 1e9, rel=0.5)


def test_prefetch_measure_rename_and_counters():
    items = ((i, np.ones((4, 4), np.float32)) for i in range(3))
    c_items = obs_metrics.REGISTRY.get("repro_prefetch_items_total")
    before = c_items.get()
    measure = {}
    out = list(prefetch_to_device(items, measure=measure))
    assert len(out) == 3
    assert measure["items"] == 3 and measure["max_item_bytes"] == 64
    assert c_items.get() - before == 3
    # legacy stats= still works but warns
    with pytest.deprecated_call(match="measure"):
        out = list(prefetch_to_device(
            ((0, np.ones(2, np.float32)),), stats={}))
    assert len(out) == 1


# -- fit wiring ------------------------------------------------------------

@pytest.fixture(scope="module")
def blobs():
    return make_blobs(300, 6, 4, seed=0)


def test_traced_fit_spans_memory_and_counters(blobs, tmp_path):
    x, y = blobs
    path = tmp_path / "fit_trace"
    fits = obs_metrics.REGISTRY.get("repro_fits_total")
    solves = obs_metrics.REGISTRY.get("repro_eigensolves_total")
    f0 = sum(fits.collect().values())
    s0 = sum(solves.collect().values())

    res = executor.execute(x, SCRBConfig(**FAST, trace=str(path)))

    assert not obs_trace.active()                     # scoped: off again
    evs = _events(path)
    names = {e.name for e in evs}
    assert {"fit", "rb_features", "eigensolve", "kmeans"} <= names
    root, = (e for e in evs if e.name == "fit")
    assert root.attrs["placement"] == "single"
    assert "solver" in root.attrs
    solve, = (e for e in evs if e.name == "eigensolve")
    assert solve.attrs["iterations"] >= 0 and _inside(solve, root)
    # memory watermark lands in diagnostics even without tracing
    assert res.diagnostics["memory"]["rss_delta_bytes"] is not None
    assert sum(fits.collect().values()) == f0 + 1
    assert sum(solves.collect().values()) >= s0 + 1
    # and the same fit config without trace= starts no session
    executor.execute(x, SCRBConfig(**FAST))
    assert not obs_trace.active()
    assert os.listdir(tmp_path) == ["fit_trace"]
    assert len(_profile_dirs(path)) == 1


def test_fit_spans_land_in_an_outside_session(blobs, tmp_path):
    x, _ = blobs
    with _session(tmp_path):                          # nothing else
        executor.execute(x, SCRBConfig(**FAST, trace=str(tmp_path / "x")))
    assert not (tmp_path / "x").exists()              # recorded into ours
    evs = _events(tmp_path, {"fit", "rb_features", "eigensolve", "kmeans"})
    assert [e.name for e in evs][0] == "fit"
    assert {e.name for e in evs} == {"fit", "rb_features", "eigensolve",
                                     "kmeans"}
    solve, = (e for e in evs if e.name == "eigensolve")
    assert solve.attrs["iterations"] >= 1
    assert "resnorm_max" in solve.attrs


def test_config_trace_excluded_from_artifact_dict(tmp_path):
    cfg = SCRBConfig(**FAST, trace=str(tmp_path / "t"))
    d = cfg.to_dict()
    assert "trace" not in d
    rt = SCRBConfig(**d)                              # older-loader shape
    assert rt.trace is None and rt.n_grids == cfg.n_grids


def test_partitioned_traced_fit_has_worker_tracks(tmp_path):
    x, _ = make_blobs(600, 6, 4, seed=0)
    cfg = SCRBConfig(n_clusters=4, n_grids=32, sigma=1.5, d_g=256,
                     solver_tol=1e-2, kmeans_replicates=1, seed=0,
                     partition=PartitionOptions(n_partitions=3, workers=2),
                     trace=str(tmp_path))
    res = executor.execute(x, cfg)
    assert res.labels.shape == (600,)
    evs = _events(tmp_path, {"fit", "partition_fit"})
    root, = (e for e in evs if e.name == "fit"
             and e.attrs["placement"] == "partitioned")
    parts = [e for e in evs if e.name == "partition_fit"]
    assert len(parts) == 3
    assert {e.attrs["partition"] for e in parts} == {0, 1, 2}
    lines = {e.line for e in parts}
    assert len(lines) >= 2 and root.line not in lines  # worker lanes
    for e in parts:                                   # inside the root
        assert _inside(e, root)
    # each partition's own fit nests inside its partition_fit span
    subs = [e for e in evs if e.name == "fit"
            and e.attrs["placement"] == "single"]
    assert len(subs) == 3
    assert all(any(s.line == p.line and _inside(s, p) for p in parts)
               for s in subs)


# -- engine + server wiring ------------------------------------------------

@pytest.fixture(scope="module")
def model(blobs):
    x, _ = blobs
    return SCRBModel.fit(x, SCRBConfig(**FAST)), x


def test_engine_stats_parity_and_latency_quantiles(model):
    mdl, x = model
    eng = ClusterEngine(EngineConfig(buckets=(32, 64)))
    eng.load_model("m", mdl)
    s = eng.stats("m")
    assert set(STAT_KEYS) <= set(s)                   # legacy keys, zeroed
    assert all(s[k] == 0 for k in STAT_KEYS)
    for _ in range(4):
        np.testing.assert_array_equal(eng.predict("m", x[:20]),
                                      mdl.predict(x[:20]))
    s = eng.stats("m")
    assert s["batches"] == 4 and s["rows_served"] == 80
    assert s["compiles"] == 1 and s["cache_hits"] == 3
    assert isinstance(s["batches"], int)              # ints, not floats
    sm = eng.stats()["models"]["m"]                   # latency keys live here
    assert sm["latency_predict_p50_ms"] > 0
    assert sm["latency_predict_p99_ms"] >= sm["latency_predict_p50_ms"]
    lq = eng.latency_quantiles("m")
    assert 0 < lq[0.5] <= lq[0.99]
    assert eng.latency_quantiles("m", "transform")[0.5] is None  # no traffic
    with pytest.raises(KeyError):
        eng.stats("nope")


def test_engine_batch_spans_phases_and_queue_wait(model, tmp_path):
    mdl, x = model
    eng = ClusterEngine(EngineConfig(buckets=(32, 64)))
    eng.load_model("m", mdl)
    eng.warmup("m")
    phases = ["engine.stage", "engine.h2d", "engine.dispatch",
              "engine.readback"]
    with _session(tmp_path):
        t_small = eng.submit("m", x[:20])
        t_big = eng.submit("m", x[20:120])    # 20 + 100 rows: two batches
        before = time.perf_counter()
        eng.drain()
        after = time.perf_counter()
    small, big = eng.take(t_small), eng.take(t_big)
    np.testing.assert_array_equal(small.values, mdl.predict(x[:20]))
    np.testing.assert_array_equal(big.values, mdl.predict(x[20:120]))

    evs = _events(tmp_path, {"engine.batch", *phases})
    batches = [e for e in evs if e.name == "engine.batch"]
    assert len(batches) == 2
    dispatched = []
    for b in batches:
        inner = [e for e in evs if e.name != "engine.batch"
                 and e.line == b.line and _inside(e, b)]
        # each step stages, copies and dispatches the batches it sends (the
        # second one only while the first is in flight), then reads back one
        sent = (len(inner) - 1) // 3
        assert [e.name for e in inner] == phases[:3] * sent + phases[3:]
        dispatched += [e.attrs["overlapped"] for e in inner
                       if e.name == "engine.dispatch"]
        assert b.attrs["model"] == "m" and b.attrs["mode"] == "predict"
        assert b.attrs["bucket"] == 64
    assert dispatched in ([0, 1], [0, 0])
    assert eng.stats()["overlapped_batches"] == sum(dispatched)
    first, second = batches
    assert (first.attrs["rows"], second.attrs["rows"]) == (64, 56)
    # the first batch takes both requests' first rows; the second only
    # the rest of the big one
    assert (first.attrs["requests"], second.attrs["requests"]) == (2, 0)
    assert second.attrs["queue_wait_us"] == 0
    waited = [(before - r.submitted_at, after - r.submitted_at)
              for r in (small, big)]
    lo, hi = (1e6 * sum(w[i] for w in waited) for i in (0, 1))
    assert lo <= first.attrs["queue_wait_us"] <= hi
    hist = eng.registry.get("engine_queue_wait_seconds")
    assert hist.count(model="m", mode="predict") == 2   # requests served
    assert hist.sum(model="m", mode="predict") * 1e6 == pytest.approx(
        first.attrs["queue_wait_us"])
    sm = eng.stats()["models"]["m"]
    assert 0 < sm["queue_wait_predict_p50_ms"] <= sm[
        "queue_wait_predict_p99_ms"]


def test_engine_registries_are_isolated(model):
    mdl, x = model
    a, b = (ClusterEngine(EngineConfig(buckets=(32,))) for _ in range(2))
    a.load_model("m", mdl)
    b.load_model("m", mdl)
    a.predict("m", x[:8])
    assert a.stats("m")["batches"] == 1
    assert b.stats("m")["batches"] == 0               # no cross-engine bleed


def test_metrics_text_and_http_roundtrip(model):
    mdl, x = model
    eng = ClusterEngine(EngineConfig(buckets=(32, 64)))
    eng.load_model("m", mdl)
    eng.predict("m", x[:10])
    text = eng.metrics_text()
    assert 'engine_requests_total{model="m",mode="predict"} 1' in text
    assert "# TYPE engine_request_latency_seconds histogram" in text
    assert "# TYPE engine_queue_wait_seconds histogram" in text
    assert "engine_resident_models 1" in text
    assert "repro_stage_seconds" in text              # global registry merged

    with ClusterServer(eng) as srv:
        with urllib.request.urlopen(srv.url + "/metrics") as r:
            assert r.status == 200
            assert r.headers["Content-Type"].startswith("text/plain")
            body = r.read().decode()
        assert 'engine_requests_total{model="m",mode="predict"} 1' in body
        with urllib.request.urlopen(srv.url + "/v1/stats") as r:
            stats = json.loads(r.read())
        assert stats["models"]["m"]["latency_predict_p50_ms"] > 0
