"""The program-span readers (``spans.py`` and the four ``engine_*`` /
``device_idle_engine_pct.serve`` metrics) on a small serving trace recorded
on the CPU (``testdata/cpu_serve_trace.xplane.pb``, made by
``testdata/record_cpu_serve_trace.py``), against numbers computed here from
the same events; and a traced run of the tiny serving cell.

As in ``test_bench_trace_reduce.py``, the CPU's XLA ops run on the
``tf_XLA...`` thread lines of the host plane, which stand in for the
device."""
import json
import os
import re
import shutil
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_tiny  # noqa: E402
import harness  # noqa: E402
import spans  # noqa: E402
import trace_reduce  # noqa: E402

BENCH = bench_tiny.BENCH
TRACE = os.path.join(BENCH, "testdata", "cpu_serve_trace.xplane.pb")
FIT_TRACE = os.path.join(BENCH, "testdata", "cpu_trace.xplane.pb")
CPU = {"device_plane": r"^/host:CPU$", "ops_line": r"^tf_XLA",
       "modules_line": r"^python$", "host_plane": r"^/host:CPU$",
       "skip": r"^(ThreadpoolListener|SlinkyThreadPool|ThunkExecutor|end: )"}
READERS = ("engine_batch_ms", "engine_readback_ms", "engine_queue_wait_ms",
           "device_idle_engine_pct.serve")


def _raw():
    """Every host event of the recorded trace: (line, name, start, end,
    attrs), read straight from ``ProfileData``."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(TRACE).planes:
        for line in plane.lines:
            for e in line.events:
                s = int(e.start_ns)
                out.append((line.name, e.name, s, s + int(e.duration_ns),
                            dict(e.stats)))
    return out


@pytest.fixture(scope="module")
def raw():
    return _raw()


@pytest.fixture(scope="module")
def expected(raw):
    """The four numbers, computed from the raw events by hand."""
    (lo, hi), = [(s, e) for _l, n, s, e, _a in raw if n == "serve"]
    batches = [(s, e, a) for _l, n, s, e, a in raw
               if n == "engine.batch" and lo <= s and e <= hi]
    reads = [e - s for _l, n, s, e, _a in raw
             if n == "engine.readback" and lo <= s and e <= hi]
    ops = [(s, e) for line, n, s, e, _a in raw if line.startswith("tf_XLA")
           and not re.search(CPU["skip"], n)]
    # paint the window at 1 µs: device busy, host inside engine.batch
    cells = (hi - lo) // 1000 + 1
    busy = np.zeros(cells, bool)
    engine = np.zeros(cells, bool)
    for grid, intervals in ((busy, ops), (engine, [b[:2] for b in batches])):
        for s, e in intervals:
            s, e = max(s, lo), min(e, hi)
            if e > s:
                grid[(s - lo) // 1000:(e - lo) // 1000] = True
    return {
        "batches": len(batches),
        "engine_batch_ms": np.mean([e - s for s, e, _ in batches]) * 1e-6,
        "engine_readback_ms": np.mean(reads) * 1e-6,
        "engine_queue_wait_ms": sum(a["queue_wait_us"] for *_x, a in batches)
        / sum(a["requests"] for *_x, a in batches) / 1e3,
        "device_idle_engine_pct.serve":
            100.0 * np.sum(engine & ~busy) * 1e3 / (hi - lo),
        "requests": sum(a["requests"] for *_x, a in batches),
        # painting moves each interval's edges by under 1 µs
        "paint_pct": 100.0 * 2e3 * (len(batches) + len(ops)) / (hi - lo)}


def _ctx(loaded):
    cell = harness.Cell("tiny.serve", {}, {}, {}, None, [], [], {}, 1)
    ctx = harness.Context(cell, 1, None, BENCH)
    ctx.trace = {"trace": None}
    ctx.state["spans"] = loaded
    return ctx


def _read(name, ctx):
    mod = harness.load_module(os.path.join(BENCH, "metrics", f"{name}.py"),
                              name)
    return mod.read(ctx)


def test_trace_holds_the_program_spans(expected):
    # the recorder's six rounds at 64 rows a batch: 1 + 3 + 1 + 1 + 3 + 1
    # batches, and each of the ten requests' first rows taken once
    assert expected["batches"] == 10
    assert expected["requests"] == 10


def test_load_keeps_the_spans_inside_the_window():
    loaded = spans.load(TRACE, patterns=CPU)
    lo, hi = loaded["window"]
    assert set(loaded["events"]) == set(spans.NAMES)
    for name in spans.NAMES:
        evs = loaded["events"][name]
        assert len(evs) == 10, name
        assert all(lo <= s < e <= hi for s, e, _ in evs)
    for s, e, a in loaded["events"]["engine.batch"]:
        assert a["model"] == "m" and a["mode"] == "predict"
        assert a["requests"] >= 0 and a["queue_wait_us"] >= 0
    assert loaded["busy"]                     # the CPU's op lines


@pytest.mark.parametrize("name", READERS)
def test_reader_matches_the_events(name, expected):
    got = _read(name, _ctx(spans.load(TRACE, patterns=CPU)))
    painted = name.startswith("device")
    assert got == pytest.approx(expected[name], rel=1e-6,
                                abs=expected["paint_pct"] if painted else 0)


def test_readers_agree_with_each_other_and_the_idle_share():
    ctx = _ctx(spans.load(TRACE, patterns=CPU))
    got = {name: _read(name, ctx) for name in READERS}
    reduced = trace_reduce.reduce(TRACE, window="serve", patterns=CPU)
    idle = 100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"])
    assert 0 < got["engine_readback_ms"] < got["engine_batch_ms"]
    assert 0 < got["device_idle_engine_pct.serve"] <= idle
    assert got["engine_queue_wait_ms"] >= 0


@pytest.mark.parametrize("name", READERS)
def test_reader_is_silent_without_the_spans(name):
    # a trace of a program without engine spans, and one without the window
    assert _read(name, _ctx(spans.load(FIT_TRACE, window="fit",
                                       patterns=CPU))) is None
    assert _read(name, _ctx(spans.load(TRACE, window="no_such",
                                       patterns=CPU))) is None
    untraced = _ctx(None)
    untraced.trace = None
    assert _read(name, untraced) is None


def test_overlap_of_merged_intervals():
    assert spans._overlap([(0, 4), (6, 10)], [(2, 7), (9, 12)]) == 4
    assert spans._overlap([], [(0, 5)]) == 0


# BENCHMARK.json does not list these readers yet, so the tiny benchmark
# here gets their entries, and copies of their files, on top of its own.
ENTRIES = [{"name": name, "unit": unit, "better": "lower", "source": source,
            "layer": "serving", "moves": "serve_p50_ms",
            "workloads": ["tiny.serve"]}
           for name, unit, source in (
               ("engine_batch_ms", "ms", "program_span"),
               ("engine_readback_ms", "ms", "program_span"),
               ("engine_queue_wait_ms", "ms", "program_span"),
               ("device_idle_engine_pct.serve", "%", "device_trace"))]


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("tinybench"))
    bench = bench_tiny.make(root)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        benchmark = json.load(f)
    names = {m["name"] for m in benchmark["per_layer"]}
    benchmark["per_layer"] += [m for m in ENTRIES if m["name"] not in names]
    with open(path, "w") as f:
        json.dump(benchmark, f)
    for name in READERS:
        shutil.copy(os.path.join(BENCH, "metrics", f"{name}.py"),
                    os.path.join(bench, "metrics", f"{name}.py"))
    return bench


def test_tiny_serve_cell_traced(bench):
    out = bench_tiny.run(bench, "tiny.serve", trace=True)
    assert out["correct"] is True, out["checks"]
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(READERS) <= set(m)
    assert all(m[k] is not None for k in READERS)
    assert 0 < m["engine_readback_ms"] < m["engine_batch_ms"]
    assert 0 <= m["device_idle_engine_pct.serve"] <= m[
        "device_idle_pct.serve"]
    assert m["engine_queue_wait_ms"] >= 0
    assert {"engine_step_ms", "engine_batch_rows"} <= set(m)
