"""The ``engine_overlap_pct`` reader on two small serving traces recorded on
the CPU: ``testdata/cpu_overlap_trace.xplane.pb`` (made by
``testdata/record_cpu_overlap_trace.py``), from an engine that dispatches a
batch while the one before it is in flight, against the share counted here
from the same events; and ``testdata/cpu_serve_trace.xplane.pb``, from an
engine whose ``engine.dispatch`` spans carry no ``overlapped`` attribute,
where the reader is silent."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_tiny  # noqa: E402
import harness  # noqa: E402
import spans  # noqa: E402

BENCH = bench_tiny.BENCH
TRACE = os.path.join(BENCH, "testdata", "cpu_overlap_trace.xplane.pb")
SERIAL_TRACE = os.path.join(BENCH, "testdata", "cpu_serve_trace.xplane.pb")
CPU = {"device_plane": r"^/host:CPU$", "ops_line": r"^tf_XLA",
       "modules_line": r"^python$", "host_plane": r"^/host:CPU$",
       "skip": r"^(ThreadpoolListener|SlinkyThreadPool|ThunkExecutor|end: )"}


def _read(loaded, traced=True):
    cell = harness.Cell("tiny.serve", {}, {}, {}, None, [], [], {}, 1)
    ctx = harness.Context(cell, 1, None, BENCH)
    ctx.trace = {"trace": None} if traced else None
    ctx.state["spans"] = loaded
    mod = harness.load_module(
        os.path.join(BENCH, "metrics", "engine_overlap_pct.py"),
        "engine_overlap_pct")
    return mod.read(ctx)


def _dispatches(path):
    """``overlapped`` of every ``engine.dispatch`` event inside the trace's
    ``serve`` annotation, read straight from ``ProfileData``."""
    from jax.profiler import ProfileData
    events = [(e.name, int(e.start_ns), int(e.start_ns) + int(e.duration_ns),
               dict(e.stats))
              for plane in ProfileData.from_file(path).planes
              for line in plane.lines for e in line.events]
    (lo, hi), = [(s, e) for n, s, e, _a in events if n == "serve"]
    return [a.get("overlapped") for n, s, e, a in events
            if n == "engine.dispatch" and lo <= s and e <= hi]


def test_overlap_share_matches_the_events():
    marks = _dispatches(TRACE)
    # six rounds of 1 + 4 + 2 + 1 + 3 + 1 batches of up to 512 rows
    assert len(marks) == 12 and set(marks) <= {0, 1}
    got = _read(spans.load(TRACE, patterns=CPU))
    assert 0 <= got <= 100
    assert got == pytest.approx(100.0 * sum(marks) / len(marks), rel=1e-12)
    # a round's first batch finds nothing in flight
    assert 0 < sum(marks) <= 12 - 6


def test_overlap_share_is_silent_without_the_attribute():
    assert set(_dispatches(SERIAL_TRACE)) == {None}
    assert _read(spans.load(SERIAL_TRACE, patterns=CPU)) is None
    assert _read(spans.load(TRACE, window="no_such", patterns=CPU)) is None
    assert _read(None, traced=False) is None
