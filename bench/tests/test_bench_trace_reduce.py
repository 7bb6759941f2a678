"""trace_reduce on a small trace recorded on the CPU
(``testdata/cpu_trace.xplane.pb``, made by ``testdata/record_cpu_trace.py``).

The CPU has no device plane: its XLA ops run on the ``tf_XLAPjRtCpuClient``
thread of the host plane, which stands in for the device here, and the
python thread's ``PjitFunction(...)`` events stand in for XLA modules."""
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import trace_reduce  # noqa: E402

TRACE = os.path.join(BENCH, "testdata", "cpu_trace.xplane.pb")
CPU = {"device_plane": r"^/host:CPU$", "ops_line": r"^tf_XLAPjRtCpuClient",
       "modules_line": r"^python$", "host_plane": r"^/host:CPU$",
       "skip": r"^(ThreadpoolListener|SlinkyThreadPool|ThunkExecutor|end: )"}


@pytest.fixture(scope="module")
def trace():
    return trace_reduce.load(TRACE)


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce(TRACE, window="fit", patterns=CPU)


def _ops(trace):
    import re
    return [(s, e) for p, line, evs in trace
            if p == "/host:CPU" and line.startswith("tf_XLAPjRtCpuClient")
            for name, s, e in evs if not re.search(CPU["skip"], name)]


def _busy_by_grid(intervals, lo, hi):
    """Covered length of [lo, hi) at 1 µs resolution, by painting."""
    cover = np.zeros((hi - lo) // 1000 + 1, bool)
    for s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            cover[(s - lo) // 1000:(e - lo) // 1000] = True
    return cover.sum() * 1e-6


def test_union_on_hand_intervals():
    assert trace_reduce.union([(5, 9), (0, 3), (2, 4), (9, 10)]) == \
        [(0, 4), (5, 10)]
    assert trace_reduce.union([]) == []


def test_busy_union_matches_painting(trace, reduced):
    lo, hi = trace_reduce.annotation_window(trace, "fit", CPU["host_plane"])
    assert reduced["window_s"] == pytest.approx((hi - lo) * 1e-9)
    want = _busy_by_grid(_ops(trace), lo, hi)
    assert reduced["busy_s"] > 0
    assert reduced["busy_s"] == pytest.approx(want, abs=2e-5 * len(_ops(trace)))


def test_idle_share_counts_the_sleep(reduced):
    idle = 1.0 - reduced["busy_s"] / reduced["window_s"]
    assert 0.0 < idle < 1.0
    assert reduced["window_s"] - reduced["busy_s"] >= 0.045


def test_module_time_and_calls(trace):
    mods = [(s, e) for p, line, evs in trace if line == "python"
            for name, s, e in evs if name == "PjitFunction(bench_probe)"]
    # two calls; the host stand-in records each dispatch at two levels
    assert len(mods) in (2, 4)
    got = trace_reduce.module_seconds(trace, "PjitFunction(bench_probe)", CPU)
    assert got == pytest.approx(sum(e - s for s, e in mods) * 1e-9)
    assert trace_reduce.module_calls(trace, "PjitFunction(bench_probe)",
                                     CPU) == len(mods)
    assert trace_reduce.module_seconds(trace, "no_such_module", CPU) == 0.0


def test_longest_gap_is_labelled_by_the_wait(reduced):
    gaps = reduced["breakdown"]["idle_gaps"]
    assert 1 <= len(gaps) <= 10
    label, seconds = gaps[0]
    assert label.startswith("loadgen.wait")
    assert seconds >= 0.045
    assert [g[1] for g in gaps] == sorted((g[1] for g in gaps), reverse=True)


def test_top_device_ops_are_sorted_and_named(reduced):
    ops = reduced["breakdown"]["device_ops"]
    assert ops and len(ops) <= 10
    assert all(isinstance(n, str) and t > 0 for n, t in ops)
    assert [t for _, t in ops] == sorted((t for _, t in ops), reverse=True)
    assert any("dot_general" in n for n, _ in ops)


def test_unknown_annotation_is_an_error(trace):
    with pytest.raises(ValueError):
        trace_reduce.annotation_window(trace, "no_such_span", r"^/host:CPU$")
