"""The harness at a tiny size on the CPU: a cell found from files alone,
its traffic run through the harness's functions, the open-loop timing, and
the command's refusal to run without a TPU."""
import os
import subprocess
import sys
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_tiny  # noqa: E402
import harness  # noqa: E402

REPO = os.path.dirname(bench_tiny.BENCH)


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return bench_tiny.make(str(tmp_path_factory.mktemp("tinybench")))


def test_cell_defined_by_files_is_found(bench):
    cell = harness.find_cell("tiny.fit", bench_dir=bench)
    assert cell.config["d"] == 4 and cell.spec["rows"] == 600
    assert cell.mix["kind"] == "fit_repeat"
    assert hasattr(cell.kind, "window") and hasattr(cell.kind, "check")
    assert [m["name"] for m in cell.end_to_end] == ["setup_s", "fit_s"]
    assert set(cell.readers) == {
        "eigensolve_iters", "gram_matvec_roofline_pct",
        "rb_binning_roofline_pct", "device_idle_pct.fit"}
    assert all(hasattr(r, "read") for r in cell.readers.values())
    serve = harness.find_cell("tiny.serve", bench_dir=bench)
    assert {m["name"] for m in serve.end_to_end} == {
        "setup_s", "serve_p50_ms", "serve_rows_per_s"}
    assert set(serve.readers) == {"device_idle_pct.serve", "engine_step_ms",
                                  "engine_batch_rows"}
    with pytest.raises(harness.BenchError):
        harness.find_cell("tiny.nothing", bench_dir=bench)


def test_tiny_fit_cell_runs(bench):
    out = bench_tiny.run(bench, "tiny.fit")
    assert out["correct"] is True, out["checks"]
    assert set(out["metrics"]) == {"setup_s", "fit_s"}
    assert out["metrics"]["fit_s"]["value"] > 0
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert set(out["checks"]) == set(bench_tiny.FIT_LIMITS)


def test_tiny_fit_cell_traced(bench):
    out = bench_tiny.run(bench, "tiny.fit", trace=True)
    assert out["correct"] is True, out["checks"]
    assert out["metrics"]["eigensolve_iters"]["value"] >= 0
    assert out["device"]["window_s"] > 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


def test_tiny_serve_cell_runs(bench):
    out = bench_tiny.run(bench, "tiny.serve", seconds=1.0)
    assert out["correct"] is True, out["checks"]
    assert set(out["metrics"]) == {"setup_s", "serve_p50_ms",
                                   "serve_rows_per_s"}
    assert out["attempted"] == 40 and out["failed"] == 0
    m = out["metrics"]
    assert m["serve_p50_ms"]["value"] > 0
    assert m["serve_rows_per_s"]["value"] > 0


class _StallingEngine:
    """Answers every pending request in one ``step``; the first step that
    finds a request due at or after ``stall_due`` sleeps ``stall_s``."""

    def __init__(self, stall_due: float, stall_s: float, t0_ref: list):
        self.pending, self.done, self.n = [], {}, 0
        self.stall_due, self.stall_s, self.t0_ref = stall_due, stall_s, t0_ref
        self.stalled = False

    def submit(self, model, rows):
        self.n += 1
        self.pending.append((self.n, len(rows)))
        return self.n

    def step(self):
        now = time.perf_counter() - self.t0_ref[0]
        if not self.stalled and now >= self.stall_due:
            self.stalled = True
            time.sleep(self.stall_s)
        t = time.perf_counter()
        for ticket, rows in self.pending:
            self.done[ticket] = (t, rows)
        self.pending = []

    def take(self, ticket):
        t, rows = self.done.pop(ticket)
        return type("R", (), {"completed_at": t,
                              "values": np.zeros(rows, np.int32)})()


def test_open_loop_times_latency_from_the_due_time(bench, monkeypatch):
    cell = harness.find_cell("tiny.serve", bench_dir=bench)
    kind = cell.kind
    t0_ref = [None]
    real = time.perf_counter

    def clock():
        t = real()
        if t0_ref[0] is None:
            t0_ref[0] = t
        return t

    monkeypatch.setattr(kind.time, "perf_counter", clock)
    engine = _StallingEngine(stall_due=0.1, stall_s=0.3, t0_ref=t0_ref)
    ctx = harness.Context(cell, 1, None, bench)
    ctx.state.update(engine=engine, pool=np.zeros((64, 4), np.float32))
    due = np.array([0.0, 0.05, 0.1, 0.15, 0.2, 0.25, 0.3, 0.5])
    sched = {"due": due, "sizes": np.full(8, 2), "offsets": np.zeros(8, int)}
    out = kind._loop(ctx, sched, annotate=False)
    lat = out["latency"]
    assert np.all(np.isfinite(lat))
    # the stall starts with the request due at 0.1 and ends near 0.4:
    # requests due inside it wait for its end, counted from their due time
    stall_end = 0.1 + 0.3
    for j in (2, 3, 4, 5, 6):
        assert lat[j] >= stall_end - due[j] - 0.01, (j, lat[j])
    assert lat[0] < 0.05 and lat[7] < 0.05
    # the generator submitted those requests late, and says so
    assert out["late"][3] >= 0.2


def test_serve_check_sample_is_seeded_and_keeps_the_largest(bench):
    kind = harness.find_cell("tiny.serve", bench_dir=bench).kind
    sizes = np.array([5, 300, 7, 40, 8000, 12, 900, 3])
    got = [0, 1, 2, 3, 4, 5, 6]                  # request 7 unanswered
    assert kind.compared(1, sizes, got, 10, 1 << 30) == got
    some = kind.compared(1, sizes, got, 10, 10 * 8400)
    assert 4 in some and set(some) <= set(got)
    assert int(np.sum(sizes[some])) * 10 <= 10 * 8400
    assert some == kind.compared(1, sizes, got, 10, 10 * 8400)


def test_command_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench", "run.py"),
         "--workload", "poker.serve", "--seed", str(2**31 + 3), "--seconds",
         "1", "--trace", "0"], cwd=REPO, env=env, capture_output=True,
        text=True, timeout=300)
    assert proc.returncode != 0
    assert not any(line.lstrip().startswith("{")
                   for line in proc.stdout.splitlines())
    assert "no TPU" in proc.stderr
