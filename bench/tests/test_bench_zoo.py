"""The ``zoo`` deployment's traffic kind ``serve_zipf`` at a tiny size on the
CPU: a two-member zoo cell run through the harness, the repo's cell found
from ``BENCHMARK.json``, a cross-model fault and each member's bfloat16
control that the check must catch, and a schedule whose per-model totals do
not depend on the seed."""
import json
import os
import shutil
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_tiny  # noqa: E402
import harness  # noqa: E402

MEMBER = {"d": 4, "k": 3, "d_g": 64, "spread": 0.25}
# centers close enough that many served rows lie near a boundary, where a
# bfloat16 control labels some of them otherwise
CONFIG = {"name": "tinyzoo", "source": "test", "generator": "blobs",
          "members": [dict(MEMBER, name="a", center_norm=0.5),
                      dict(MEMBER, name="b", center_norm=0.75)],
          "n_grids": 32, "solver_tol": 1e-4, "solver_iters": 300,
          "kmeans_iters": 25, "kmeans_replicates": 10, "ari_min": 0.95}
LIMITS = {"missing": 0, "label_gap": 1e-5, "evictions": 0}
CELL = "tinyzoo.serve_zipf"


def make(root: str) -> str:
    """``bench_tiny``'s benchmark plus a two-member zoo cell."""
    bench = bench_tiny.make(root)

    def put(rel, obj):
        with open(os.path.join(bench, rel), "w") as f:
            json.dump(obj, f)

    put("configs/tinyzoo.json", CONFIG)
    put("traffic/serve_zipf.json", {
        "kind": "serve_zipf", "arrivals": "poisson", "models": 2,
        "zipf_s": 1.1, "rows_min": 1, "rows_max": 1024, "pool_rows": 4096,
        "traced_seconds": 0.5, "check_values": 1 << 30})
    put(f"cells/{CELL}.json", {"config": "tinyzoo", "traffic": "serve_zipf",
                               "train_rows": 600, "rate_rps": 40,
                               "limits": LIMITS})
    shutil.copy(os.path.join(bench_tiny.BENCH, "traffic", "serve_zipf.py"),
                os.path.join(bench, "traffic", "serve_zipf.py"))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        benchmark = json.load(f)
    for m in benchmark["end_to_end"] + benchmark["per_layer"]:
        if "zoo.serve_zipf" in m.get("workloads", ()):
            m["workloads"] = [CELL if w == "zoo.serve_zipf" else w
                              for w in m["workloads"]]
    benchmark["workloads"].append({"name": CELL, "config": "tinyzoo",
                                   "traffic": "serve_zipf", "chips": 1,
                                   "why": "test"})
    with open(path, "w") as f:
        json.dump(benchmark, f)
    return bench


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return make(str(tmp_path_factory.mktemp("zoobench")))


@pytest.mark.parametrize("trace", [False, True], ids=["window", "traced"])
def test_tiny_zoo_cell_runs(bench, trace):
    out = bench_tiny.run(bench, CELL, seed=2**31 + 31, seconds=1.0,
                         trace=trace)
    assert out["correct"] is True, out["checks"]
    assert set(out["checks"]) == set(LIMITS)
    assert out["failed"] == 0
    if trace:
        m = out["metrics"]
        for name in ("engine_switch_pct", "engine_step_ms",
                     "engine_batch_rows"):
            assert m[name]["value"] is not None, name
        assert 0 < m["engine_switch_pct"]["value"] <= 100
    else:
        assert set(out["metrics"]) == {"setup_s", "serve_p50_ms",
                                       "serve_rows_per_s"}
        assert out["attempted"] == 40      # 2 epochs of 20 requests


def test_repo_zoo_cell_is_found():
    cell = harness.find_cell("zoo.serve_zipf")
    assert cell.chips == 1 and cell.mix["kind"] == "serve_zipf"
    assert len(cell.config["members"]) == cell.mix["models"] == 4
    assert set(cell.readers) == {"device_idle_pct.serve", "engine_step_ms",
                                 "engine_batch_rows", "engine_switch_pct"}
    assert {m["name"] for m in cell.end_to_end} == {
        "setup_s", "serve_p50_ms", "serve_rows_per_s"}
    assert cell.spec["limits"] == {"missing": 0, "label_gap": 0.001,
                                   "evictions": 0}


def test_cross_model_fault_comes_out_not_correct(bench, monkeypatch):
    """Member a's batches served with member b's state and cells (same d,
    K and d_g), once b is loaded."""
    from repro.serve.cluster_engine import ClusterEngine
    real = ClusterEngine.submit

    def crossed(self, name, x, mode="predict"):
        return real(self, "b" if name == "a" and "b" in self.models
                    else name, x, mode)

    monkeypatch.setattr(ClusterEngine, "submit", crossed)
    out = bench_tiny.run(bench, CELL, seed=2**31 + 37, seconds=1.0)
    assert out["correct"] is False, out["checks"]
    assert out["checks"]["missing"]["value"] == 0


def test_bfloat16_control_comes_out_not_correct(bench):
    """``calibrate_zoo``: the program's labels pass the cell's check, and
    the reference with bfloat16 operands put in either member's place does
    not."""
    import jax

    import calibrate_zoo
    cell = harness.find_cell(CELL, bench_dir=bench)
    ctx = harness.Context(cell, 2**31 + 41, jax,
                          os.path.join(os.path.dirname(bench), "calib"))
    os.makedirs(ctx.out_dir, exist_ok=True)
    with bench_tiny.compile_cache(os.path.join(os.path.dirname(bench),
                                               "jax_cache")):
        cell.kind.setup(ctx)
        cell.kind.window(ctx, 1.0)
        lines = calibrate_zoo.judge_controls(ctx, cell.kind)
    program, *controls = lines
    assert program["correct"] is True and program["label_gap"] == 0.0
    assert [c["member"] for c in controls] == ["a", "b"]
    for c in controls:
        assert c["correct"] is False, c
        assert c["label_gap"] > LIMITS["label_gap"]
        assert all(gap == 0.0 for name, gap in c["label_gaps"].items()
                   if name != c["member"])


def test_schedule_totals_do_not_depend_on_the_seed():
    cell = harness.find_cell("zoo.serve_zipf")
    kind, mix = cell.kind, cell.mix
    scheds = [kind.schedule(seed, 200.0, 51.0, mix, mix["pool_rows"])
              for seed in (2**31 + 1, 2**33 + 5)]
    shares = kind.zipf_shares(4, mix["zipf_s"])
    np.testing.assert_allclose(shares, [0.504, 0.235, 0.151, 0.110],
                               atol=5e-4)
    per_epoch = 425                                 # 200/s × 51 s / 24
    for s in scheds:
        assert s["due"].shape == (24 * per_epoch,)
        assert np.all(np.diff(s["due"]) >= 0) and s["due"][-1] <= 51.0
        assert np.all(s["offsets"] + s["sizes"] <= mix["pool_rows"])
        epochs = s["member"].reshape(24, per_epoch)
        tops = {tuple(np.argsort(-np.bincount(e, minlength=4),
                                 kind="stable")) for e in epochs}
        assert len(tops) == 24                      # every order once
        assert tuple(np.argsort(-np.bincount(epochs[0], minlength=4),
                                kind="stable")) == (0, 1, 2, 3)
        ranked = np.argsort(-np.bincount(epochs[0], minlength=4),
                            kind="stable")
        first = [np.sort(s["sizes"][:per_epoch][epochs[0] == m])
                 for m in ranked]
        for e, members in enumerate(epochs):    # each rank's sizes, every
            sizes = s["sizes"][e * per_epoch:(e + 1) * per_epoch]  # epoch
            ranked = np.argsort(-np.bincount(members, minlength=4),
                                kind="stable")
            for r, m in enumerate(ranked):
                np.testing.assert_array_equal(
                    np.sort(sizes[members == m]), first[r])
    for m in range(4):
        a, b = (np.sort(s["sizes"][s["member"] == m]) for s in scheds)
        assert a.size == b.size == 6 * per_epoch
        np.testing.assert_array_equal(a, b)
    assert not np.array_equal(scheds[0]["member"], scheds[1]["member"])
    latin = kind.latin_orders(2**31 + 1, 4)
    assert all(sorted(col) == [0, 1, 2, 3] for col in zip(*latin))
    assert all(sorted(row) == [0, 1, 2, 3] for row in latin)
