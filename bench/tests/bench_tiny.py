"""Tiny cells for the CPU tests: a benchmark defined only by files in a
temporary directory (its cells, configuration, traffic mixes, and copies of
the traffic kinds and metric readers), run through ``harness.run``."""
from __future__ import annotations

import contextlib
import json
import os
import shutil
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import harness  # noqa: E402

CONFIG = {"name": "tiny", "source": "test", "generator": "blobs",
          "n": 600, "d": 4, "k": 3, "n_grids": 32, "d_g": 64,
          "solver_tol": 1e-4, "solver_iters": 300, "kmeans_iters": 25,
          "kmeans_replicates": 10, "ari_min": 0.95}
FIT_LIMITS = {"dual_err": 0, "eig_residual": 1e-4, "sigma_err": 1e-4,
              "ari_loss": 0.05}
SERVE_LIMITS = {"missing": 0, "label_gap": 1e-5}
# The fit cell's metrics, which BENCHMARK.json holds only while it has a fit
# cell: the readers are files under bench/metrics all the same.
FIT_END_TO_END = [{"name": "fit_s", "unit": "s", "better": "lower",
                   "bound": 0.01, "source": "host_clock",
                   "workloads": ["tiny.fit"]}]
FIT_PER_LAYER = [
    {"name": name, "unit": unit, "better": better, "source": source,
     "layer": layer, "moves": "fit_s", "workloads": ["tiny.fit"]}
    for name, unit, better, source, layer in (
        ("eigensolve_iters", "iterations", "lower", "program_counter",
         "eigensolver"),
        ("gram_matvec_roofline_pct", "%", "higher", "device_trace",
         "kernels"),
        ("rb_binning_roofline_pct", "%", "higher", "device_trace",
         "kernels"),
        ("device_idle_pct.fit", "%", "lower", "device_trace", "device"))]


def make(root: str) -> str:
    """Write the tiny benchmark under ``root``; returns its bench dir."""
    bench = os.path.join(root, "bench")
    for sub in ("cells", "configs", "traffic", "metrics"):
        os.makedirs(os.path.join(bench, sub), exist_ok=True)

    def put(rel, obj):
        with open(os.path.join(bench, rel), "w") as f:
            json.dump(obj, f)

    put("configs/tiny.json", CONFIG)
    put("traffic/fit_repeat.json", {"kind": "fit_repeat", "jobs": 2,
                                    "checked_fits": 2})
    put("traffic/serve_open_loop.json", {
        "kind": "serve_open_loop", "arrivals": "poisson", "rows_min": 1,
        "rows_max": 1024, "pool_rows": 4096, "traced_seconds": 0.5,
        "check_values": 1 << 30})
    put("cells/tiny.fit.json", {"config": "tiny", "traffic": "fit_repeat",
                                "rows": 600, "fit_set_seed": 5,
                                "limits": FIT_LIMITS})
    put("cells/tiny.serve.json", {"config": "tiny",
                                  "traffic": "serve_open_loop",
                                  "train_rows": 600, "rate_rps": 40,
                                  "limits": SERVE_LIMITS})
    for kind in ("fit_repeat", "serve_open_loop"):
        shutil.copy(os.path.join(BENCH, "traffic", f"{kind}.py"),
                    os.path.join(bench, "traffic", f"{kind}.py"))
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        benchmark = json.load(f)
    for m in benchmark["end_to_end"] + benchmark["per_layer"]:
        if "workloads" in m:
            m["workloads"] = sorted({w.replace("poker.", "tiny.")
                                     for w in m["workloads"]})
    names = {m["name"] for m in benchmark["end_to_end"]
             + benchmark["per_layer"]}
    benchmark["end_to_end"] += [m for m in FIT_END_TO_END
                                if m["name"] not in names]
    benchmark["per_layer"] += [m for m in FIT_PER_LAYER
                               if m["name"] not in names]
    for m in benchmark["per_layer"]:
        shutil.copy(os.path.join(BENCH, "metrics", f"{m['name']}.py"),
                    os.path.join(bench, "metrics", f"{m['name']}.py"))
    shutil.copy(os.path.join(BENCH, "metrics", "_probe.py"),
                os.path.join(bench, "metrics", "_probe.py"))
    benchmark["workloads"] = [
        {"name": "tiny.fit", "config": "tiny", "traffic": "fit_repeat",
         "chips": 1, "why": "test"},
        {"name": "tiny.serve", "config": "tiny", "traffic": "serve_open_loop",
         "chips": 1, "why": "test"}]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(benchmark, f)
    return bench


@contextlib.contextmanager
def compile_cache(path: str):
    """JAX's persistent compilation cache in ``path`` for the duration, as
    ``harness.use_compile_cache`` sets it for a run, then as it was: a fit's
    eager LOBPCG loop is lowered anew per fit and found in that cache."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    before = {k: getattr(jax.config, k) for k in keys}
    cc.reset_cache()
    jax.config.update(keys[0], path)
    jax.config.update(keys[1], 0.0)
    jax.config.update(keys[2], -1)
    try:
        yield
    finally:
        for k, v in before.items():
            jax.config.update(k, v)
        cc.reset_cache()


def run(bench: str, cell: str, *, seed: int = 2**31 + 7,
        seconds: float = 0.5, trace: bool = False) -> dict:
    """One run of a tiny cell on the CPU through the harness."""
    import time
    lines = []
    with compile_cache(os.path.join(os.path.dirname(bench), "jax_cache")):
        out = harness.run(cell, seed, seconds, trace,
                          t_start=time.perf_counter(), bench_dir=bench,
                          require_tpu=False, compile_cache=False,
                          emit=lines.append)
    assert json.loads(lines[-1]) == out
    return out
