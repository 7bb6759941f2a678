"""Serving bench: ClusterEngine vs per-request ``model.predict``.

Open-loop request mix over two resident models (2-d rings K=2, 6-d blobs
K=4): ~fifty ragged requests with *unique* row counts (so the per-request
baseline honestly pays one jit specialization per shape), interleaved across
models, ~80% predict / 20% transform, submitted in arrival waves. Three legs:

  cold   — per-request ``model.predict(rows)``, fresh process jit cache:
           every unique (model, shape, mode) compiles. This is what serving
           ad-hoc traffic through the raw model costs today.
  warm   — the same loop again: per-request dispatch with a hot jit cache
           (the best a shape-specialized per-request server could do).
  engine — ``ClusterEngine``: warmup precompiles the (model, bucket, mode)
           grid, then two identical timed runs. Run 2 is steady state: the
           gate pins zero recompiles and zero new staging-ring allocations
           there, plus p50/p99 per-request latency from ticket timestamps.

A fourth leg squeezes both models through ``max_resident_models=1`` to prove
LRU eviction + re-fault keeps results correct (and that compiled cells
survive eviction — the re-fault costs one H2D, zero recompiles).

``--gate`` (CI bench-smoke) fails unless: engine rows/s ≥ 3× cold AND ≥ 1×
warm; p99 ≤ 5× p50; compile count == distinct cells with zero steady-state
recompiles; engine outputs bit-identical to direct ``model.predict``;
steady-state staging allocations zero; LRU leg evicts and stays correct;
and the engine's own ``engine_request_latency_seconds`` histogram quantiles
agree with the external ticket-timestamp p50/p99 within one log-bucket
growth factor. Snapshot JSON goes to ``--out`` (committed as
bench_results/BENCH_PR8.json).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

from repro.core.executor import SCRBConfig
from repro.core.model import SCRBModel
from repro.data.synthetic import make_blobs, make_rings
from repro.serve.cluster_engine import ClusterEngine, EngineConfig

BUCKETS = (64, 256, 1024)


def build_models(smoke: bool, seed: int = 0):
    """Two fitted models with different dims/K so multi-model routing is
    exercised for real (distinct cells, distinct staging shapes)."""
    n = 600 if smoke else 2_000
    grids = 32 if smoke else 64
    dg = 256 if smoke else 1_024
    xr, _ = make_rings(n, 2, seed=seed)
    xb, _ = make_blobs(n, 6, 4, seed=seed + 1)
    mr = SCRBModel.fit(xr, SCRBConfig(
        n_clusters=2, n_grids=grids, sigma=0.15, d_g=dg,
        solver_tol=1e-3, kmeans_replicates=2, seed=seed))
    mb = SCRBModel.fit(xb, SCRBConfig(
        n_clusters=4, n_grids=grids, sigma=1.5, d_g=dg,
        solver_tol=1e-3, kmeans_replicates=2, seed=seed + 1))
    return {"rings": (mr, xr), "blobs": (mb, xb)}


def make_mix(models, n_requests: int, seed: int = 0):
    """[(name, mode, rows)] with unique ragged sizes and model interleave."""
    rng = np.random.default_rng(seed)
    sizes = rng.choice(np.arange(17, 641), size=n_requests, replace=False)
    names = list(models)
    mix = []
    for i, size in enumerate(sizes):
        name = names[i % len(names)]
        mode = "predict" if rng.random() < 0.8 else "transform"
        _, pool = models[name]
        start = int(rng.integers(0, pool.shape[0]))
        idx = (start + np.arange(int(size))) % pool.shape[0]
        mix.append((name, mode, np.ascontiguousarray(pool[idx])))
    return mix


def run_per_request(models, mix):
    """One call per request through the raw model (batch_size=None — the
    legacy exact-shape path). Returns (timing dict, outputs list)."""
    outs = []
    t0 = time.perf_counter()
    for name, mode, rows in mix:
        mdl = models[name][0]
        fn = mdl.predict if mode == "predict" else mdl.transform
        outs.append(fn(rows))
    elapsed = time.perf_counter() - t0
    rows = sum(r.shape[0] for _, _, r in mix)
    return {"elapsed_s": elapsed, "rows": rows,
            "rows_per_s": rows / max(elapsed, 1e-9),
            "qps": len(mix) / max(elapsed, 1e-9)}, outs


def run_engine_once(eng, mix, waves: int):
    """Submit the mix in arrival waves (step after each), drain, collect
    per-ticket latencies and outputs in mix order."""
    wave = max(1, len(mix) // waves)
    tickets = []
    t0 = time.perf_counter()
    for i, (name, mode, rows) in enumerate(mix):
        tickets.append(eng.submit(name, rows, mode))
        if (i + 1) % wave == 0:
            eng.step()
    eng.drain()
    elapsed = time.perf_counter() - t0
    results = [eng.take(t) for t in tickets]
    lat = np.asarray([r.latency for r in results])
    rows = sum(r.shape[0] for _, _, r in mix)
    return {"elapsed_s": elapsed, "rows": rows,
            "rows_per_s": rows / max(elapsed, 1e-9),
            "qps": len(mix) / max(elapsed, 1e-9),
            "p50_ms": float(np.percentile(lat, 50) * 1e3),
            "p99_ms": float(np.percentile(lat, 99) * 1e3),
            "max_ms": float(lat.max() * 1e3)}, \
        [r.values for r in results], results


def run_lru_leg(models, mix):
    """Both models through a one-slot LRU: every model switch re-faults
    device state; results must stay correct and cells must not recompile."""
    eng = ClusterEngine(EngineConfig(buckets=BUCKETS, max_resident_models=1))
    for name, (mdl, _) in models.items():
        eng.load_model(name, mdl)
    ok = True
    for name, mode, rows in mix:
        mdl = models[name][0]
        got = eng.predict(name, rows) if mode == "predict" \
            else eng.transform(name, rows)
        want = mdl.predict(rows) if mode == "predict" else mdl.transform(rows)
        ok = ok and np.array_equal(got, want)
    s = eng.stats()
    compiles_after = eng.total_compiles
    # traffic replay: evictions keep happening, compiles must not
    for name, mode, rows in mix[:6]:
        if mode == "predict":
            eng.predict(name, rows)
        else:
            eng.transform(name, rows)
    return {"evictions": s["evictions"], "bit_identical": bool(ok),
            "cells": s["cells"], "compiles": s["total_compiles"],
            "recompiles_after_evictions":
                eng.total_compiles - compiles_after,
            "resident": s["resident"]}


def run(smoke: bool, n_requests: int, waves: int, seed: int = 0) -> dict:
    out = {"smoke": smoke, "n_requests": n_requests, "waves": waves,
           "buckets": list(BUCKETS), "seed": seed}
    models = build_models(smoke, seed)
    out["models"] = {
        name: {"dim": mdl.data_dim, "k": int(mdl.right_vectors.shape[1]),
               "nbytes": mdl.nbytes}
        for name, (mdl, _) in models.items()}
    mix = make_mix(models, n_requests, seed)
    out["mix_rows"] = int(sum(r.shape[0] for _, _, r in mix))

    # legs must run coldest-first: build_models never calls predict, so the
    # first per-request loop genuinely compiles every unique shape
    cold, expected = run_per_request(models, mix)
    out["per_request_cold"] = cold
    print(f"[serve] cold  per-request: {cold['rows_per_s']:9.0f} rows/s "
          f"({cold['qps']:.1f} req/s, {cold['elapsed_s']:.2f}s)")
    warm, _ = run_per_request(models, mix)
    out["per_request_warm"] = warm
    print(f"[serve] warm  per-request: {warm['rows_per_s']:9.0f} rows/s "
          f"({warm['qps']:.1f} req/s)")

    eng = ClusterEngine(EngineConfig(buckets=BUCKETS))
    for name, (mdl, _) in models.items():
        eng.load_model(name, mdl)
    t0 = time.perf_counter()
    for name in models:
        eng.warmup(name, modes=("predict", "transform"))
    out["engine_warmup_s"] = time.perf_counter() - t0
    out["engine_warmup_compiles"] = eng.total_compiles

    run1, outs1, res1 = run_engine_once(eng, mix, waves)
    compiles_run1 = eng.total_compiles
    alloc_run1 = eng.stats()["staging_allocations"]
    run2, outs2, res2 = run_engine_once(eng, mix, waves)
    stats = eng.stats()
    run1["recompiles"] = compiles_run1 - out["engine_warmup_compiles"]
    run2["recompiles"] = eng.total_compiles - compiles_run1
    run2["staging_alloc_delta"] = stats["staging_allocations"] - alloc_run1
    out["engine"] = {"run1": run1, "run2": run2, "cells": stats["cells"],
                     "total_compiles": stats["total_compiles"],
                     "padded_rows": stats["padded_rows"],
                     "batches": stats["batches"],
                     "staging_allocations": stats["staging_allocations"]}
    out["bit_identical"] = bool(all(
        np.array_equal(a, e) for a, e in zip(outs1, expected)) and all(
        np.array_equal(a, e) for a, e in zip(outs2, expected)))
    out["speedup_vs_cold"] = run2["rows_per_s"] / cold["rows_per_s"]
    out["speedup_vs_warm"] = run2["rows_per_s"] / warm["rows_per_s"]
    print(f"[serve] engine steady-state: {run2['rows_per_s']:9.0f} rows/s "
          f"({run2['qps']:.1f} req/s) — {out['speedup_vs_cold']:.1f}x cold, "
          f"{out['speedup_vs_warm']:.1f}x warm; p50 {run2['p50_ms']:.1f}ms "
          f"p99 {run2['p99_ms']:.1f}ms; {stats['cells']} cells, "
          f"{run2['recompiles']} steady recompiles, bit_identical="
          f"{out['bit_identical']}")

    # observability cross-check: the engine's own log-bucketed latency
    # histograms must agree with the external ticket-timestamp math above —
    # within one histogram bucket growth factor (10^0.25 ≈ 1.78 + sampling
    # slack), since the histogram stores buckets, not samples
    agreement = {}
    all_res = res1 + res2
    for name, mode in sorted({(r.model, r.mode) for r in all_res}):
        ext = np.asarray([r.latency for r in all_res
                          if r.model == name and r.mode == mode])
        hq = eng.latency_quantiles(name, mode, qs=(0.5, 0.99))
        agreement[f"{name}/{mode}"] = {
            "count": int(ext.size),
            "external_p50_ms": float(np.percentile(ext, 50) * 1e3),
            "hist_p50_ms": float(hq[0.5] * 1e3),
            "external_p99_ms": float(np.percentile(ext, 99) * 1e3),
            "hist_p99_ms": float(hq[0.99] * 1e3),
        }
    out["latency_hist_agreement"] = agreement

    out["lru"] = run_lru_leg(models, mix[:12])
    print(f"[serve] lru leg (1 slot): {out['lru']['evictions']} evictions, "
          f"{out['lru']['recompiles_after_evictions']} recompiles after "
          f"evictions, correct={out['lru']['bit_identical']}")
    return out


def gate(out: dict) -> list[str]:
    """CI conditions (bench-smoke serve leg). Every number here is the
    tentpole's reason to exist — regressions fail the PR."""
    failures = []
    eng, run2 = out["engine"], out["engine"]["run2"]
    if out["speedup_vs_cold"] < 3.0:
        failures.append(
            f"engine rows/s is only {out['speedup_vs_cold']:.2f}x the "
            f"per-request cold baseline (< 3x) — bucketed compile reuse "
            f"is not paying for itself")
    if out["speedup_vs_warm"] < 1.0:
        failures.append(
            f"engine rows/s {run2['rows_per_s']:.0f} fell below the warm "
            f"per-request baseline "
            f"{out['per_request_warm']['rows_per_s']:.0f} — coalescing + "
            f"padding overhead exceeds the dispatch savings")
    if run2["p99_ms"] > 5.0 * run2["p50_ms"]:
        failures.append(
            f"p99 {run2['p99_ms']:.1f}ms > 5x p50 {run2['p50_ms']:.1f}ms — "
            f"tail latency regressed (stray compile or queueing collapse)")
    if eng["total_compiles"] != eng["cells"]:
        failures.append(
            f"{eng['total_compiles']} compiles for {eng['cells']} cells — "
            f"some (model, bucket, mode) cell compiled more than once")
    if run2["recompiles"] != 0:
        failures.append(
            f"{run2['recompiles']} recompiles in the steady-state run — "
            f"warmup no longer covers the serving bucket grid")
    if run2["staging_alloc_delta"] != 0:
        failures.append(
            f"{run2['staging_alloc_delta']} staging buffers allocated in "
            f"the steady-state run — the H2D ring stopped recycling")
    if not out["bit_identical"]:
        failures.append(
            "engine outputs differ from direct model.predict/transform — "
            "bucket padding is contaminating real rows")
    for series, chk in out.get("latency_hist_agreement", {}).items():
        for q, bound in (("p50", 1.9), ("p99", 2.5)):
            ext, hist = chk[f"external_{q}_ms"], chk[f"hist_{q}_ms"]
            if not (ext / bound <= hist <= ext * bound):
                failures.append(
                    f"{series}: engine histogram {q} {hist:.3f}ms disagrees "
                    f"with external ticket math {ext:.3f}ms (outside {bound}x "
                    f"— log-bucket quantile estimation broke)")
    lru = out["lru"]
    if lru["evictions"] == 0:
        failures.append("LRU leg saw zero evictions with 1 resident slot "
                        "and 2 models — eviction accounting is broken")
    if not lru["bit_identical"]:
        failures.append("LRU leg outputs wrong after eviction/re-fault")
    if lru["recompiles_after_evictions"] != 0:
        failures.append(
            f"{lru['recompiles_after_evictions']} recompiles after "
            f"evictions — compiled cells no longer survive eviction")
    return failures


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small fits + short mix (the CI bench-smoke leg)")
    ap.add_argument("--requests", type=int, default=48)
    ap.add_argument("--waves", type=int, default=6)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="bench_results/BENCH_PR8.json")
    ap.add_argument("--gate", action="store_true",
                    help="exit non-zero on any serving regression")
    args = ap.parse_args()
    from repro.utils import use_compile_cache
    use_compile_cache()
    res = run(args.smoke, args.requests, args.waves, args.seed)
    failures = gate(res)
    res["gate_failures"] = failures
    if os.path.dirname(args.out):
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(res, f, indent=1)
    if args.gate:
        if failures:
            for msg in failures:
                print(f"[serve][GATE FAIL] {msg}", file=sys.stderr)
            sys.exit(1)
        print("[serve] gate passed: throughput, tail latency, compile "
              "accounting, bit-identity, and LRU all within bounds")


if __name__ == "__main__":
    main()
