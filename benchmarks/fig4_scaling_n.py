"""Paper Fig. 4: linear scalability of SC_RB in the number of samples N.

Per-stage runtime (RB generation / degrees / eigensolver / k-means) on the
poker-shaped dataset across a geometric N sweep + a least-squares slope in
log-log space (slope ≈ 1 ⇒ linear; the paper contrasts against quadratic SC).

``--solver`` selects the eigensolver (default ``auto``: the randomized
block-Krylov sketch with a warm-started preconditioned LOBPCG continuation
only when the sketch misses tolerance — the bake-off winner from fig3);
``--solver lobpcg`` reproduces the pre-bake-off configuration, and
``--solver compressive`` runs the eigendecomposition-free cell whose svd
stage is a fixed Chebyshev mat-vec budget independent of N (the ``auto``
policy itself routes there above ``compressive_auto_n`` samples); each sweep
point hands its (λ_K, λ_{K+1}) estimate to the next via
``compressive_lambdas``, so every point after the first skips the
eigencount sweep and pays the filter alone. The sweep
records per-N solver iteration counts alongside the stage times so the svd
stage's cost decomposes into iterations × per-iteration mat-vec cost.
"""
from __future__ import annotations

import argparse
import json

import jax.numpy as jnp
import numpy as np

from benchmarks.datasets import one
from repro.core import SCRBConfig, sc_rb


def run(ns=(1_000, 2_000, 4_000, 8_000, 16_000), rank: int = 256,
        seed: int = 0, solver: str = "auto"):
    out = {"ns": list(ns), "stages": {}, "total_s": [], "solver": solver,
           "solver_iterations": [], "solver_max_resnorm": []}
    stages = ["rb_features", "degrees", "svd", "kmeans"]
    for st in stages:
        out["stages"][st] = []

    lambdas = None   # compressive λ warm start, carried along the sweep

    def make_cfg(k, sigma):
        return SCRBConfig(n_clusters=k, n_grids=rank, sigma=sigma,
                          solver=solver, kmeans_replicates=4, seed=seed,
                          compressive_lambdas=lambdas)

    # jit warm-up at the smallest N so the sweep measures compute, not traces
    spec0, x0, _, sig0 = one("poker", scale=ns[0] / 1_025_010, seed=seed)
    sc_rb(jnp.asarray(x0[: ns[0]]), make_cfg(spec0.k, sig0))
    for n in ns:
        spec, x, y, sigma = one("poker", scale=n / 1_025_010, seed=seed)
        x = x[:n]
        res = sc_rb(jnp.asarray(x), make_cfg(spec.k, sigma))
        if "compressive" in res.diagnostics:
            # the spectrum of Â is N-stable on a fixed distribution, so each
            # point hands its (λ_K, λ_{K+1}) bracket to the next — after the
            # first point the svd stage is the filter's fixed budget alone
            cd = res.diagnostics["compressive"]
            lambdas = (cd["lambda_k"], cd["lambda_k1"])
        for st in stages:
            out["stages"][st].append(res.timer.times.get(st, 0.0))
        out["total_s"].append(res.timer.total)
        out["solver_iterations"].append(res.diagnostics["solver_iterations"])
        out["solver_max_resnorm"].append(
            float(res.diagnostics["solver_resnorms"].max()))
        print(f"[fig4] N={n:7d} total={res.timer.total:6.2f}s "
              f"svd_iters={out['solver_iterations'][-1]} {res.timer}")
    # log-log slope of total runtime vs N (jit caching makes later runs
    # cheaper, so fit from the 2nd point)
    ln_n = np.log(np.asarray(out["ns"][1:], float))
    ln_t = np.log(np.maximum(np.asarray(out["total_s"][1:], float), 1e-9))
    slope = (float(np.polyfit(ln_n, ln_t, 1)[0]) if len(ns) > 2
             else float("nan"))
    out["loglog_slope"] = slope
    print(f"[fig4] log-log slope = {slope:.2f} (1.0 = linear, 2.0 = quadratic)")
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--max-n", type=int, default=16_000)
    ap.add_argument("--solver", default="auto")
    ap.add_argument("--out", default="bench_results/fig4.json")
    args = ap.parse_args()
    from repro.utils import use_compile_cache
    use_compile_cache()
    ns = [n for n in (1_000, 2_000, 4_000, 8_000, 16_000, 32_000, 64_000,
                      128_000, 256_000)
          if n <= args.max_n]
    res = run(ns=tuple(ns), solver=args.solver)
    import os
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(res, f, indent=1)


if __name__ == "__main__":
    main()
