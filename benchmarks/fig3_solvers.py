"""Paper Fig. 3, extended into a solver bake-off on the covtype-shaped
dataset (clustered spectrum): the full ``SOLVERS`` registry — LOBPCG
(PRIMME-analogue, degree-preconditioned), its host-driven twin, Lanczos
('svds'), subspace iteration, the randomized block-Krylov one-pass sketch,
the eigendecomposition-free compressive cell (Chebyshev-filtered random
signals, no (N, K) iterate) — plus the ``auto`` meta-policy, measured on
accuracy + svd runtime + iteration count while varying R.

The bake-off emits a per-R ``recommendation``: the fastest solver whose
accuracy lands within ``acc_margin`` of the best at that R. This is the
measurement behind the ``solver="auto"`` heuristic (randomized sketch
first, warm-started preconditioned LOBPCG continuation only when the sketch
misses tolerance) — rerun it when the operator regime changes to check the
policy still matches the data.
"""
from __future__ import annotations

import argparse
import json

import jax.numpy as jnp

from benchmarks.datasets import one
from repro.core import SCRBConfig, metrics as M, sc_rb

BAKEOFF_SOLVERS = ["lobpcg", "lobpcg_host", "lanczos", "subspace",
                   "randomized", "compressive", "auto"]


def recommend(per_solver: dict, rs, acc_margin: float = 0.01) -> list[str]:
    """Fastest solver within ``acc_margin`` of the best accuracy, per R."""
    recs = []
    for i, _ in enumerate(rs):
        best_acc = max(s["acc"][i] for s in per_solver.values())
        ok = {name: s["svd_time_s"][i] for name, s in per_solver.items()
              if s["acc"][i] >= best_acc - acc_margin}
        recs.append(min(ok, key=ok.get))
    return recs


def run(scale: float = 0.01, seed: int = 0, rs=(16, 32, 64, 128),
        solvers=tuple(BAKEOFF_SOLVERS)):
    spec, x, y, sigma = one("covtype-mult", scale=scale, seed=seed)
    xj = jnp.asarray(x)
    out = {"n": x.shape[0], "rs": list(rs), "solvers": {}}
    for solver in solvers:
        accs, times, iters, resns = [], [], [], []
        for r in rs:
            cfg = SCRBConfig(
                n_clusters=spec.k, n_grids=r, sigma=sigma, solver=solver,
                solver_iters=200, kmeans_replicates=4, seed=seed)
            res = sc_rb(xj, cfg)
            accs.append(M.accuracy(res.labels, y))
            times.append(res.timer.times.get("svd", 0.0))
            iters.append(res.diagnostics["solver_iterations"])
            resns.append(float(res.diagnostics["solver_resnorms"].max()))
        out["solvers"][solver] = {"acc": accs, "svd_time_s": times,
                                  "iterations": iters,
                                  "max_resnorm": resns}
        print(f"[fig3] {solver:10s} acc={['%.3f' % a for a in accs]} "
              f"svd_s={['%.2f' % t for t in times]} iters={iters}")
    out["recommendation"] = recommend(out["solvers"], rs)
    print(f"[fig3] per-R recommendation: {out['recommendation']}")
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.01)
    ap.add_argument("--out", default="bench_results/fig3.json")
    args = ap.parse_args()
    from repro.utils import use_compile_cache
    use_compile_cache()
    res = run(scale=args.scale)
    import os
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(res, f, indent=1)


if __name__ == "__main__":
    main()
