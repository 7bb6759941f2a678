"""Paper Fig. 5: runtime scalability in the number of latent features R for
the approximation methods across 4 datasets (linear-in-R check).

The ``sc_rb`` sweep is warm-started: each R point's eigensolve begins from
the previous point's converged subspace (``ExecutionPlan.eig_x0``) instead
of a fresh random block — the operators at neighboring R share their
leading invariant subspace, so the solver only pays for the spectral drift
between R points. The per-point solver iteration counts ride along in the
output so the warm-start win is visible next to the runtimes.
"""
from __future__ import annotations

import argparse
import dataclasses
import json

import jax.numpy as jnp

from benchmarks.datasets import one
from repro.core import executor
from repro.core.baselines import METHODS, BaselineConfig, _scrb_config

DATASETS = ["pendigits", "letter", "ijcnn1", "covtype-mult"]
FIG5_METHODS = ["sc_rb", "sc_rf", "sv_rf", "kk_rf", "kk_rs", "sc_nys", "sc_lsc"]


def _sc_rb_sweep(xj, spec, sigma, rs, seed, kmeans_replicates=2):
    """The warm-started sc_rb R-sweep: eig of point i seeds point i+1."""
    times, iters = [], []
    warm = None
    for r in rs:
        cfg = BaselineConfig(n_clusters=spec.k, rank=r, sigma=sigma,
                             kmeans_replicates=kmeans_replicates, seed=seed)
        scfg = _scrb_config(cfg)
        plan = executor.plan_from_config(scfg)
        if warm is not None:
            plan = dataclasses.replace(plan, eig_x0=warm)
        res = executor.execute(xj, scfg, plan, keep_state=True)
        warm = res.state["eig"]
        res.state = None          # keep only the (N, k) subspace alive
        times.append(res.timer.total)
        iters.append(res.diagnostics["solver_iterations"])
    return times, iters


def run(scale: float = 0.02, seed: int = 0, rs=(16, 32, 64, 128, 256)):
    out = {"rs": list(rs), "datasets": {}}
    for ds in DATASETS:
        spec, x, y, sigma = one(ds, scale=scale, seed=seed)
        xj = jnp.asarray(x)
        per = {}
        sc_rb_iters = None
        for name in FIG5_METHODS:
            if name == "sc_rb":
                times, sc_rb_iters = _sc_rb_sweep(xj, spec, sigma, rs, seed)
            else:
                times = []
                for r in rs:
                    cfg = BaselineConfig(n_clusters=spec.k, rank=r,
                                         sigma=sigma, kmeans_replicates=2,
                                         seed=seed)
                    res = METHODS[name](xj, cfg)
                    times.append(res.timer.total)
            per[name] = times
        out["datasets"][ds] = {"n": x.shape[0], "times": per,
                               "sc_rb_solver_iters": sc_rb_iters}
        print(f"[fig5] {ds:14s} sc_rb={['%.2f' % t for t in per['sc_rb']]} "
              f"warm iters={sc_rb_iters}")
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.02)
    ap.add_argument("--out", default="bench_results/fig5.json")
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
