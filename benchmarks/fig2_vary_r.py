"""Paper Fig. 2: accuracy + runtime vs R on the mnist-shaped dataset for the
random-feature methods (SC_RB vs SC_RF vs SV_RF vs KK_RF) — the empirical
Thm-2 check: SC_RB converges in R faster than RF-based SC."""
from __future__ import annotations

import argparse
import json

import jax.numpy as jnp

from benchmarks.datasets import one
from repro.core import metrics as M
from repro.core.baselines import METHODS, BaselineConfig


def run(scale: float = 0.02, seed: int = 0, rs=(16, 32, 64, 128, 256, 512)):
    spec, x, y, sigma = one("mnist", scale=scale, seed=seed)
    xj = jnp.asarray(x)
    out = {"n": x.shape[0], "rs": list(rs), "methods": {}}
    for name in ["sc_rb", "sc_rf", "sv_rf", "kk_rf"]:
        accs, times = [], []
        for r in rs:
            cfg = BaselineConfig(n_clusters=spec.k, rank=r, sigma=sigma,
                                 kmeans_replicates=4, seed=seed)
            res = METHODS[name](xj, cfg)
            accs.append(M.accuracy(res.labels, y))
            times.append(res.timer.total)
        out["methods"][name] = {"acc": accs, "time_s": times}
        print(f"[fig2] {name:6s} acc={['%.3f' % a for a in accs]}")
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.02)
    ap.add_argument("--out", default="bench_results/fig2.json")
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
