"""Paper Table 2 + Table 3: accuracy (4 metrics → average rank) and runtime
for all 9 methods on the 8 paper-shaped datasets."""
from __future__ import annotations

import argparse
import json
from typing import Dict, List

import jax.numpy as jnp

from benchmarks.datasets import suite
from repro.core import metrics as M
from repro.core.baselines import METHOD_FEATURE_MAPS, METHODS, BaselineConfig
from repro.core.featuremap import FEATURE_MAPS

# exact SC is O(N²·d) memory/compute — cap like the paper caps with '—'
SC_EXACT_MAX_N = 4_000


def check_registry_coverage() -> None:
    """Every Table-2 method must be present and, where feature-map-backed,
    point at a registered map — a rewrite of baselines.py can never silently
    drop one of the paper's comparison methods."""
    missing = set(METHOD_FEATURE_MAPS) ^ set(METHODS)
    if missing:
        raise AssertionError(
            f"METHODS / METHOD_FEATURE_MAPS disagree on {sorted(missing)}")
    if len(METHODS) != 10:
        raise AssertionError(
            f"expected the paper's 9 methods (8 baselines + sc_rb) plus "
            f"the compressive variant csc_rb, got {sorted(METHODS)}")
    unbacked = {name: fm for name, fm in METHOD_FEATURE_MAPS.items()
                if fm is not None and fm not in FEATURE_MAPS}
    if unbacked:
        raise AssertionError(
            f"methods reference unregistered feature maps: {unbacked}")


def run(scale: float = 0.02, rank: int = 256, seed: int = 0,
        methods: List[str] | None = None) -> Dict:
    check_registry_coverage()
    methods = methods or list(METHODS)
    results: Dict[str, Dict] = {}
    for spec, x, y, sigma in suite(scale=scale, seed=seed):
        xj = jnp.asarray(x)
        per_method: Dict[str, Dict[str, float]] = {}
        times: Dict[str, float] = {}
        for name in methods:
            if name == "sc" and x.shape[0] > SC_EXACT_MAX_N:
                continue   # '—' in the paper's tables
            cfg = BaselineConfig(
                n_clusters=spec.k, rank=rank, sigma=sigma,
                kmeans_replicates=4, seed=seed)
            out = METHODS[name](xj, cfg)
            per_method[name] = M.all_metrics(out.labels, y)
            times[name] = out.timer.total
        ranks = M.average_rank_scores(per_method)
        results[spec.name] = {
            "n": x.shape[0], "k": spec.k, "d": spec.d,
            "metrics": per_method, "avg_rank": ranks, "time_s": times,
            # provenance: the registry map each method ran through
            "feature_maps": {m: METHOD_FEATURE_MAPS[m] for m in per_method},
        }
        best = min(ranks, key=ranks.get)
        print(f"[table2] {spec.name:14s} N={x.shape[0]:7d} "
              f"best={best:7s} sc_rb_rank={ranks.get('sc_rb', -1):.2f} "
              f"sc_rb_time={times.get('sc_rb', -1):.1f}s")
    return results


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.02)
    ap.add_argument("--rank", type=int, default=256)
    ap.add_argument("--out", default="bench_results/table2.json")
    args = ap.parse_args()
    from repro.utils import use_compile_cache
    use_compile_cache()
    res = run(scale=args.scale, rank=args.rank)
    import os
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(res, f, indent=1)


if __name__ == "__main__":
    main()
