"""Benchmark entry point: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (one per benchmark artifact) and
writes detailed JSON under bench_results/. Scales are CPU-sized by default;
pass --scale to grow toward the paper's dataset sizes.
"""
from __future__ import annotations

import argparse
import json
import os
import time


def _row(name: str, seconds: float, derived) -> str:
    return f"{name},{seconds * 1e6:.0f},{derived}"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.01)
    ap.add_argument("--quick", action="store_true",
                    help="smallest scales (CI smoke)")
    args = ap.parse_args()
    from repro.utils import use_compile_cache
    use_compile_cache()
    scale = 0.004 if args.quick else args.scale
    os.makedirs("bench_results", exist_ok=True)
    rows = ["name,us_per_call,derived"]

    from benchmarks import (fig2_vary_r, fig3_solvers, fig4_scaling_n,
                            fig5_scaling_r, fig6_streaming, table2_accuracy)

    t0 = time.time()
    t2 = table2_accuracy.run(scale=scale, rank=128 if args.quick else 256)
    dt = time.time() - t0
    n_ds = len(t2)
    mean_rank = sum(d["avg_rank"].get("sc_rb", 9) for d in t2.values()) / n_ds
    wins = sum(1 for d in t2.values()
               if min(d["avg_rank"], key=d["avg_rank"].get) == "sc_rb")
    rows.append(_row("table2_avg_rank_sc_rb", dt / n_ds,
                     f"mean_rank={mean_rank:.2f};wins={wins}/{n_ds}"))
    mean_time = sum(d["time_s"].get("sc_rb", 0) for d in t2.values()) / n_ds
    rows.append(_row("table3_runtime_sc_rb", mean_time, "seconds_per_dataset"))
    with open("bench_results/table2.json", "w") as f:
        json.dump(t2, f, indent=1)

    t0 = time.time()
    f2 = fig2_vary_r.run(scale=scale, rs=(16, 64, 256))
    dt = time.time() - t0
    acc_rb = f2["methods"]["sc_rb"]["acc"][-1]
    acc_rf = f2["methods"]["sc_rf"]["acc"][-1]
    rows.append(_row("fig2_convergence_R", dt,
                     f"acc_rb@256={acc_rb:.3f};acc_rf@256={acc_rf:.3f}"))
    with open("bench_results/fig2.json", "w") as f:
        json.dump(f2, f, indent=1)

    t0 = time.time()
    f3 = fig3_solvers.run(scale=scale / 2, rs=(16, 64))
    dt = time.time() - t0
    lob = sum(f3["solvers"]["lobpcg"]["svd_time_s"])
    lan = sum(f3["solvers"]["lanczos"]["svd_time_s"])
    rows.append(_row("fig3_solver_speedup", dt,
                     f"lanczos/lobpcg_svd_time={lan / max(lob, 1e-9):.2f}x"))
    with open("bench_results/fig3.json", "w") as f:
        json.dump(f3, f, indent=1)

    t0 = time.time()
    f4 = fig4_scaling_n.run(ns=(1_000, 2_000, 4_000, 8_000)
                            if args.quick else (1_000, 2_000, 4_000, 8_000, 16_000))
    dt = time.time() - t0
    rows.append(_row("fig4_scaling_N", dt,
                     f"loglog_slope={f4['loglog_slope']:.2f}"))
    with open("bench_results/fig4.json", "w") as f:
        json.dump(f4, f, indent=1)

    t0 = time.time()
    f5 = fig5_scaling_r.run(scale=scale, rs=(16, 64, 128))
    dt = time.time() - t0
    rb_t = f5["datasets"]["pendigits"]["times"]["sc_rb"]
    slope_r = (rb_t[-1] / max(rb_t[0], 1e-9))
    rows.append(_row("fig5_scaling_R", dt,
                     f"time_ratio_128_vs_16={slope_r:.2f}x"))
    with open("bench_results/fig5.json", "w") as f:
        json.dump(f5, f, indent=1)

    t0 = time.time()
    f6 = fig6_streaming.run(
        ns=(1_000, 2_000, 4_000) if args.quick else (1_000, 2_000, 4_000, 8_000),
        chunk_size=512, rank=64 if args.quick else 128,
        prefetch_sweep=not args.quick)
    dt = time.time() - t0
    shrink = ((f6["ell_bytes_single_shot"][-1]
               + f6["embedding_bytes_single_shot"][-1])
              / (f6["ell_bytes_streaming"][-1]
                 + f6["embedding_bytes_streaming"][-1]))
    rows.append(_row("fig6_streaming_N", dt,
                     f"e2e_peak_shrink={shrink:.1f}x;"
                     f"agree={f6['label_agreement_at_n0']:.3f}"))
    with open("bench_results/fig6.json", "w") as f:
        json.dump(f6, f, indent=1)

    # roofline summary (if dry-run artifacts exist)
    try:
        from benchmarks import roofline
        rl = [roofline.derive(r) for r in roofline.load("dryrun_results")]
        ok = [r for r in rl if r.get("status") == "ok"]
        if ok:
            worst = min(ok, key=lambda r: r["roofline_fraction"])
            rows.append(_row(
                "roofline_cells", 0.0,
                f"ok={len(ok)};worst={worst['arch']}×{worst['shape']}"
                f"@{worst['roofline_fraction']:.2f}"))
            with open("bench_results/roofline.json", "w") as f:
                json.dump(rl, f, indent=1)
    except Exception as e:  # dry-run not yet executed
        rows.append(_row("roofline_cells", 0.0, f"unavailable:{e}"))

    print("\n".join(rows))


if __name__ == "__main__":
    main()
