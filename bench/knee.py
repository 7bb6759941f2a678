#!/usr/bin/env python3
"""Sweep the offered rate of a serve cell to find its knee, in one process.

    python3 bench/knee.py --workload poker.serve --rates 60,90,120 --seconds 30

Sets the cell up once, then offers each rate for ``--seconds`` with the
cell's traffic. Per rate: the share of the offered rows completed inside
the window, the backlog (rows due but not done) at half and at the end of
the window, and the latency quantiles. The knee is the highest rate that
completes at least 99% of the offered rows in the window with no growing
backlog. Not part of a benchmark run; its result is the cell's fixed rate.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=2**31 + 77)
    args = ap.parse_args()
    sys.path.insert(0, HERE)
    sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))
    import harness
    cell = harness.find_cell(args.workload)
    import jax
    harness.device_info(jax, cell.chips)
    harness.use_compile_cache(jax)
    out_dir = os.path.join(harness.OUT_DIR, args.workload)
    os.makedirs(out_dir, exist_ok=True)
    ctx = harness.Context(cell, args.seed, jax, out_dir)
    kind = cell.kind
    kind.setup(ctx)
    pool_rows = ctx.state["pool"].shape[0]
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        sched = kind.schedule(args.seed + i, rate, args.seconds, cell.mix,
                              pool_rows)
        out = kind._loop(ctx, sched, annotate=False)
        due, sizes = sched["due"], sched["sizes"]
        done_at = due + out["latency"]
        t_end, t_half = args.seconds, args.seconds / 2

        def backlog(t):
            return int(np.sum(sizes[due <= t]) - np.sum(sizes[done_at <= t]))

        ms = out["latency"] * 1e3
        print(json.dumps({
            "rate_rps": rate, "requests": int(due.size),
            "offered_rows_per_s": float(np.sum(sizes) / args.seconds),
            "done_in_window": float(np.sum(sizes[done_at <= t_end])
                                    / np.sum(sizes)),
            "backlog_half": backlog(t_half), "backlog_end": backlog(t_end),
            "p50_ms": float(np.percentile(ms, 50)),
            "p99_ms": float(np.percentile(ms, 99)),
            "step_ms": float(1e3 * out["steps"].mean()),
            "late_p99_ms": float(np.percentile(out["late"] * 1e3, 99)),
            "late_max_ms": float(np.max(out["late"]) * 1e3),
            "step_max_ms": float(1e3 * out["steps"].max()),
            "gc_pauses": len(out["gc"]),
            "gc_max_ms": float(1e3 * max((p[1] for p in out["gc"]),
                                         default=0.0))}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
