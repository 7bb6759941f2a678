#!/usr/bin/env python3
"""Readings that the check's limits are set from, on the chip, in one
process: the compared numbers of sound runs of the program over many seeds
(the lower reading is their largest) and of the control over a few (the
upper reading is its smallest).

    python3 bench/calibrate.py --workload poker.fit --seeds 12 --control 3

Fit cells: each seed draws one fit job as the cell's set does (the
cell's rows and a config seed); the program fits it and the check compares
the fit. The control is the plain reference with its binning exact and
bfloat16 operands in every feature-matrix product and the solver's dense
algebra at the default precision; ``--witness`` adds a float32 reference
fit of the first seed, which the check has to pass.

Serve cells: each seed sets the cell up as a run does and serves
``--serve-seconds`` of its traffic; the program's labels and the control's
(the reference's out-of-sample labels with bfloat16 operands, binning
included, on the same rows) are compared with the reference.

Not part of a benchmark run. Prints one JSON line per reading.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))


def _emit(**kw) -> None:
    print(json.dumps(kw), flush=True)


def fit_cell(ctx, kind, seeds, control_seeds, witness) -> None:
    import jax.numpy as jnp

    import checks
    import reference
    cfg = ctx.config
    for i, seed in enumerate(seeds):
        job = kind.jobs(cfg, ctx.spec["rows"], seed, 1)[0]
        x, y = job["x"], job["y"]
        t = time.perf_counter()
        answer = kind._answer(kind._fit(ctx, job, job["seed"]), 0, 0.0)
        fit_s = time.perf_counter() - t
        nums = checks.fit_numbers(x, y, answer, n_grids=cfg["n_grids"])
        _emit(who="program", seed=seed, fit_s=fit_s,
              iterations=answer["iterations"], **nums)
        runs = [("control", jnp.bfloat16)] if i < control_seeds else []
        if i == 0 and witness:
            runs.append(("witness", None))
        for who, dtype in runs:
            t = time.perf_counter()
            ans = reference.fit(
                x, k=cfg["k"], n_grids=cfg["n_grids"],
                sigma=job["sigma"], d_g=cfg["d_g"],
                seed=job["seed"], tol=cfg["solver_tol"],
                iters=cfg["solver_iters"], kmeans_iters=cfg["kmeans_iters"],
                kmeans_replicates=cfg["kmeans_replicates"],
                operand_dtype=dtype)
            ref_s = time.perf_counter() - t
            nums = checks.fit_numbers(x, y, ans, n_grids=cfg["n_grids"])
            _emit(who=who, seed=seed, fit_s=ref_s, **nums)


def serve_cell(ctx, kind, seeds, control_seeds, seconds) -> None:
    import jax.numpy as jnp
    import numpy as np

    import checks
    import reference
    for i, seed in enumerate(seeds):
        ctx.seed = seed
        ctx.state.clear()
        kind.setup(ctx)
        kind.window(ctx, seconds)
        sched, out = ctx.state["sched"], ctx.state["served"]
        pool = ctx.state["pool"]
        rows = np.concatenate([pool[o:o + n] for o, n in
                               zip(sched["offsets"], sched["sizes"])])
        labels = np.concatenate(out["labels"])
        model = ctx.state["model"]
        ctx.state.pop("engine")
        gap = checks.served_gap(model, rows, labels)
        _emit(who="program", seed=seed, rows=int(rows.shape[0]),
              label_gap=gap)
        if i < control_seeds:
            low = []
            for lo in range(0, rows.shape[0], 1 << 16):
                emb = reference.embed_new(rows[lo:lo + (1 << 16)], model,
                                          operand_dtype=jnp.bfloat16)
                low.append(np.asarray(reference.sq_dists(
                    emb, jnp.asarray(model["centroids"]))).argmin(1))
            gap = checks.served_gap(model, rows, np.concatenate(low))
            _emit(who="control", seed=seed, rows=int(rows.shape[0]),
                  label_gap=gap)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2**31 + 1000)
    ap.add_argument("--serve-seconds", type=float, default=10.0)
    ap.add_argument("--witness", action="store_true",
                    help="fit cells: also fit the first seed with the "
                         "float32 reference")
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
    ctx = harness.Context(cell, args.first_seed, jax, out_dir)
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    if cell.mix["kind"] == "fit_repeat":
        fit_cell(ctx, cell.kind, seeds, args.control, args.witness)
    else:
        serve_cell(ctx, cell.kind, seeds, args.control, args.serve_seconds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
