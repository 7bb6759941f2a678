#!/usr/bin/env python3
"""The bfloat16 control of a ``serve_zipf`` cell's check, member by member,
on the chip, in one process.

    python3 bench/calibrate_zoo.py --workload zoo.serve_zipf --seconds 10

Sets the cell up as a run does, serves ``--seconds`` of its traffic and
judges the program's labels with the cell's own check (``serve_zipf.check``
→ ``checks.judge``). Then, one member at a time, it puts the control in
that member's place: the plain reference's out-of-sample labels with
bfloat16 operands (binning included) for the rows of every answered request
of that member, the other members keeping the program's labels. Each
control has to come out not correct; a member whose control passes has a
check that cannot tell its served labels from bfloat16 ones.

Not part of a benchmark run. Prints one JSON line per judgement: ``who``
(program or control), the member put in place, ``correct``, that member's
``label_gap`` and every member's.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def _emit(**kw) -> None:
    print(json.dumps(kw), flush=True)


def control_labels(model: dict, rows: np.ndarray,
                   block_rows: int = 1 << 16) -> np.ndarray:
    """Nearest centroid of the reference's bfloat16 embedding of ``rows``."""
    import jax.numpy as jnp

    import reference
    cents = jnp.asarray(model["centroids"])
    return np.concatenate([np.asarray(reference.sq_dists(
        reference.embed_new(rows[lo:lo + block_rows], model,
                            operand_dtype=jnp.bfloat16), cents)).argmin(1)
        for lo in range(0, rows.shape[0], block_rows)])


def judge_controls(ctx, kind) -> list:
    """The program's judgement, then one per member with the control in its
    place; ``ctx`` holds a served window (``kind.window``)."""
    sched, out = ctx.state["sched"], ctx.state["served"]
    program = out["labels"]
    names = ctx.state["names"]

    def line(who, member):
        judged = kind.check(ctx)
        gaps = ctx.state["label_gaps"]
        return {"who": who, "member": member,
                "correct": all(c["ok"] for c in judged.values()),
                "label_gap": gaps.get(member, max(gaps.values())),
                "label_gaps": gaps}

    lines = [line("program", None)]
    try:
        for m, name in enumerate(names):
            mine = [j for j in np.flatnonzero(sched["member"] == m)
                    if program[j] is not None]
            rows = np.concatenate([ctx.state["pools"][m][
                sched["offsets"][j]:sched["offsets"][j] + sched["sizes"][j]]
                for j in mine])
            low = np.split(control_labels(ctx.state["models"][m], rows),
                           np.cumsum(sched["sizes"][mine])[:-1])
            labels = list(program)
            for j, lab in zip(mine, low):
                labels[j] = lab
            out["labels"] = labels
            lines.append(line("control", name))
    finally:
        out["labels"] = program
    return lines


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="zoo.serve_zipf")
    ap.add_argument("--seed", type=int, default=2**31 + 2024)
    ap.add_argument("--seconds", type=float, default=10.0)
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
    cell.kind.setup(ctx)
    cell.kind.window(ctx, args.seconds)
    ctx.state.pop("engine")
    for judged in judge_controls(ctx, cell.kind):
        _emit(seed=args.seed, **judged)
    return 0


if __name__ == "__main__":
    sys.exit(main())
