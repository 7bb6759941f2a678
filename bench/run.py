#!/usr/bin/env python3
"""Run one benchmark cell once on the chip this process is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Sets up the cell (its data and a warm-up at every shape it will use), then
measures for ``--seconds`` (``--trace 0``: the cell's end-to-end metrics) or
runs one traced slice under ``jax.profiler`` (``--trace 1``: its per-layer
metrics), checks what the timed path produced against the plain reference
(``bench/reference.py``), and prints one JSON object as the last line of
standard output. Exits non-zero, printing no result, when JAX finds no TPU or
fewer chips than the cell needs. See ``bench/harness.py`` for the files a
cell is made of.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, HERE)
    sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))
    import harness
    try:
        harness.run(args.workload, args.seed, args.seconds, bool(args.trace),
                    t_start=T_START)
    except harness.BenchError as e:
        harness.say(f"bench: {e}")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
