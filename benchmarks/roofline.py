"""Roofline of the distributed SC_RB eigensolver iteration.

Reads the dry-run records that ``benchmarks/clustering_cell.py`` writes
(``dryrun_results/sc-rb-clustering__*.json``, ``kind = "clustering"``): one
Gram mat-vec ``q = Ẑᵀu`` (psum over the row axes) then ``y = Ẑq``, lowered
and compiled for the production meshes. For each record:

  compute    = flops_per_chip / 197e12        [s]  (bf16 MXU peak, v5e)
  memory     = bytes_per_chip / 819e9         [s]  (HBM bandwidth)
  collective = coll_bytes_per_chip / 50e9     [s]  (ICI per-link)

Two estimates are reported. The ``*_ub_s`` terms come from the compiled
program: ``compiled.cost_analysis()`` on the SPMD-partitioned module is the
per-device program, and collective bytes are the summed result sizes of the
collectives in its HLO text. They are upper bounds, since the CPU backend
widens bf16 buffers to f32. The analytic terms (``analytic_terms``) count
the iteration from its shapes and decide the verdict: 4·N·R·K useful flops,
the idx stream plus the gathered rows, and a D·K psum.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
from typing import Dict, List

PEAK_FLOPS = 197e12        # bf16 / chip (TPU v5e)
HBM_BW = 819e9             # B/s / chip
ICI_BW = 50e9              # B/s / link


def analytic_terms(rec: Dict) -> Dict[str, float]:
    """Per-chip flops, HBM bytes and collective bytes of one iteration."""
    c = rec["clustering"]
    chips = rec["n_devices"]
    flops = 4 * c["n"] * c["r"] * c["k"] / chips
    mem = (c["n"] * c["r"] * (4 + 4 * c["k"]) / chips     # idx + gather
           + 2 * c["r"] * c["d_g"] * c["k"] * 4)          # q RW
    coll = rec.get("coll_analytic_bytes", c["r"] * c["d_g"] * c["k"] * 4)
    return {"flops": flops, "bytes": mem, "coll": coll}


def load(results_dir: str) -> List[Dict]:
    """The clustering dry-run records under ``results_dir``."""
    rows = []
    for path in sorted(glob.glob(os.path.join(results_dir, "*.json"))):
        with open(path) as f:
            rec = json.load(f)
        if rec.get("kind") == "clustering":
            rows.append(rec)
    return rows


def derive(rec: Dict) -> Dict:
    if rec.get("status") != "ok":
        return {**rec, "dominant": "n/a"}
    chips = rec["n_devices"]
    flops_chip = rec["cost"]["flops"]
    bytes_chip = rec["cost"]["bytes_accessed"]
    coll_chip = sum(v["bytes"] for v in rec["collectives"].values())
    est = analytic_terms(rec)
    te_compute = est["flops"] / PEAK_FLOPS
    te_memory = est["bytes"] / HBM_BW
    te_coll = est["coll"] / ICI_BW
    dominant = max(
        [("compute", te_compute), ("memory", te_memory),
         ("collective", te_coll)],
        key=lambda kv: kv[1])[0]
    c = rec["clustering"]   # one Gram iteration: Ẑᵀu + Ẑq, 2 flops/MAC
    model_flops = 4 * c["n"] * c["r"] * c["k"]
    hlo_flops_global = flops_chip * chips
    ratio = model_flops / hlo_flops_global if hlo_flops_global > 0 else 0.0
    bound_time = max(te_compute, te_memory, te_coll)
    # intrinsically streaming-bound (2 flops per 4 idx bytes): fraction =
    # how close the binding term is to pure HBM streaming of Z
    fraction = te_memory / bound_time if bound_time else 0.0
    notes = {
        "compute": "compute-bound: raise the useful-flop fraction",
        "memory": "memory-bound: stream fewer bytes of Z per mat-vec",
        "collective": "collective-bound: compress or overlap the psum",
    }
    return {
        **rec,
        # measured (HLO-derived, CPU-backend upper bounds)
        "t_compute_ub_s": flops_chip / PEAK_FLOPS,
        "t_memory_ub_s": bytes_chip / HBM_BW,
        "t_collective_ub_s": coll_chip / ICI_BW,
        # analytic best estimate, which drives the verdicts
        "t_compute_s": te_compute,
        "t_memory_s": te_memory,
        "t_collective_s": te_coll,
        "dominant": dominant,
        "model_flops": model_flops,
        "useful_flop_ratio": ratio,
        "roofline_fraction": fraction,
        "note": notes[dominant],
    }


def table(rows: List[Dict]) -> str:
    hdr = ("| cell | mesh | compute s | memory s | collective s | "
           "dominant | model/HLO flops | roofline frac | peak GiB/chip |\n"
           "|---|---|---|---|---|---|---|---|---|\n")
    lines = []
    for r in rows:
        if r.get("status") != "ok":
            lines.append(f"| {r['shape']} | {r['mesh']} | — | — | — | "
                         f"{r.get('status')} | — | — | — |")
            continue
        mem_gib = r["memory"]["peak_bytes_per_device"] / 2**30
        lines.append(
            f"| {r['shape']} | {r['mesh']} "
            f"| {r['t_compute_s']:.3e} | {r['t_memory_s']:.3e} "
            f"| {r['t_collective_s']:.3e} | **{r['dominant']}** "
            f"| {r['useful_flop_ratio']:.2f} | {r['roofline_fraction']:.2f} "
            f"| {mem_gib:.1f} |")
    return hdr + "\n".join(lines)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--results-dir", default="dryrun_results")
    ap.add_argument("--out", default="bench_results/roofline.json")
    args = ap.parse_args()
    rows = [derive(r) for r in load(args.results_dir)]
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(rows, f, indent=1)
    print(table(rows))


if __name__ == "__main__":
    main()
