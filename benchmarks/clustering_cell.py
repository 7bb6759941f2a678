import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

"""Roofline cell for the paper's own workload: one distributed SC_RB
eigensolver iteration (q = Ẑᵀu psum; y = Ẑq) at production scale —
N = 100M embedding rows, R = 256 grids, d_g = 4096 (D ≈ 1M), K = 16
Ritz vectors — on both production meshes, fp32 vs bf16-compressed psum.

Writes dryrun_results/sc-rb-clustering__eigeniter[...].json records
(kind = "clustering") that benchmarks/roofline.py reads.
"""
import argparse
import json
import re
import time

COLLECTIVE_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                  "collective-permute")
_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(text: str) -> int:
    """Sum byte sizes of every shape literal in an HLO result type string."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo: str) -> dict:
    """Per-op-kind byte totals from the (post-SPMD, per-device) HLO text."""
    out = {k: {"count": 0, "bytes": 0} for k in COLLECTIVE_OPS}
    for line in hlo.splitlines():
        stripped = line.strip()
        m = re.match(r"^(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(.+?)\s+([\w\-]+)\(", stripped)
        if not m:
            continue
        result_type, opname = m.groups()
        for kind in COLLECTIVE_OPS:
            if opname == kind or opname.startswith(kind + "-"):
                # result type covers output bytes (per device)
                out[kind]["count"] += 1
                out[kind]["bytes"] += _shape_bytes(result_type)
                break
    return out


def run(multi_pod: bool, compress: bool, out_dir: str,
        n: int = 25_000_000, n_grids: int = 256, d_g: int = 4096,
        k: int = 16) -> dict:
    # n=25M keeps the CPU-backend compile artifact-free (XLA CPU unrolls the
    # r-chunk loop, transiently materializing all gathers); per-chip ratios
    # are representative and every term scales linearly in N.
    from repro.core.distributed import lower_clustering_cell
    from repro.launch.mesh import make_production_mesh

    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_tag = "pod2x16x16" if multi_pod else "pod16x16"
    variant = "eigeniter_bf16" if compress else "eigeniter"
    t0 = time.time()
    lowered = lower_clustering_cell(
        mesh, n=n, dim=0, k=k, n_grids=n_grids, d_g=d_g, compress=compress)
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    chips = 512 if multi_pod else 256
    rec = {
        "arch": "sc-rb-clustering",
        "shape": variant,
        "mesh": mesh_tag,
        "n_devices": chips,
        "status": "ok",
        "kind": "clustering",
        "compile_s": round(time.time() - t0, 2),
        "memory": {
            "argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "peak_bytes_per_device": int(mem.argument_size_in_bytes
                                         + mem.temp_size_in_bytes),
        },
        "cost": {
            "flops": float(cost.get("flops", -1.0)),
            "bytes_accessed": float(cost.get("bytes accessed", -1.0)),
        },
        "collectives": parse_collectives(compiled.as_text()),
        "clustering": {"n": n, "r": n_grids, "d_g": d_g, "k": k},
        # CPU backend widens bf16 collectives to f32 in HLO; the true TPU
        # psum payload is D·K·itemsize:
        "coll_analytic_bytes": n_grids * d_g * k * (2 if compress else 4),
    }
    path = os.path.join(out_dir,
                        f"sc-rb-clustering__{variant}__{mesh_tag}.json")
    os.makedirs(out_dir, exist_ok=True)
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    coll = sum(v["bytes"] for v in rec["collectives"].values())
    print(f"[clustering {variant} × {mesh_tag}] compile {rec['compile_s']}s "
          f"flops/chip {rec['cost']['flops']:.3e} "
          f"coll/chip {coll/2**20:.1f} MiB "
          f"peak {rec['memory']['peak_bytes_per_device']/2**30:.2f} GiB")
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out-dir", default="dryrun_results")
    args = ap.parse_args()
    for multi_pod in (False, True):
        for compress in (False, True):
            run(multi_pod, compress, args.out_dir)


if __name__ == "__main__":
    main()
