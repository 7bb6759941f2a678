"""The least work of a layer entry, from shapes: the operations and HBM
bytes that any implementation of it must spend, and the roofline share of a
measured time against the chip's peaks (``peaks.json``, keyed by
``device_kind``).

Counts are the least, so no implementation can read over 100%:

- Gram mat-vec y = Ẑ Ẑᵀ u (u: N×b). Z's entries are structural (one per row
  and grid; the 1/√R and D^{-1/2} factors are a per-row scale), so Ẑᵀ(s∘u)
  is N·b multiplies and N·R·b adds, and Ẑ q is N·R·b adds and N·b
  multiplies: 2·N·R·b + 2·N·b operations. Bytes: the ELL read once, each
  index at the fewest whole bytes that hold [0, d_g), plus u in, y out and
  the (N,) row scale at 4 bytes each. The (D, b) intermediate is not
  counted: a fused implementation need not write it.
- RB binning of x (N×d) into R grids: per (row, grid, dimension) a
  subtraction, a division and the hash's multiply and add; per (row, grid)
  the mix multiply, the shift and the grid offset: N·R·(4·d + 3). Bytes: x
  read once at 4 bytes, the indices written at the least index width.
"""
from __future__ import annotations

import json
import os

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def index_bytes(d_g: int) -> int:
    """Fewest whole bytes that hold an index in [0, d_g)."""
    bits = max(1, (int(d_g) - 1).bit_length())
    return -(-bits // 8)


def gram_matvec(n: int, r: int, b: int, d_g: int) -> dict:
    return {"ops": 2 * n * r * b + 2 * n * b,
            "bytes": n * r * index_bytes(d_g) + 4 * (2 * n * b + n)}


def rb_binning(n: int, r: int, d: int, d_g: int) -> dict:
    return {"ops": n * r * (4 * d + 3),
            "bytes": 4 * n * d + n * r * index_bytes(d_g)}


def peaks(device_kind: str) -> dict:
    with open(PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json ({sorted(table)})")
    return table[device_kind]


def roofline(work: dict, seconds: float, device_kind: str) -> dict:
    """Least time over measured time, in %, and which bound binds."""
    p = peaks(device_kind)
    t_ops = work["ops"] / p["flops_per_s"]
    t_bytes = work["bytes"] / p["hbm_bytes_per_s"]
    return {"pct": 100.0 * max(t_ops, t_bytes) / seconds,
            "bound": "bytes" if t_bytes >= t_ops else "ops",
            "least_s": max(t_ops, t_bytes)}
