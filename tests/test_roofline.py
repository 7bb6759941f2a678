"""The clustering roofline: collective bytes read from HLO text
(``benchmarks/clustering_cell.py``) and the verdicts ``benchmarks/roofline.py``
derives from a dry-run record."""
import importlib.util
import os

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(name):
    spec = importlib.util.spec_from_file_location(
        f"_bench_{name}", os.path.join(_ROOT, "benchmarks", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_parse_collectives_counts_result_bytes(monkeypatch):
    # the module defaults XLA_FLAGS to 512 host devices for its dry run;
    # keep that out of this process's environment
    monkeypatch.setenv("XLA_FLAGS", os.environ.get("XLA_FLAGS", ""))
    cell = _load("clustering_cell")
    hlo = "\n".join([
        "%p = f32[64,4]{1,0} parameter(0)",
        "%ar = f32[64,4]{1,0} all-reduce(f32[64,4]{1,0} %p), to_apply=%add",
        "%ag.1 = bf16[8,16]{1,0} all-gather(bf16[2,16]{1,0} %q)",
        "ROOT %ars = (s32[3], u8[5]) all-reduce-start(s32[3] %r, u8[5] %s)",
        "%f = f32[64,4]{1,0} fusion(f32[64,4]{1,0} %ar), kind=kLoop",
    ])
    got = cell.parse_collectives(hlo)
    assert got["all-reduce"] == {"count": 2, "bytes": 64 * 4 * 4 + 3 * 4 + 5}
    assert got["all-gather"] == {"count": 1, "bytes": 8 * 16 * 2}
    assert all(got[k] == {"count": 0, "bytes": 0} for k in
               ("reduce-scatter", "all-to-all", "collective-permute"))


@pytest.mark.parametrize("compress", [False, True])
def test_roofline_derives_clustering_record(compress):
    roofline = _load("roofline")
    n, r, d_g, k, chips = 1 << 20, 256, 4096, 16, 256
    rec = {
        "arch": "sc-rb-clustering", "shape": "eigeniter", "mesh": "pod16x16",
        "n_devices": chips, "status": "ok", "kind": "clustering",
        "memory": {"peak_bytes_per_device": 2**30},
        "cost": {"flops": 4.0 * n * r * k / chips, "bytes_accessed": 1e9},
        "collectives": {"all-reduce": {"count": 1, "bytes": r * d_g * k * 4}},
        "clustering": {"n": n, "r": r, "d_g": d_g, "k": k},
        "coll_analytic_bytes": r * d_g * k * (2 if compress else 4),
    }
    out = roofline.derive(rec)
    assert out["model_flops"] == 4 * n * r * k
    assert out["useful_flop_ratio"] == pytest.approx(1.0)
    assert out["t_collective_s"] == pytest.approx(
        rec["coll_analytic_bytes"] / roofline.ICI_BW)
    assert out["dominant"] in ("compute", "memory", "collective")
    bound = max(out["t_compute_s"], out["t_memory_s"], out["t_collective_s"])
    assert out["roofline_fraction"] == pytest.approx(
        out["t_memory_s"] / bound)
    assert "| eigeniter | pod16x16 |" in roofline.table([out])
    failed = roofline.derive({**rec, "status": "error"})
    assert failed["dominant"] == "n/a"
    assert "| eigeniter | pod16x16 | — |" in roofline.table([failed])
