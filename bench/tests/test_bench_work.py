"""work.py: the least work counts, the peaks table, the roofline share."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import work  # noqa: E402

# (N, R, b, d_g, d) of the cells and a small shape
SHAPES = [(262_144, 256, 14, 512, 10), (70_000, 256, 14, 2048, 780),
          (1_000, 32, 5, 256, 3)]


def test_gram_matvec_of_poker_fit():
    w = work.gram_matvec(262_144, 256, 14, 512)
    # ELL at 2 bytes (d_g 512 needs 9 bits) + u in, y out, row scale
    assert w["bytes"] == 164_626_432
    # adds over the stored entries in both products, row-scale multiplies
    assert w["ops"] == 2 * 262_144 * 256 * 14 + 2 * 262_144 * 14


def test_index_bytes():
    assert [work.index_bytes(d) for d in (2, 256, 257, 512, 65_536)] == \
        [1, 1, 2, 2, 2]
    assert work.index_bytes(1 << 17) == 3


def test_rb_binning_counts():
    w = work.rb_binning(262_144, 256, 10, 512)
    assert w["bytes"] == 4 * 262_144 * 10 + 2 * 262_144 * 256
    assert w["ops"] == 262_144 * 256 * 43


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        work.peaks("TPU v99 imaginary")
    with pytest.raises(KeyError):
        work.roofline({"ops": 1, "bytes": 1}, 1.0, "cpu")


def test_roofline_share_and_bound():
    p = work.peaks("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    w = work.gram_matvec(262_144, 256, 14, 512)
    least = w["bytes"] / 819e9
    r = work.roofline(w, 2 * least, "TPU v5 lite")
    assert r["bound"] == "bytes"
    assert r["pct"] == pytest.approx(50.0)
    assert work.roofline({"ops": 197e12, "bytes": 1}, 2.0,
                         "TPU v5 lite")["bound"] == "ops"


def _pallas_fused_gram(n, r, b, d_g):
    """What ``ell_spmm.gram_matmul_pallas`` moves and does: two phases each
    read the int32 ELL strip and the row scale, phase 0 reads u (b padded to
    8 rows), phase 1 writes y; each phase contracts a (dc, block_n) one-hot
    against the factor for every (grid, bin) of the strip."""
    bp = -(-b // 8) * 8
    return {"bytes": 2 * 4 * n * r + 2 * 4 * n + 4 * n * bp + 4 * n * bp,
            "ops": 2 * (2 * n * r * d_g * bp)}


def _pallas_zt_z_pair(n, r, b, d_g):
    """``zt_matmul_pallas`` then ``z_matmul_pallas``: each reads the ELL
    and the row scale; the (D, b) factor goes out to HBM and back."""
    bp = -(-b // 8) * 8
    return {"bytes": 2 * 4 * n * r + 2 * 4 * n + 2 * 4 * n * bp
            + 2 * 4 * r * d_g * bp,
            "ops": 2 * (2 * n * r * d_g * bp)}


def _xla_gram(n, r, b, d_g):
    """``ops._zt_matmul_xla`` then ``ops._z_matmul_xla``: u scaled once,
    one segment-sum add and one gather add per stored entry and column,
    the ELL read by both, the (D, b) factor written and read."""
    return {"bytes": 2 * 4 * n * r + 4 * n * b * 2 + 4 * n
            + 2 * 4 * r * d_g * b,
            "ops": 2 * n * r * b + 2 * n * b}


def _pallas_binning(n, r, d, d_g):
    """``rb_binning_pallas``: x read at 4 bytes, int32 indices written; per
    (row, grid, dimension) at least a column extraction, subtract, divide,
    floor, convert, multiply and add."""
    return {"bytes": 4 * n * d + 4 * n * r, "ops": n * r * (7 * d + 4)}


@pytest.mark.parametrize("n,r,b,d_g,d", SHAPES)
def test_least_work_is_no_more_than_todays_kernels(n, r, b, d_g, d):
    least = work.gram_matvec(n, r, b, d_g)
    for route in (_pallas_fused_gram, _pallas_zt_z_pair, _xla_gram):
        done = route(n, r, b, d_g)
        assert least["bytes"] <= done["bytes"], route.__name__
        assert least["ops"] <= done["ops"], route.__name__
    least = work.rb_binning(n, r, d, d_g)
    done = _pallas_binning(n, r, d, d_g)
    assert least["bytes"] <= done["bytes"] and least["ops"] <= done["ops"]
