"""The correctness check must fail what it exists to catch, at a size a
test run holds (CPU):

- the control: the plain reference put in the program's place and computed
  with bfloat16 operands (for the fit: every feature-matrix product and the
  solver's dense algebra, its binning exact, so that only the eigenpairs
  can fail it; for the served embedding: the binning's inputs too), against
  a witness: the same reference in float32, which passes;
- each fault a cell can have, planted under the timed path of a tiny run
  driven through the harness: a solver step that returns its state
  unchanged, half of a batch left out, an answer altered where it is
  produced. (The exchange between chips does not exist in one-chip cells.)
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_tiny  # noqa: E402
import checks  # noqa: E402
import data  # noqa: E402
import reference  # noqa: E402

CFG = bench_tiny.CONFIG


def _fit(x, seed, operand_dtype=None):
    return reference.fit(
        x, k=CFG["k"], n_grids=CFG["n_grids"], sigma=data.suggest_sigma(x),
        d_g=CFG["d_g"], seed=seed, tol=CFG["solver_tol"],
        iters=CFG["solver_iters"], operand_dtype=operand_dtype)


@pytest.fixture(scope="module")
def rows():
    return data.make_blobs(1200, CFG["d"], CFG["k"], seed=2**31 + 11)


@pytest.mark.parametrize("seed", [3, 2**31 + 5])
def test_fit_check_passes_the_float32_reference(rows, seed):
    x, y = rows
    nums = checks.fit_numbers(x, y, _fit(x, seed), n_grids=CFG["n_grids"])
    judged = checks.judge(nums, bench_tiny.FIT_LIMITS)
    assert all(v["ok"] for v in judged.values()), judged


@pytest.mark.parametrize("seed", [3, 2**31 + 5])
def test_fit_check_fails_the_bfloat16_control(rows, seed):
    x, y = rows
    nums = checks.fit_numbers(x, y, _fit(x, seed, jnp.bfloat16),
                              n_grids=CFG["n_grids"])
    judged = checks.judge(nums, bench_tiny.FIT_LIMITS)
    assert judged["dual_err"]["ok"], judged          # binning stays exact
    assert not judged["eig_residual"]["ok"], judged


def _near_ties(model, x, n):
    """Rows between two fitted clusters, where labels are most sensitive."""
    emb = np.asarray(reference.embed_new(x, model))
    d2 = np.asarray(reference.sq_dists(jnp.asarray(emb),
                                       jnp.asarray(model["centroids"])))
    lab = d2.argmin(1)
    rng = np.random.default_rng(0)
    a = rng.integers(0, x.shape[0], 4 * n)
    b = rng.integers(0, x.shape[0], 4 * n)
    keep = lab[a] != lab[b]
    t = rng.uniform(0.3, 0.7, keep.sum())[:, None]
    return (x[a[keep]] * t + x[b[keep]] * (1 - t))[:n].astype(np.float32)


def test_serve_check_fails_the_bfloat16_control(rows):
    x, _ = rows
    model = _fit(x, 7)
    new = _near_ties(model, x, 2000)
    cents = jnp.asarray(model["centroids"])
    exact = np.asarray(reference.sq_dists(reference.embed_new(new, model),
                                          cents)).argmin(1)
    assert checks.served_gap(model, new, exact) <= \
        bench_tiny.SERVE_LIMITS["label_gap"]
    low = np.asarray(reference.sq_dists(
        reference.embed_new(new, model, operand_dtype=jnp.bfloat16),
        cents)).argmin(1)
    assert checks.served_gap(model, new, low) > \
        bench_tiny.SERVE_LIMITS["label_gap"]


# -- faults planted under the timed path --------------------------------------

@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return bench_tiny.make(str(tmp_path_factory.mktemp("faultbench")))


def _unchanged_solver(matvec, x0, **_):
    """A LOBPCG that returns its (orthonormalized) start block unchanged."""
    from repro.core import eigensolver
    x = jnp.linalg.qr(x0.astype(jnp.float32))[0]
    ax = matvec(x)
    theta = jnp.sum(x * ax, axis=0)
    res = jnp.linalg.norm(ax - x * theta, axis=0) / theta
    order = jnp.argsort(-theta)
    return eigensolver.EigResult(theta[order], x[:, order], res[order],
                                 jnp.int32(0))


def _half_rows_counts(real):
    """Bin counts (and degrees) from the first half of the rows, doubled."""
    def counts(idx, *, d, d_g, impl="auto"):
        half = idx.shape[0] // 2
        deg, cnt = real(idx[:half], d=d, d_g=d_g, impl=impl)
        deg = jnp.concatenate([deg, deg, deg[:idx.shape[0] - 2 * half]])
        return deg, 2.0 * cnt
    return counts


def _altered_index(real):
    """RB binning that moves one row's bin in the first grid."""
    def rb_binning(*args, d_g, **kw):
        idx = real(*args, d_g=d_g, **kw)
        return idx.at[0, 0].set((idx[0, 0] + 1) % d_g)
    return rb_binning


@pytest.mark.parametrize("fault", ["unchanged_step", "half_batch",
                                   "altered_answer"])
def test_fit_faults_come_out_not_correct(bench, monkeypatch, fault):
    from repro.core import eigensolver, graph
    from repro.kernels import ops
    if fault == "unchanged_step":
        monkeypatch.setattr(eigensolver, "lobpcg", _unchanged_solver)
    elif fault == "half_batch":
        monkeypatch.setattr(graph, "rb_degrees_and_counts",
                            _half_rows_counts(graph.rb_degrees_and_counts))
    else:
        monkeypatch.setattr(ops, "rb_binning", _altered_index(ops.rb_binning))
    out = bench_tiny.run(bench, "tiny.fit", seed=2**31 + 21)
    assert out["correct"] is False, (fault, out["checks"])


def _faulty_predict(real, fault):
    def predict(fm, dual, proj, cents, x, *, laplacian, impl):
        if fault == "unchanged_step":
            x = jnp.zeros_like(x)
        elif fault == "half_batch":
            half = x.shape[0] // 2
            x = x.at[half:].set(0.0)
        labels = real(fm, dual, proj, cents, x, laplacian=laplacian,
                      impl=impl)
        if fault == "altered_answer":
            labels = labels.at[0].set((labels[0] + 1) % cents.shape[0])
        return labels
    return predict


@pytest.mark.parametrize("fault", ["unchanged_step", "half_batch",
                                   "altered_answer"])
def test_serve_faults_come_out_not_correct(bench, monkeypatch, fault):
    from repro.core import model
    monkeypatch.setattr(model, "_oos_predict_impl",
                        _faulty_predict(model._oos_predict_impl, fault))
    jax.clear_caches()
    out = bench_tiny.run(bench, "tiny.serve", seed=2**31 + 23, seconds=1.0)
    assert out["correct"] is False, (fault, out["checks"])
