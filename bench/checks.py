"""The comparisons that decide ``correct``: what the timed path produced
against the plain reference (``reference.py``), as numbers with limits.

A fit is compared through the fitted model it returns and its labels:

- ``dual_err``: columns (of D) whose bin count, rounded, differs from the
  reference's count of the training rows under the model's grids (binned
  features and degrees; an exact comparison);
- ``eig_residual``: max over the K pairs of ‖Ẑᵀ Ẑ v_k − θ_k v_k‖ / θ_k,
  with Ẑ the reference's, v_k the model's right singular vectors (scaled
  to unit norm) and θ_k = σ_k² its singular values squared: the solver's
  relative residual, taken on the pairs the model keeps and serves with
  (Gram mat-vec and eigensolver);
- ``sigma_err``: max over the K pairs of |v_kᵀ Ẑᵀ Ẑ v_k / ‖v_k‖² − θ_k| / θ_k,
  the model's singular values against the reference's Rayleigh quotients of
  its own vectors: second order in a vector's error, first order in an
  error of the operator the solver applied;
- ``label_gap``: the widest gap by which a row's label lies further from
  its centroid than the row's nearest centroid, on the embedding rebuilt
  from the model (embedding and k-means labels);
- ``ari_loss``: 1 − ARI of the labels against the generator's (the
  clustering quality the deployment states).

Served labels are compared through ``label_gap`` on the reference's
out-of-sample embedding, and ``missing`` counts requests never answered.
"""
from __future__ import annotations

import sys

import jax.numpy as jnp
import numpy as np

import reference as ref


def say(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def fit_numbers(x, y, answer: dict, *, n_grids: int,
                block_rows: int = 1 << 16) -> dict:
    """The four fit numbers of one fitted ``answer`` on rows ``x``."""
    d_g = int(answer["d_g"])
    if answer["grids"]["widths"].shape[0] != n_grids:
        raise ValueError(f"model has {answer['grids']['widths'].shape[0]} "
                         f"grids, the configuration {n_grids}")
    idx = ref.bins(x, answer["grids"], d_g)
    counts = ref.bin_counts(idx, n_features=n_grids * d_g)
    dual_err = float(np.sum(np.rint(np.asarray(answer["dual"], np.float64))
                            != np.asarray(counts, np.float64)))
    scale = ref.row_scale(ref.degrees(idx, counts), n_grids)
    sig = jnp.asarray(answer["singular_values"], jnp.float32)
    theta = sig ** 2
    v = jnp.asarray(answer["right_vectors"])
    zv = ref.z_apply(idx, scale, v)
    norm = jnp.linalg.norm(v, axis=0)
    gv = ref.zt_apply(idx, scale, zv, d_g=d_g)
    resid = jnp.linalg.norm(gv - v * theta[None, :], axis=0) / (theta * norm)
    rayleigh = jnp.sum(v * gv, axis=0) / norm ** 2
    sigma_err = jnp.max(jnp.abs(rayleigh - theta) / theta)
    emb = ref.row_normalize(zv / sig[None, :])
    cents = jnp.asarray(answer["centroids"])
    labels = np.asarray(answer["labels"])
    gap = 0.0
    for lo in range(0, emb.shape[0], block_rows):
        d2 = ref.sq_dists(emb[lo:lo + block_rows], cents)
        gap = max(gap, ref.label_gap(d2, labels[lo:lo + block_rows]))
    return {"dual_err": dual_err,
            "eig_residual": float(jnp.max(resid)),
            "sigma_err": float(sigma_err),
            "label_gap": gap,
            "ari_loss": 1.0 - ref.adjusted_rand_index(labels, y)}


def served_gap(model: dict, rows: np.ndarray, labels: np.ndarray, *,
               block_rows: int = 1 << 16) -> float:
    """``label_gap`` of served ``labels`` for ``rows`` under ``model``."""
    gap = 0.0
    cents = jnp.asarray(model["centroids"])
    for lo in range(0, rows.shape[0], block_rows):
        emb = ref.embed_new(rows[lo:lo + block_rows], model)
        gap = max(gap, ref.label_gap(ref.sq_dists(emb, cents),
                                     labels[lo:lo + block_rows]))
    return gap


def judge(numbers: dict, limits: dict) -> dict:
    """Each number beside its limit; a number over its limit is a failure."""
    missing = set(limits) - set(numbers)
    if missing:
        raise ValueError(f"no number for limits {sorted(missing)}")
    return {k: {"value": numbers[k], "limit": limits[k],
                "ok": bool(numbers[k] <= limits[k])} for k in limits}
