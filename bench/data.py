"""Seeded inputs of the benchmark: the configurations' data and the σ choice.

Copied from the program's generator (``repro.data.synthetic.make_blobs``)
and its bandwidth heuristic (``repro.core.rb.suggest_sigma``) so that a
change to the program cannot move the yardstick: the same seed always gives
the same rows here, whatever the program does with them.
"""
from __future__ import annotations

import numpy as np


def sub_seed(seed: int, *tags: int) -> int:
    """A 31-bit seed derived from ``seed`` (any size) and integer tags, for
    the parts of a run that take a seed of their own (each fit's config)."""
    state = np.random.SeedSequence([int(seed) & (2**64 - 1), *tags])
    return int(state.generate_state(1)[0] & 0x7FFFFFFF)


def make_blobs(n: int, d: int, k: int, *, seed: int, spread: float = 0.25,
               center_norm: float = 2.0) -> tuple[np.ndarray, np.ndarray]:
    """Gaussian mixture with K centers of norm ``center_norm`` on the
    sphere: (x float32 (n, d), y int32 (n,)). The generator's rows and labels for ``n`` rows
    are a prefix of those for more rows only through the same RNG stream,
    so callers draw every split they need in one call."""
    rng = np.random.default_rng(int(seed))
    centers = rng.normal(size=(k, d))
    centers /= np.linalg.norm(centers, axis=1, keepdims=True)
    centers *= center_norm
    y = rng.integers(0, k, size=n)
    x = centers[y] + spread * rng.normal(size=(n, d))
    return x.astype(np.float32), y.astype(np.int32)


def suggest_sigma(x: np.ndarray, *, n_sample: int = 512,
                  scale: float = 0.5) -> float:
    """Median heuristic for the Laplacian kernel: σ = scale · median L1
    distance over a fixed subsample of the rows."""
    xs = np.asarray(x)
    if xs.shape[0] > n_sample:
        sel = np.random.default_rng(0).choice(xs.shape[0], n_sample,
                                              replace=False)
        xs = xs[sel]
    d1 = np.abs(xs[:, None, :] - xs[None, :, :]).sum(-1)
    iu = np.triu_indices(xs.shape[0], k=1)
    return float(np.median(d1[iu]) * scale)


def dataset(config: dict, n: int, seed: int):
    """Rows and true labels of ``config``'s generator, ``n`` rows."""
    gen = config["generator"]
    if gen != "blobs":
        raise ValueError(f"unknown generator {gen!r}")
    return make_blobs(n, config["d"], config["k"], seed=seed,
                      spread=config.get("spread", 0.25),
                      center_norm=config.get("center_norm", 2.0))
