"""Share of its roofline that the Gram mat-vec reaches at the dispatcher
(``ops.gram_matmul``, ``impl="auto"``), at the cell's N, R, d_g and LOBPCG
block width b = K + 4, on the traced fit's own binned rows. Device time of
the module ``jit_bench_gram_matvec`` per call; least work from
``work.gram_matvec``."""
from __future__ import annotations

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_metrics_probe", os.path.join(os.path.dirname(__file__), "_probe.py"))
_probe = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_probe)

NAME = "bench_gram_matvec"


def probe(ctx) -> None:
    import jax
    import jax.numpy as jnp

    import reference as ref
    import work
    from repro.kernels import ops

    fit = ctx.state.get("traced_fit")
    if fit is None:
        return
    d_g, r = fit["d_g"], fit["grids"]["widths"].shape[0]
    n_features = r * d_g
    idx = ref.bins(ctx.state["x"], fit["grids"], d_g)
    scale = ref.row_scale(ref.degrees(idx, ref.bin_counts(
        idx, n_features=n_features)), r)
    b = ctx.config["k"] + 4
    u = jax.random.normal(jax.random.PRNGKey(0), (idx.shape[0], b),
                          jnp.float32)

    def bench_gram_matvec(idx, u, scale):
        return ops.gram_matmul(idx, u, scale, n_features, d_g=d_g)

    _probe.run(ctx, NAME, jax.jit(bench_gram_matvec), (idx, u, scale),
               work.gram_matvec(idx.shape[0], r, b, d_g))


def read(ctx):
    return _probe.read(ctx, NAME)
