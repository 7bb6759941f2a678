"""Share of its roofline that RB binning reaches at the dispatcher
(``ops.rb_binning``, ``impl="auto"``), on the cell's rows with the traced
fit's grids. Device time of the module ``jit_bench_rb_binning`` per call;
least work from ``work.rb_binning``."""
from __future__ import annotations

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_metrics_probe", os.path.join(os.path.dirname(__file__), "_probe.py"))
_probe = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_probe)

NAME = "bench_rb_binning"


def probe(ctx) -> None:
    import jax
    import jax.numpy as jnp

    import work
    from repro.kernels import ops

    fit = ctx.state.get("traced_fit")
    if fit is None:
        return
    g = {k: jnp.asarray(v) for k, v in fit["grids"].items()}
    d_g = fit["d_g"]
    x = jnp.asarray(ctx.state["x"])

    def bench_rb_binning(x, widths, biases, hash_a, hash_c):
        return ops.rb_binning(x, widths, biases, hash_a, hash_c, d_g=d_g)

    n, d = x.shape
    _probe.run(ctx, NAME, jax.jit(bench_rb_binning),
               (x, g["widths"], g["biases"], g["hash_a"], g["hash_c"]),
               work.rb_binning(n, g["widths"].shape[0], d, d_g))


def read(ctx):
    return _probe.read(ctx, NAME)
