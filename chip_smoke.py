#!/usr/bin/env python3
"""Chip smoke test: SC_RB's fit → save → load → serve path on a TPU.

Drives the program's main path once, through the entry points a user calls,
on one TPU chip at paper Table 1 "poker" size (N = 1,025,010, d = 10,
K = 10; synthetic content generated from ``--seed``) with R = 256 grids,
σ from ``rb.suggest_sigma`` and the d_g that ``rb.suggest_d_g`` picks:

  kernels  every Pallas kernel at the fit's widths against ``impl="xla"``,
           and proof that each lowered to a Mosaic ``tpu_custom_call``
  fit      ``SCRBModel.fit`` under the default plan (single device,
           device residency, default solver, ``impl="auto"``); ARI against
           the generator's labels ≥ 0.95
  serve    ``save`` → ``SCRBModel.load`` → ``ClusterEngine`` (load_model,
           warmup, submit/drain/take) on 8 ragged requests of fresh rows;
           engine labels equal ``model.predict`` on the same rows, and
           ``predict`` on the training rows agrees with the fit labels ≥ 0.99

``--chips 4`` runs only the mesh fit (``placement="mesh"`` over
``launch.mesh.make_host_mesh()``) and, as its comparison, the single-chip
fit of the same data on device 0; their labels must agree at ARI ≥ 0.99.

Every phase runs in its own process, one after another, and this parent
never imports JAX, so one process at a time holds the chip. A phase that
finds no TPU, or fails a check, exits non-zero; the script then stops with
that code and prints no result. The last line of a passing run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.

Usage:  python chip_smoke.py [--seed S] [--chips 4] [--n N]
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(ROOT, ".chip_smoke")     # git-ignored phase hand-off

POKER_N = 1_025_010          # paper Table 1: poker N, d = 10, K = 10
N_GRIDS = 256                # R
FRESH_ROWS = 16_384          # held-out rows of the same mixture for serving
KERNEL_ROWS = 100_003        # rows per kernel check (ragged on purpose)
REQUEST_ROWS = (1, 4096, 17, 1000, 256, 3001, 64, 2049)
GRAM_RTOL = 1e-5             # max|pallas − xla| / max|xla| for ELL products


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(*parts, file=None) -> None:
    print(*parts, file=file or sys.stdout, flush=True)


# --------------------------------------------------------------------------
# child side: everything below runs in a phase process that owns the chip
# --------------------------------------------------------------------------

def _device_info(jax, want: int) -> dict:
    devs = jax.devices()
    check(devs[0].platform == "tpu",
          f"no TPU: JAX's first device is {devs[0].platform!r}")
    check(len(devs) >= want, f"need {want} chips, JAX sees {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _poker(seed: int, n: int):
    """Poker-shaped blobs: the training rows, their labels, and FRESH_ROWS
    held-out rows drawn from the same mixture in the same call."""
    from repro.data.synthetic import PAPER_TABLE1, make_blobs
    spec = next(s for s in PAPER_TABLE1 if s.name == "poker")
    x, y = make_blobs(n + FRESH_ROWS, spec.d, spec.k, seed=seed)
    return spec, x[:n], y[:n], x[n:]


def _config(spec, x, seed):
    from repro.core import SCRBConfig, rb
    sigma = rb.suggest_sigma(x)
    return SCRBConfig(n_clusters=spec.k, n_grids=N_GRIDS, sigma=sigma,
                      seed=seed)


def _lowers_to_mosaic(jax, fn, *args) -> bool:
    return "tpu_custom_call" in jax.jit(fn).lower(*args).as_text()


def _peak_bytes(dev) -> int:
    return int((dev.memory_stats() or {}).get("peak_bytes_in_use", -1))


def phase_kernels(args, jax) -> dict:
    import functools

    import jax.numpy as jnp
    import numpy as np

    from repro.core import eigensolver, graph, rb
    from repro.core.kmeans import row_normalize
    from repro.kernels import ops
    from repro.utils import fold_key

    spec, x, _, _ = _poker(args.seed, args.n)
    cfg = _config(spec, x, args.seed)
    key = jax.random.PRNGKey(args.seed)
    d_g = rb.suggest_d_g(x, cfg.sigma, key=fold_key(key, "probe"))
    params = rb.make_rb_params(fold_key(key, "rb"), N_GRIDS, spec.d,
                               cfg.sigma, d_g)
    d = params.n_features
    b = eigensolver.lobpcg_block_width(args.n, spec.k,
                                       cfg.solver_options.buffer)
    say(f"[kernels] rows={KERNEL_ROWS} d={spec.d} R={N_GRIDS} d_g={d_g} "
        f"D={d} block width={b}")
    xk = jnp.asarray(x[:KERNEL_ROWS])
    out = {"d_g": d_g, "block_width": b}

    # rb_binning: indices must be equal
    rb_args = (xk, params.widths, params.biases, params.hash_a,
               params.hash_c)
    rb_fn = {impl: functools.partial(ops.rb_binning, d_g=d_g, impl=impl)
             for impl in ("pallas", "xla")}
    idx_p = np.asarray(rb_fn["pallas"](*rb_args))
    idx = jnp.asarray(rb_fn["xla"](*rb_args))
    mism = int(np.sum(idx_p != np.asarray(idx)))
    say(f"[kernels] rb_binning: {mism} of {idx_p.size} indices differ")
    check(mism == 0, "rb_binning: pallas indices differ from xla")
    mosaic = {"rb_binning": _lowers_to_mosaic(jax, rb_fn["pallas"],
                                              *rb_args)}

    # ELL products at the fit's widths: degree pass (K = 1), the solver's
    # Gram mat-vec and the two single products (K = block width)
    s = graph.build_normalized_adjacency(idx, d=d, d_g=d_g,
                                         impl="xla").rowscale
    u = jax.random.normal(fold_key(key, "u"), (KERNEL_ROWS, b), jnp.float32)
    q = ops.zt_matmul(idx, u, s, d, d_g=d_g, impl="xla")
    ell = {
        "degrees": (lambda t, impl: graph.rb_degrees(
            t, d=d, d_g=d_g, impl=impl), idx),
        "gram_matmul": (lambda t, impl: ops.gram_matmul(
            idx, t, s, d, d_g=d_g, impl=impl), u),
        "zt_matmul": (lambda t, impl: ops.zt_matmul(
            idx, t, s, d, d_g=d_g, impl=impl), u),
        "z_matmul": (lambda t, impl: ops.z_matmul(
            idx, t, s, d_g=d_g, impl=impl), q),
    }
    errs = {}
    for name, (fn, t) in ell.items():
        got = np.asarray(fn(t, "pallas"), np.float64)
        want = np.asarray(fn(t, "xla"), np.float64)
        errs[name] = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
        say(f"[kernels] {name}: max|pallas − xla| / max|xla| = "
            f"{errs[name]:.3e} (limit {GRAM_RTOL:g})")
        check(errs[name] <= GRAM_RTOL, f"{name}: pallas differs from xla")
        mosaic[name] = _lowers_to_mosaic(
            jax, functools.partial(fn, impl="pallas"), t)
    fused = ops.ell_spmm.gram_vmem_bytes(N_GRIDS, b, d_g) \
        <= ops.GRAM_FUSE_VMEM_BYTES
    say(f"[kernels] gram_matmul route: "
        f"{'fused kernel' if fused else 'zt + z kernels'}")

    # kmeans_assign: equal labels except at ties within float32 rounding
    emb = row_normalize(jax.random.normal(fold_key(key, "emb"),
                                          (KERNEL_ROWS, spec.k)))
    cents = emb[:spec.k]
    lab_p, _ = ops.kmeans_assign(emb, cents, impl="pallas")
    lab_x, _ = ops.kmeans_assign(emb, cents, impl="xla")
    lab_p, lab_x = np.asarray(lab_p), np.asarray(lab_x)
    diff = np.flatnonzero(lab_p != lab_x)
    e64, c64 = np.asarray(emb, np.float64), np.asarray(cents, np.float64)
    d2 = ((e64[diff, None, :] - c64[None]) ** 2).sum(-1)
    gap = np.abs(d2[np.arange(diff.size), lab_p[diff]]
                 - d2[np.arange(diff.size), lab_x[diff]])
    ties = int(np.sum(gap <= 1e-6))
    say(f"[kernels] kmeans_assign: {diff.size} labels differ, "
        f"{ties} of them at float32 ties")
    check(ties == diff.size, "kmeans_assign: labels differ off a tie")
    mosaic["kmeans_assign"] = _lowers_to_mosaic(
        jax, functools.partial(ops.kmeans_assign, impl="pallas"), emb, cents)

    say(f"[kernels] lowered to Mosaic (tpu_custom_call): {mosaic}")
    check(all(mosaic.values()), "a Pallas route did not lower to Mosaic")
    out.update(rel_err=errs, kmeans_label_diffs=int(diff.size),
               mosaic=mosaic, gram_fused=fused)
    return out


def phase_fit(args, jax) -> dict:
    import numpy as np

    from repro.core import metrics
    from repro.core.model import SCRBModel

    spec, x, y, _ = _poker(args.seed, args.n)
    cfg = _config(spec, x, args.seed)
    say(f"[fit] poker N={args.n} d={spec.d} K={spec.k} R={N_GRIDS} "
        f"sigma={cfg.sigma:.6g}")
    t0 = time.perf_counter()
    model = SCRBModel.fit(x, cfg)
    wall = time.perf_counter() - t0
    res = model.fit_result
    diag = res.diagnostics
    ari = metrics.adjusted_rand_index(res.labels, y)
    peak = _peak_bytes(jax.devices()[0])
    say(f"[fit] d_g picked by rb.suggest_d_g: {model.feature_map.d_g} "
        f"(D = {diag['n_features_D']})")
    say(f"[fit] wall {wall:.3f} s, stages (s): "
        + json.dumps({k: round(v, 4) for k, v in res.timings.items()}))
    say(f"[fit] solver {diag['solver']}: {diag['solver_iterations']} "
        f"iterations, resnorms {np.asarray(diag['solver_resnorms']).tolist()}")
    say(f"[fit] ARI vs generator labels {ari:.6f} (need ≥ 0.95); "
        f"peak_bytes_in_use {peak}")
    check(ari >= 0.95, f"fit ARI {ari:.4f} < 0.95")
    model.save(os.path.join(args.work, "model.npz"))
    np.save(os.path.join(args.work, "fit_labels.npy"), res.labels)
    return {"d_g": int(model.feature_map.d_g), "ari": ari, "wall_s": wall,
            "timings_s": res.timings,
            "solver_iterations": int(diag["solver_iterations"]),
            "resnorms": np.asarray(diag["solver_resnorms"]).tolist(),
            "peak_bytes_in_use": peak}


def phase_serve(args, jax) -> dict:
    import numpy as np

    from repro.core.model import SCRBModel
    from repro.serve.cluster_engine import ClusterEngine

    path = os.path.join(args.work, "model.npz")
    _, x, _, fresh = _poker(args.seed, args.n)
    fit_labels = np.load(os.path.join(args.work, "fit_labels.npy"))
    model = SCRBModel.load(path)
    engine = ClusterEngine()
    engine.load_model("poker", path)
    t0 = time.perf_counter()
    cells = engine.warmup("poker")
    say(f"[serve] warmup compiled {cells} cells in "
        f"{time.perf_counter() - t0:.3f} s")
    rows, tickets, off = [], [], 0
    for n in REQUEST_ROWS:
        rows.append(fresh[off:off + n])
        tickets.append(engine.submit("poker", rows[-1]))
        off += n
    engine.drain()
    results = [engine.take(t) for t in tickets]
    for req, res in zip(rows, results):
        want = model.predict(req)
        check(np.array_equal(res.values, want),
              f"engine labels differ from model.predict on a "
              f"{req.shape[0]}-row request "
              f"({int(np.sum(res.values != want))} rows)")
    lat = [r.latency for r in results]
    say(f"[serve] {len(results)} requests of {list(REQUEST_ROWS)} rows: "
        f"engine labels == model.predict; latency (s) "
        f"min {min(lat):.4f} max {max(lat):.4f}")
    t0 = time.perf_counter()
    pred = model.predict(x, batch_size=65_536)
    agree = float(np.mean(pred == fit_labels))
    say(f"[serve] predict(x_train) in {time.perf_counter() - t0:.3f} s "
        f"agrees with the fit labels on {agree:.6f} of rows (need ≥ 0.99)")
    check(agree >= 0.99, f"predict/fit agreement {agree:.4f} < 0.99")
    stats = engine.stats()
    return {"cells": cells, "latency_s": lat, "predict_fit_agreement": agree,
            "engine": {k: stats[k] for k in ("total_compiles", "batches",
                                             "rows_served", "padded_rows")}}


def phase_mesh(args, jax) -> dict:
    import numpy as np

    from repro.core import metrics
    from repro.core.model import SCRBModel
    from repro.launch.mesh import make_host_mesh

    devs = jax.devices()
    n = args.n - args.n % len(devs)
    if n != args.n:
        say(f"[mesh] N cut {args.n} → {n}: placement='mesh' row-shards N "
            f"evenly over {len(devs)} chips")
    spec, x, y, _ = _poker(args.seed, n)
    cfg = _config(spec, x, args.seed)
    out = {"n": n}
    labels = {}
    for name in ("mesh", "single"):
        t0 = time.perf_counter()
        if name == "mesh":
            model = SCRBModel.fit(x, cfg, mesh=make_host_mesh())
        else:
            with jax.default_device(devs[0]):
                model = SCRBModel.fit(x, cfg)
        wall = time.perf_counter() - t0
        res = model.fit_result
        labels[name] = res.labels
        peaks = [_peak_bytes(d) for d in devs]
        ari = metrics.adjusted_rand_index(res.labels, y)
        say(f"[mesh] {name} fit: wall {wall:.3f} s, "
            f"{res.diagnostics['solver_iterations']} iterations, "
            f"ARI vs generator {ari:.6f}, stages (s) "
            + json.dumps({k: round(v, 4) for k, v in res.timings.items()}))
        say(f"[mesh] peak_bytes_in_use per chip after the {name} fit: "
            f"{peaks}")
        out[name] = {"wall_s": wall, "ari_truth": ari, "timings_s":
                     res.timings, "peak_bytes_in_use": peaks,
                     "solver_iterations":
                     int(res.diagnostics["solver_iterations"])}
    agree = metrics.adjusted_rand_index(labels["mesh"], labels["single"])
    say(f"[mesh] mesh vs single-chip labels: ARI {agree:.6f} (need ≥ 0.99)")
    check(agree >= 0.99, f"mesh/single ARI {agree:.4f} < 0.99")
    out["ari_mesh_vs_single"] = agree
    return out


PHASES = {"kernels": phase_kernels, "fit": phase_fit, "serve": phase_serve,
          "mesh": phase_mesh}


def run_phase(args) -> None:
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax

    from repro.utils import use_compile_cache
    use_compile_cache()
    device = _device_info(jax, args.chips)
    out = PHASES[args.phase](args, jax)
    out["device"] = device
    with open(os.path.join(args.work, f"{args.phase}.json"), "w") as f:
        json.dump(out, f, default=float)


# --------------------------------------------------------------------------
# parent side: no JAX here
# --------------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--n", type=int, default=POKER_N,
                    help="training rows (default: poker's full N)")
    ap.add_argument("--phase", choices=sorted(PHASES), help=argparse.SUPPRESS)
    ap.add_argument("--work", default=WORK, help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.phase:
        run_phase(args)
        return 0
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        say(f"chip_smoke: no repro package under {ROOT}/src", file=sys.stderr)
        return 2
    if args.n != POKER_N:
        say(f"N cut: {POKER_N} → {args.n} rows (--n)")
    phases = ("mesh",) if args.chips == 4 else ("kernels", "fit", "serve")
    shutil.rmtree(args.work, ignore_errors=True)
    os.makedirs(args.work)
    device = None
    try:
        for phase in phases:
            t0 = time.perf_counter()
            cmd = [sys.executable, os.path.abspath(__file__), "--phase", phase,
                   "--seed", str(args.seed), "--chips", str(args.chips),
                   "--n", str(args.n), "--work", args.work]
            rc = subprocess.run(cmd).returncode
            if rc != 0:
                say(f"chip_smoke: phase {phase} failed (exit {rc})",
                    file=sys.stderr)
                return rc
            with open(os.path.join(args.work, f"{phase}.json")) as f:
                got = json.load(f)["device"]
            if device is not None and got != device:
                say(f"chip_smoke: phase {phase} ran on {got}, not {device}",
                    file=sys.stderr)
                return 1
            device = got
            say(f"[{phase}] passed in {time.perf_counter() - t0:.1f} s "
                f"(process start and compilation included)")
    finally:
        shutil.rmtree(args.work, ignore_errors=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
