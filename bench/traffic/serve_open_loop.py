"""Traffic kind ``serve_open_loop``: new rows labelled by a fitted model
through ``ClusterEngine``, offered as an open loop at a fixed rate.

Set-up draws the training rows and a pool of held-out rows of the same
mixture from ``--seed``, fits the model with the plain reference (so that the
check compares against state the program did not make; its time is the
check's and is left out of ``setup_s``), writes it as a model artifact,
and hands it to the engine the way a deployment does: ``load_model(path)``
→ ``warmup`` (every bucket cell), then one request of each bucket size.

The window offers ``rate_rps · --seconds`` requests, due on a Poisson
schedule that ends at ``--seconds``. Every seed gets the same inter-arrival
gaps and request sizes (the exponential and log-uniform quantiles), shuffled
in its own order; rows are a slice of the pool at a seeded offset. One
thread submits each request once it is due and calls ``step()`` while work
is pending; after the window it drains what is left. A request's latency
runs from its due time to its completion.

The traced slice runs the same loop for ``traced_seconds`` under the
profiler, with ``engine.step`` and ``loadgen.wait`` annotations.

The check counts the requests never answered and compares the served labels
with the reference's nearest centroid: of every answered request, or, where
their rows hold more than ``check_values`` values, of the largest request
and others drawn from the seed up to that many.
"""
from __future__ import annotations

import collections
import gc
import json
import os
import time

import numpy as np

import checks
import data
import reference
import trace_reduce

MODEL = "bench"


def _write_artifact(path: str, model: dict, cfg: dict, sigma: float,
                    seed: int) -> None:
    """The fitted reference model in the program's model-artifact format
    (format 1.1: a JSON header beside the arrays, in one npz)."""
    k, r, d_g = cfg["k"], cfg["n_grids"], model["d_g"]
    meta = {"format_version": "1.1",
            "config": {"n_clusters": k, "n_grids": r, "sigma": sigma,
                       "d_g": d_g, "seed": seed, "impl": "auto"},
            "laplacian_normalize": True, "has_centroids": True,
            "feature_map": {"name": "rb", "n_grids": r, "sigma": sigma,
                            "d_g": d_g, "impl": "auto"},
            "data_dim": cfg["d"]}
    arrays = {"degree_dual": model["dual"],
              "right_vectors": model["right_vectors"],
              "singular_values": model["singular_values"],
              "centroids": model["centroids"]}
    arrays.update({f"fm_{name}": a for name, a in model["grids"].items()})
    with open(path, "wb") as f:
        np.savez(f, _meta=np.frombuffer(json.dumps(meta).encode(), np.uint8),
                 **arrays)


def setup(ctx) -> None:
    from repro.serve.cluster_engine import ClusterEngine
    cfg, spec, mix = ctx.config, ctx.spec, ctx.mix
    n_train = spec["train_rows"]
    x, _ = data.dataset(cfg, n_train + mix["pool_rows"], ctx.seed)
    train, pool = x[:n_train], x[n_train:]
    sigma = data.suggest_sigma(train)
    seed = data.sub_seed(ctx.seed, 1)
    t = time.perf_counter()
    model = reference.fit(
        train, k=cfg["k"], n_grids=cfg["n_grids"], sigma=sigma,
        d_g=cfg["d_g"], seed=seed, tol=cfg["solver_tol"],
        iters=cfg["solver_iters"], kmeans_iters=cfg["kmeans_iters"],
        kmeans_replicates=cfg["kmeans_replicates"])
    ctx.reference_s += time.perf_counter() - t
    checks.say(f"reference fit of the served model on {n_train} rows: "
               f"{ctx.reference_s:.4f} s (not set-up), sigma {sigma:.6g}, "
               f"d_g {model['d_g']}")
    path = os.path.join(ctx.out_dir, "model.npz")
    _write_artifact(path, model, cfg, sigma, seed)
    engine = ClusterEngine()
    engine.load_model(MODEL, path)
    cells = engine.warmup(MODEL)
    for bucket in engine.config.buckets:
        engine.predict(MODEL, pool[:bucket])
    ctx.state.update(pool=pool, model=model, engine=engine)
    checks.say(f"engine warm: {cells} cells compiled or loaded, buckets "
               f"{list(engine.config.buckets)}")


def schedule(seed: int, rate: float, seconds: float, mix: dict,
             pool_rows: int) -> dict:
    """Due times (s from the window's start), sizes and pool offsets."""
    n = max(1, int(round(rate * seconds)))
    rng = np.random.default_rng(data.sub_seed(seed, 2))
    q = (np.arange(n) + 0.5) / n
    gaps = rng.permutation(-np.log1p(-q))
    due = np.cumsum(gaps) * (seconds / np.sum(gaps))
    lo, hi = mix["rows_min"], mix["rows_max"]
    sizes = np.floor(lo * ((hi + 1) / lo) ** q).astype(np.int64)
    sizes = rng.permutation(np.clip(sizes, lo, hi))
    offsets = rng.integers(0, pool_rows - sizes + 1)
    return {"due": due, "sizes": sizes, "offsets": offsets}


def _loop(ctx, sched: dict, *, annotate: bool) -> dict:
    """Offer ``sched`` to the engine; returns per-request latency (s),
    labels, the generator's lateness, each ``step()`` call's start and
    time, and the garbage collector's pauses (start, seconds, generation)
    inside the loop."""
    from jax.profiler import TraceAnnotation
    engine, pool = ctx.state["engine"], ctx.state["pool"]
    due, sizes, offs = sched["due"], sched["sizes"], sched["offsets"]
    n = due.shape[0]
    latency = np.full(n, np.nan)
    labels = [None] * n
    late = np.zeros(n)
    steps, step_at, pauses = [], [], []
    outstanding = collections.deque()
    note = TraceAnnotation if annotate else (lambda _name: _Null())

    def on_gc(phase, info, _t=[0.0]):
        if phase == "start":
            _t[0] = time.perf_counter()
        else:
            pauses.append((_t[0] - t0, time.perf_counter() - _t[0],
                           info["generation"]))

    gc.callbacks.append(on_gc)
    t0 = time.perf_counter()
    i = 0
    try:
        while i < n or outstanding:
            now = time.perf_counter() - t0
            while i < n and due[i] <= now:
                rows = pool[offs[i]:offs[i] + sizes[i]]
                outstanding.append((i, engine.submit(MODEL, rows)))
                late[i] = now - due[i]
                i += 1
            if outstanding:
                t = time.perf_counter()
                with note("engine.step"):
                    engine.step()
                steps.append(time.perf_counter() - t)
                step_at.append(t - t0)
                while outstanding:
                    j, ticket = outstanding[0]
                    try:
                        res = engine.take(ticket)
                    except KeyError:
                        break
                    outstanding.popleft()
                    latency[j] = res.completed_at - t0 - due[j]
                    labels[j] = res.values
            else:
                with note("loadgen.wait"):
                    time.sleep(max(0.0, due[i] - (time.perf_counter() - t0)))
    finally:
        gc.callbacks.remove(on_gc)
    return {"latency": latency, "labels": labels, "late": late,
            "steps": np.asarray(steps), "step_at": np.asarray(step_at),
            "gc": pauses, "t0": t0, "end": time.perf_counter() - t0}


class _Null:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def _summary(ctx, sched, out) -> None:
    late = out["late"] * 1e3
    checks.say(f"{len(late)} requests, {int(np.sum(sched['sizes']))} rows; "
               f"generator lateness ms p50 {np.percentile(late, 50):.4f} "
               f"p99 {np.percentile(late, 99):.4f} max {late.max():.4f}; "
               f"{len(out['steps'])} steps, mean "
               f"{1e3 * out['steps'].mean():.4f} ms")
    slow = np.argsort(out["steps"])[::-1][:5]
    checks.say("longest steps (at s, ms): " + " ".join(
        f"({out['step_at'][j]:.4f}, {1e3 * out['steps'][j]:.4f})"
        for j in slow))
    pauses = out["gc"]
    if pauses:
        worst = max(pauses, key=lambda p: p[1])
        checks.say(f"gc pauses in the loop: {len(pauses)}, total "
                   f"{1e3 * sum(p[1] for p in pauses):.4f} ms, longest "
                   f"{1e3 * worst[1]:.4f} ms (generation {worst[2]}, at "
                   f"{worst[0]:.4f} s)")
    ctx.state.update(sched=sched, served=out)


def window(ctx, seconds: float) -> dict:
    sched = schedule(ctx.seed, ctx.spec["rate_rps"], seconds, ctx.mix,
                     ctx.state["pool"].shape[0])
    out = _loop(ctx, sched, annotate=False)
    _summary(ctx, sched, out)
    lat = out["latency"]
    done = np.isfinite(lat)
    ms = lat[done] * 1e3
    last = float(np.max(out["latency"][done] + sched["due"][done]))
    checks.say(f"latency ms p50 {np.percentile(ms, 50):.4f} p95 "
               f"{np.percentile(ms, 95):.4f} p99 {np.percentile(ms, 99):.4f}")
    return {"metrics": {
        "serve_p50_ms": float(np.percentile(ms, 50)),
        "serve_rows_per_s": float(np.sum(sched["sizes"]) / last)},
        "attempted": int(lat.size), "failed": int(np.sum(~done))}


def traced(ctx) -> dict:
    from jax.profiler import TraceAnnotation
    jax = ctx.jax
    seconds = float(ctx.mix["traced_seconds"])
    sched = schedule(ctx.seed, ctx.spec["rate_rps"], seconds, ctx.mix,
                     ctx.state["pool"].shape[0])
    engine = ctx.state["engine"]
    before = engine.stats()
    path = trace_reduce.fresh_dir(ctx.out_dir, "trace")
    jax.profiler.start_trace(path)
    try:
        with TraceAnnotation("serve"):
            out = _loop(ctx, sched, annotate=True)
    finally:
        jax.profiler.stop_trace()
    after = engine.stats()
    _summary(ctx, sched, out)
    ctx.probes["engine_step_ms"] = float(1e3 * out["steps"].mean())
    ctx.probes["engine_rows"] = after["rows_served"] - before["rows_served"]
    ctx.probes["engine_batches"] = after["batches"] - before["batches"]
    ctx.trace = trace_reduce.reduce(trace_reduce.xplane_file(path),
                                    window="serve")
    lat = out["latency"]
    return {"attempted": int(lat.size),
            "failed": int(np.sum(~np.isfinite(lat)))}


def release(ctx) -> None:
    ctx.state.pop("engine", None)


def compared(seed: int, sizes, got: list, dim: int, cap: int) -> list:
    """The answered requests the check compares: all of them while their
    rows hold at most ``cap`` values (rows × d), else the largest and then
    others in an order drawn from the seed, up to ``cap``."""
    if not got or int(np.sum(sizes[got])) * dim <= cap:
        return got
    largest = max(got, key=lambda j: sizes[j])
    rng = np.random.default_rng(data.sub_seed(seed, 5))
    take, total = [largest], int(sizes[largest])
    for j in rng.permutation(got).tolist():
        if j != largest and (total + sizes[j]) * dim <= cap:
            take.append(j)
            total += int(sizes[j])
    return sorted(take)


def check(ctx) -> dict:
    sched, out = ctx.state["sched"], ctx.state["served"]
    got = [j for j, lab in enumerate(out["labels"])
           if lab is not None and len(lab) == sched["sizes"][j]]
    pool = ctx.state["pool"]
    some = compared(ctx.seed, sched["sizes"], got, pool.shape[1],
                    int(ctx.mix["check_values"]))
    t = time.perf_counter()
    rows = np.concatenate([pool[sched["offsets"][j]:sched["offsets"][j]
                                + sched["sizes"][j]] for j in some])
    labels = np.concatenate([out["labels"][j] for j in some])
    nums = {"missing": float(len(out["labels"]) - len(got)),
            "label_gap": checks.served_gap(ctx.state["model"], rows, labels)}
    checks.say(f"served rows compared: {rows.shape[0]} of "
               f"{int(np.sum(sched['sizes'][got]))} ({len(some)} of "
               f"{len(got)} answered requests) in "
               f"{time.perf_counter() - t:.4f} s")
    return checks.judge(nums, ctx.spec["limits"])
