"""Traffic kind ``serve_zipf``: several fitted models resident in one
``ClusterEngine``, each labelling new rows of its own, offered as an open
loop whose popularity is Zipf-skewed across the models and whose hot model
changes.

Set-up, for each of the configuration's ``members`` (listed in the first
epoch's rank order): draw its training rows and a held-out pool of
``pool_rows`` from ``--seed``, fit it with the plain reference (that time is
the check's and is left out of ``setup_s``), write it as a model artifact,
``load_model`` → ``warmup``, and send one request of each bucket size. One
engine with the default ``EngineConfig`` holds every member resident.

The window offers ``rate_rps · --seconds`` requests in one epoch of equal
length per order of the members (24 for four): the first epoch ranks the
members as the configuration lists them, then every other order comes once,
in the seed's order. Each epoch holds the same number of requests, due on a
Poisson schedule that ends with the epoch (the exponential quantiles in the
seed's order), and gives them to the ranks by count in Zipf shares of
exponent ``zipf_s``; a rank's requests take the log-uniform quantiles of
their count in [rows_min, rows_max] as sizes, the same in every epoch. So
the work an epoch offers depends only on its order, and over a window every
member takes each rank equally often and gets the same number of requests
and the same multiset of sizes in every seed; a seed moves only the order of
the epochs and of the requests inside each. Rows are a slice of the member's
pool at a seeded offset.
The loop, the latency (from the due time) and the metrics are those of
``serve_open_loop``.

The traced slice runs ``traced_seconds`` of one epoch per member, whose
orders form a Latin square (each member takes each rank once), under the
profiler, with the ``engine.step`` and ``loadgen.wait`` annotations.

The check counts the requests never answered and the engine's evictions, and
compares each member's served labels with its reference model's nearest
centroid, as ``serve_open_loop`` does for its one model (all rows up to
``check_values`` values a member, else its largest request and a seeded
sample); ``label_gap`` is the largest member's, and each member's is kept
in ``ctx.state["label_gaps"]`` (``bench/calibrate_zoo.py`` reads them).
"""
from __future__ import annotations

import collections
import gc
import itertools
import os
import time

import numpy as np

import checks
import data
import harness
import reference
import trace_reduce

open_loop = harness.load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "serve_open_loop.py"), "serve_open_loop")


def setup(ctx) -> None:
    from repro.serve.cluster_engine import ClusterEngine
    n_train, pool_rows = ctx.spec["train_rows"], ctx.mix["pool_rows"]
    shared = {k: v for k, v in ctx.config.items() if k != "members"}
    zoo = [dict(shared, **m) for m in ctx.config["members"]]
    if len(zoo) != ctx.mix["models"]:
        raise harness.BenchError(f"the mix serves {ctx.mix['models']} "
                                 f"models, the configuration has {len(zoo)}")
    engine = ClusterEngine()
    names, pools, models = [], [], []
    for i, cfg in enumerate(zoo):
        x, _ = data.dataset(cfg, n_train + pool_rows, data.sub_seed(
            ctx.seed, 3, i))
        train, pool = x[:n_train], x[n_train:]
        sigma = data.suggest_sigma(train)
        seed = data.sub_seed(ctx.seed, 1, i)
        t = time.perf_counter()
        model = reference.fit(
            train, k=cfg["k"], n_grids=cfg["n_grids"], sigma=sigma,
            d_g=cfg["d_g"], seed=seed, tol=cfg["solver_tol"],
            iters=cfg["solver_iters"], kmeans_iters=cfg["kmeans_iters"],
            kmeans_replicates=cfg["kmeans_replicates"])
        ref_s = time.perf_counter() - t
        ctx.reference_s += ref_s
        checks.say(f"reference fit of {cfg['name']} on {n_train} rows: "
                   f"{ref_s:.4f} s (not set-up), sigma {sigma:.6g}, d_g "
                   f"{model['d_g']}")
        path = os.path.join(ctx.out_dir, f"model-{i}.npz")
        open_loop._write_artifact(path, model, cfg, sigma, seed)
        engine.load_model(cfg["name"], path)
        engine.warmup(cfg["name"])
        for bucket in engine.config.buckets:
            engine.predict(cfg["name"], pool[:bucket])
        names.append(cfg["name"])
        pools.append(pool)
        models.append(model)
    # ``pool`` is what bench/knee.py reads the pool length from; every
    # member's pool has ``pool_rows`` rows
    ctx.state.update(names=names, pools=pools, pool=pools[0], models=models,
                     engine=engine)
    checks.say(f"engine warm: {engine.stats()['cells']} cells, resident "
               f"{engine.stats()['resident']}, buckets "
               f"{list(engine.config.buckets)}")


def zipf_shares(n: int, s: float) -> np.ndarray:
    w = 1.0 / np.arange(1, n + 1) ** s
    return w / w.sum()


def epoch_orders(seed: int, n: int) -> list:
    """Every order of ``n`` members once: the listed order first, the
    others in the seed's order."""
    rest = list(itertools.permutations(range(n)))[1:]
    rng = np.random.default_rng(data.sub_seed(seed, 4))
    return [tuple(range(n))] + [rest[j] for j in rng.permutation(len(rest))]


def latin_orders(seed: int, n: int) -> list:
    """``n`` orders in which each member takes each rank once."""
    base = np.random.default_rng(data.sub_seed(seed, 8)).permutation(n)
    return [tuple(int(base[(r + e) % n]) for r in range(n))
            for e in range(n)]


def _counts(total: int, shares: np.ndarray) -> np.ndarray:
    """``total`` split by ``shares`` (largest remainders)."""
    raw = total * shares
    out = np.floor(raw).astype(np.int64)
    out[np.argsort(out - raw)[:total - int(out.sum())]] += 1
    return out


def _log_uniform(n: int, lo: int, hi: int) -> np.ndarray:
    """The ``n`` log-uniform quantiles of sizes in [lo, hi]."""
    q = (np.arange(n) + 0.5) / max(n, 1)
    return np.clip(np.floor(lo * ((hi + 1) / lo) ** q).astype(np.int64),
                   lo, hi)


def schedule(seed: int, rate: float, seconds: float, mix: dict,
             pool_rows: int, *, orders: list | None = None) -> dict:
    """Due times (s from the window's start), sizes, pool offsets and the
    member (index) of each request, in due order; one epoch per order of
    the mix's ``models``, unless ``orders`` are given. Every epoch gives
    each rank the same count and sizes, so a seed moves only the order of
    the epochs and of the requests inside each."""
    orders = orders or epoch_orders(seed, int(mix["models"]))
    n_members = len(orders[0])
    n_epochs = len(orders)
    per_epoch = max(1, int(round(rate * seconds / n_epochs)))
    length = seconds / n_epochs
    rng = np.random.default_rng(data.sub_seed(seed, 2))
    q = (np.arange(per_epoch) + 0.5) / per_epoch
    counts = _counts(per_epoch, zipf_shares(n_members, float(mix["zipf_s"])))
    rank = np.repeat(np.arange(n_members), counts)
    size = np.concatenate([_log_uniform(c, mix["rows_min"], mix["rows_max"])
                           for c in counts])
    due, member, sizes = [], [], []
    for e, order in enumerate(orders):
        gaps = rng.permutation(-np.log1p(-q))
        due.append(e * length + np.cumsum(gaps) * (length / np.sum(gaps)))
        mine = rng.permutation(per_epoch)
        member.append(np.asarray(order)[rank[mine]])
        sizes.append(size[mine])
    due, member, sizes = (np.concatenate(a) for a in (due, member, sizes))
    offsets = rng.integers(0, pool_rows - sizes + 1)
    return {"due": due, "sizes": sizes, "offsets": offsets,
            "member": member}


def _schedule(ctx, seconds: float, orders=None) -> dict:
    return schedule(ctx.seed, ctx.spec["rate_rps"], seconds, ctx.mix,
                    ctx.state["pool"].shape[0], orders=orders)


def _loop(ctx, sched: dict, *, annotate: bool) -> dict:
    """``serve_open_loop._loop`` with each request sent to its member's
    model, with rows from that member's pool."""
    from jax.profiler import TraceAnnotation
    engine, pools = ctx.state["engine"], ctx.state["pools"]
    names = ctx.state["names"]
    due, sizes, offs = sched["due"], sched["sizes"], sched["offsets"]
    member = sched["member"]
    n = due.shape[0]
    latency = np.full(n, np.nan)
    labels = [None] * n
    late = np.zeros(n)
    steps, step_at, pauses = [], [], []
    outstanding = collections.deque()
    note = TraceAnnotation if annotate else (lambda _name: open_loop._Null())

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
                rows = pools[member[i]][offs[i]:offs[i] + sizes[i]]
                outstanding.append((i, engine.submit(names[member[i]],
                                                     rows)))
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


def _served(ctx, sched, out) -> None:
    open_loop._summary(ctx, sched, out)
    ctx.state["evictions"] = ctx.state["engine"].stats()["evictions"]
    ms = out["latency"] * 1e3
    checks.say("latency ms p50 by member: " + ", ".join(
        f"{name} {np.nanpercentile(ms[sched['member'] == m], 50):.4f}"
        for m, name in enumerate(ctx.state["names"])
        if np.any(sched["member"] == m)))


def window(ctx, seconds: float) -> dict:
    sched = _schedule(ctx, seconds)
    out = _loop(ctx, sched, annotate=False)
    _served(ctx, sched, out)
    lat = out["latency"]
    done = np.isfinite(lat)
    ms = lat[done] * 1e3
    last = float(np.max(lat[done] + sched["due"][done]))
    checks.say(f"latency ms p50 {np.percentile(ms, 50):.4f} p95 "
               f"{np.percentile(ms, 95):.4f} p99 {np.percentile(ms, 99):.4f}")
    return {"metrics": {
        "serve_p50_ms": float(np.percentile(ms, 50)),
        "serve_rows_per_s": float(np.sum(sched["sizes"]) / last)},
        "attempted": int(lat.size), "failed": int(np.sum(~done))}


def traced(ctx) -> dict:
    from jax.profiler import TraceAnnotation
    jax = ctx.jax
    sched = _schedule(ctx, float(ctx.mix["traced_seconds"]), latin_orders(
        ctx.seed, int(ctx.mix["models"])))
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
    _served(ctx, sched, out)
    ctx.probes["engine_step_ms"] = float(1e3 * out["steps"].mean())
    ctx.probes["engine_rows"] = after["rows_served"] - before["rows_served"]
    ctx.probes["engine_batches"] = after["batches"] - before["batches"]
    if "model_switches" in after:
        ctx.probes["engine_model_switches"] = (after["model_switches"]
                                               - before["model_switches"])
    ctx.trace = trace_reduce.reduce(trace_reduce.xplane_file(path),
                                    window="serve")
    lat = out["latency"]
    return {"attempted": int(lat.size),
            "failed": int(np.sum(~np.isfinite(lat)))}


def release(ctx) -> None:
    ctx.state.pop("engine", None)


def check(ctx) -> dict:
    sched, out = ctx.state["sched"], ctx.state["served"]
    got = [j for j, lab in enumerate(out["labels"])
           if lab is not None and len(lab) == sched["sizes"][j]]
    t = time.perf_counter()
    gaps, compared = {}, 0
    for m, name in enumerate(ctx.state["names"]):
        pool = ctx.state["pools"][m]
        mine = [j for j in got if sched["member"][j] == m]
        some = open_loop.compared(data.sub_seed(ctx.seed, 6, m),
                                  sched["sizes"], mine, pool.shape[1],
                                  int(ctx.mix["check_values"]))
        if not some:
            continue
        rows = np.concatenate([pool[sched["offsets"][j]:sched["offsets"][j]
                                    + sched["sizes"][j]] for j in some])
        labels = np.concatenate([out["labels"][j] for j in some])
        gaps[name] = checks.served_gap(ctx.state["models"][m], rows, labels)
        compared += rows.shape[0]
        checks.say(f"{name}: label_gap {gaps[name]!r} over {rows.shape[0]} "
                   f"of {int(np.sum(sched['sizes'][mine]))} served rows "
                   f"({len(some)} of {len(mine)} answered requests)")
    checks.say(f"served rows compared: {compared} in "
               f"{time.perf_counter() - t:.4f} s")
    ctx.state["label_gaps"] = gaps
    nums = {"missing": float(len(out["labels"]) - len(got)),
            "label_gap": max(gaps.values(), default=0.0),
            "evictions": float(ctx.state["evictions"])}
    return checks.judge(nums, ctx.spec["limits"])
