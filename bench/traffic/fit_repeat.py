"""Traffic kind ``fit_repeat``: ``SCRBModel.fit`` run back to back.

A fit's cost depends on its input: on the CPU, LOBPCG took 8 to 34
iterations over config seeds on one data set, and 17 to 300 over data sets
for one config seed (PR 12). So every seed gets the same set of fits, in an
order of its own. The cell's ``fit_set_seed`` draws ``jobs`` fit jobs
(rows from the configuration's generator, a config seed each); ``--seed``
draws their order and which of them the check compares.

Set-up draws every job's rows, picks each σ by the median heuristic, and
runs one warm-up fit on the first job's rows with a config seed of its own,
which compiles every program a fit uses. The window fits the jobs in the
seed's order, cycling through the set until ``--seconds`` have passed and a
cycle is whole, so every run does whole sets. ``fit_s`` is the mean time of
a fit.

The traced slice is one fit of the seed's first job under the profiler
(annotated ``fit``), followed by the metrics' dispatch probes.
"""
from __future__ import annotations

import time

import numpy as np

import checks
import data
import trace_reduce

WARMUP_TAG = 1 << 20


def _program_config(ctx, sigma: float, seed: int):
    from repro.core import SCRBConfig
    from repro.core.options import SolverOptions
    cfg = ctx.config
    return SCRBConfig(
        n_clusters=cfg["k"], n_grids=cfg["n_grids"], d_g=cfg["d_g"],
        sigma=sigma, seed=seed,
        kmeans_iters=cfg["kmeans_iters"],
        kmeans_replicates=cfg["kmeans_replicates"],
        solver_options=SolverOptions(tol=cfg["solver_tol"],
                                     iters=cfg["solver_iters"]))


def _fit(ctx, job: dict, seed: int):
    from repro.core.model import SCRBModel
    return SCRBModel.fit(job["x"], _program_config(ctx, job["sigma"], seed))


def _answer(model, job: int, seconds: float) -> dict:
    """What the fit produced, as host arrays: the fitted model (grids, bin
    counts, V, Σ, centroids) and the training labels."""
    res = model.fit_result
    state = model.feature_map.state_dict()
    return {"job": job,
            "grids": {k: np.asarray(state[k]) for k in
                      ("widths", "biases", "hash_a", "hash_c")},
            "d_g": int(model.feature_map.d_g),
            "dual": np.asarray(model.degree_dual),
            "right_vectors": np.asarray(model.right_vectors),
            "singular_values": np.asarray(model.singular_values),
            "centroids": np.asarray(model.centroids),
            "labels": np.asarray(res.labels),
            "iterations": int(res.diagnostics["solver_iterations"]),
            "seconds": seconds}


def jobs(config: dict, rows: int, set_seed: int, count: int) -> list:
    """The cell's fit jobs: rows, true labels, σ and a config seed each."""
    out = []
    for j in range(count):
        x, y = data.dataset(config, rows, data.sub_seed(set_seed, j))
        out.append({"x": x, "y": y, "sigma": data.suggest_sigma(x),
                    "seed": data.sub_seed(set_seed, 1000 + j)})
    return out


def setup(ctx) -> None:
    spec = ctx.spec
    todo = jobs(ctx.config, spec["rows"], spec["fit_set_seed"],
                ctx.mix["jobs"])
    order = np.random.default_rng(data.sub_seed(ctx.seed, 3)).permutation(
        len(todo))
    ctx.state.update(jobs=todo, order=[int(j) for j in order])
    t = time.perf_counter()
    model = _fit(ctx, todo[0], data.sub_seed(spec["fit_set_seed"],
                                             WARMUP_TAG))
    checks.say(f"warm-up fit {time.perf_counter() - t:.4f} s; "
               f"{len(todo)} jobs of N {spec['rows']}, d "
               f"{ctx.config['d']}, d_g {model.feature_map.d_g}, order "
               f"{ctx.state['order']}; warm-up iterations "
               f"{model.fit_result.diagnostics['solver_iterations']}")


def window(ctx, seconds: float) -> dict:
    todo, order = ctx.state["jobs"], ctx.state["order"]
    answers = []
    t0 = time.perf_counter()
    while True:
        j = order[len(answers) % len(order)]
        t = time.perf_counter()
        model = _fit(ctx, todo[j], todo[j]["seed"])
        dt = time.perf_counter() - t
        answers.append(_answer(model, j, dt))
        del model
        if (time.perf_counter() - t0 >= seconds
                and len(answers) % len(order) == 0):
            break
    ctx.state["answers"] = answers
    checks.say(f"{len(answers)} fits (job, s, iterations): " + " ".join(
        f"({a['job']}, {a['seconds']:.4f}, {a['iterations']})"
        for a in answers))
    return {"metrics": {"fit_s": float(np.mean([a["seconds"]
                                                for a in answers]))},
            "attempted": len(answers), "failed": 0}


def traced(ctx) -> dict:
    from jax.profiler import TraceAnnotation
    jax = ctx.jax
    j = ctx.state["order"][0]
    job = ctx.state["jobs"][j]
    path = trace_reduce.fresh_dir(ctx.out_dir, "trace")
    jax.profiler.start_trace(path)
    try:
        t = time.perf_counter()
        with TraceAnnotation("fit"):
            model = _fit(ctx, job, job["seed"])
        answer = _answer(model, j, time.perf_counter() - t)
        del model
        ctx.state.update(answers=[answer], traced_fit=answer, x=job["x"])
        for reader in ctx.cell.readers.values():
            if hasattr(reader, "probe"):
                with TraceAnnotation("dispatch_probe"):
                    reader.probe(ctx)
    finally:
        jax.profiler.stop_trace()
    ctx.trace = trace_reduce.reduce(trace_reduce.xplane_file(path),
                                    window="fit")
    return {"attempted": 1, "failed": 0}


def release(ctx) -> None:
    """Nothing to free: each fit's model is dropped once its answer is
    copied to the host."""


def check(ctx) -> dict:
    answers, todo = ctx.state["answers"], ctx.state["jobs"]
    take = min(int(ctx.mix["checked_fits"]), len(answers))
    rng = np.random.default_rng(data.sub_seed(ctx.seed, 7))
    worst = None
    for i in sorted(rng.choice(len(answers), take, replace=False).tolist()):
        a = answers[i]
        t = time.perf_counter()
        nums = checks.fit_numbers(todo[a["job"]]["x"], todo[a["job"]]["y"],
                                  a, n_grids=ctx.config["n_grids"])
        checks.say(f"fit {i} (job {a['job']}; {time.perf_counter() - t:.4f}"
                   f" s to check): "
                   + " ".join(f"{k} {v!r}" for k, v in nums.items()))
        worst = nums if worst is None else {k: max(worst[k], nums[k])
                                            for k in nums}
    return checks.judge(worst, ctx.spec["limits"])
