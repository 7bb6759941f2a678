"""Observability overhead bench + trace validation (the PR 10 gate).

Three subprocess legs time the *same* fit under three observability states —
subprocesses because both switches act at import time, so each leg needs a
fresh interpreter:

  baseline — ``REPRO_OBS_DISABLED=1``: spans are no-ops, instruments drop
             writes. The honest "the subsystem does not exist" wall-clock.
  default  — observability importable, metrics recording, tracing *off*
             (the shipping default; what every user pays).
  traced   — ``REPRO_TRACE=<path>``: every stage/eigensolve/h2d span
             recorded with device-sync closes + Chrome export at exit.

Timing protocol: run times within one process correlate strongly (CPU
placement, allocator state), so repeating inside a single process cannot
separate a few-percent effect from which-core-did-I-land-on noise. Each
leg therefore runs ``--procs`` independent interpreters in *interleaved*
order (baseline, default, traced, baseline, ...), each doing one warmup
fit then ``--repeats`` timed fits; a leg's time is the min over all its
processes × repeats. ``--gate`` enforces the CI budget: default within 1%
of baseline, traced within 5%.

A fourth in-process leg runs a ``placement="partitioned"`` fit with
``workers=2`` and ``SCRBConfig(trace=...)`` and validates the exported
Chrome trace structurally: per-partition ``partition_fit`` spans on ≥ 2
distinct thread tracks, each temporally contained in the root ``fit`` span
— the acceptance criterion's Perfetto picture, checked as JSON. The trace
file is kept (CI uploads it as an artifact).

Snapshot: ``bench_results/BENCH_PR10.json``.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(os.path.dirname(HERE), "src")


# --------------------------------------------------------------------------
# child: one observability state, fixed fit workload, min-of-repeats
# --------------------------------------------------------------------------

def _child(n: int, repeats: int) -> None:
    from repro.core.executor import SCRBConfig, execute
    from repro.core.options import SolverOptions
    from repro.data.synthetic import make_blobs

    x, _ = make_blobs(n, 8, 4, seed=0)
    cfg = SCRBConfig(n_clusters=4, n_grids=64, sigma=1.5, d_g=512,
                     solver_options=SolverOptions(tol=1e-3),
                     kmeans_replicates=2, seed=0)
    execute(x, cfg)                        # warmup: compiles + first traffic
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        res = execute(x, cfg)
        times.append(time.perf_counter() - t0)
    assert res.labels is not None and res.labels.shape == (n,)
    print(json.dumps({"fit_s": min(times), "all_s": times,
                      "timings": res.timings}))


def _run_child_proc(env_extra: dict, n: int, repeats: int) -> dict:
    env = dict(os.environ)
    extra = env.get("PYTHONPATH", "")
    env["PYTHONPATH"] = SRC + (os.pathsep + extra if extra else "")
    env.pop("REPRO_OBS_DISABLED", None)
    env.pop("REPRO_TRACE", None)
    env.update(env_extra)
    cmd = [sys.executable, os.path.abspath(__file__), "--run-child",
           "--n", str(n), "--repeats", str(repeats)]
    proc = subprocess.run(cmd, env=env, capture_output=True, text=True,
                          check=False)
    if proc.returncode != 0:
        raise RuntimeError(
            f"obs_bench child leg failed:\n{proc.stderr[-2000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def run_legs(legs: dict, n: int, repeats: int, procs: int) -> dict:
    """Interleaved: one process per leg per round, so slow-machine phases
    (thermal, noisy neighbors) hit every leg equally instead of whichever
    leg ran last."""
    samples = {name: [] for name in legs}
    for round_i in range(procs):
        for name, env_extra in legs.items():
            child = _run_child_proc(env_extra, n, repeats)
            samples[name].extend(child["all_s"])
            print(f"[obs] round {round_i} {name:9s}: "
                  f"{', '.join(f'{t:.3f}' for t in child['all_s'])}")
    return {name: {"name": name, "fit_s": min(ts), "all_s": ts}
            for name, ts in samples.items()}


# --------------------------------------------------------------------------
# in-process: partitioned traced fit → structural Chrome-trace validation
# --------------------------------------------------------------------------

def validate_partitioned_trace(trace: dict) -> dict:
    """Structural checks on the Chrome trace of a partitioned fit; returns
    summary facts (raises AssertionError with a reason on violation)."""
    events = trace["traceEvents"]
    xs = [e for e in events if e.get("ph") == "X"]
    roots = [e for e in xs if e["name"] == "fit"
             and e.get("args", {}).get("placement") == "partitioned"]
    assert roots, "no root 'fit' span with placement=partitioned"
    root = roots[0]
    parts = [e for e in xs if e["name"] == "partition_fit"]
    assert parts, "no per-partition 'partition_fit' spans"
    tids = {e["tid"] for e in parts}
    assert len(tids) >= 2, \
        f"partition_fit spans on {len(tids)} thread track(s); expected ≥ 2 " \
        f"parallel worker lanes (workers=2)"
    slack = 1e3   # µs — perf_counter_ns is per-thread-read, allow scheduling
    for e in parts:
        assert e["ts"] >= root["ts"] - slack and \
            e["ts"] + e["dur"] <= root["ts"] + root["dur"] + slack, \
            f"partition_fit span [{e['ts']:.0f}, {e['ts'] + e['dur']:.0f}] " \
            f"escapes the root fit span"
    thread_names = [e["args"]["name"] for e in events
                    if e.get("ph") == "M" and e["name"] == "thread_name"]
    assert any(n.startswith("partfit") for n in thread_names), \
        f"no partfit worker track names in {thread_names}"
    return {
        "spans": len(xs),
        "partition_spans": len(parts),
        "partition_tracks": len(tids),
        "thread_names": thread_names,
        "span_names": sorted({e["name"] for e in xs}),
    }


def run_partitioned_trace(n: int, trace_path: str) -> dict:
    from repro.core.executor import SCRBConfig, execute
    from repro.core.options import PartitionOptions, SolverOptions
    from repro.data.synthetic import make_blobs

    x, _ = make_blobs(n, 8, 4, seed=0)
    cfg = SCRBConfig(n_clusters=4, n_grids=64, sigma=1.5, d_g=512,
                     solver_options=SolverOptions(tol=1e-3),
                     kmeans_replicates=2, seed=0,
                     partition=PartitionOptions(n_partitions=3, workers=2),
                     trace=trace_path)
    res = execute(x, cfg)
    assert res.labels is not None
    with open(trace_path) as f:
        facts = validate_partitioned_trace(json.load(f))
    facts["trace_file"] = trace_path
    facts["trace_bytes"] = os.path.getsize(trace_path)
    print(f"[obs] partitioned trace: {facts['spans']} spans, "
          f"{facts['partition_spans']} partition fits on "
          f"{facts['partition_tracks']} worker tracks → {trace_path}")
    return facts


# --------------------------------------------------------------------------
# gates
# --------------------------------------------------------------------------

DISABLED_BUDGET_PCT = 1.0
ENABLED_BUDGET_PCT = 5.0


def gate(out: dict) -> list:
    failures = []
    ov = out["overhead"]
    if ov["disabled_overhead_pct"] > DISABLED_BUDGET_PCT:
        failures.append(
            f"observability-on-but-tracing-off fit is "
            f"{ov['disabled_overhead_pct']:.2f}% slower than the no-obs "
            f"baseline (budget {DISABLED_BUDGET_PCT}%) — the disabled span "
            f"path stopped being free")
    if ov["enabled_overhead_pct"] > ENABLED_BUDGET_PCT:
        failures.append(
            f"traced fit is {ov['enabled_overhead_pct']:.2f}% slower than "
            f"the no-obs baseline (budget {ENABLED_BUDGET_PCT}%) — span "
            f"recording/sync is on the hot path")
    return failures


def run(n: int, repeats: int, procs: int, trace_out: str) -> dict:
    out = {"n": n, "repeats": repeats, "procs": procs}
    leg_trace = trace_out + ".leg"
    legs = run_legs({"baseline": {"REPRO_OBS_DISABLED": "1"},
                     "default": {},
                     "traced": {"REPRO_TRACE": leg_trace}},
                    n, repeats, procs)
    with open(leg_trace) as f:                     # env-enabled path works:
        n_spans = len(json.load(f)["traceEvents"])  # atexit export happened
    os.remove(leg_trace)
    out["legs"] = legs
    base, default, traced = (legs[k] for k in ("baseline", "default",
                                               "traced"))
    out["overhead"] = {
        "baseline_s": base["fit_s"],
        "default_s": default["fit_s"],
        "traced_s": traced["fit_s"],
        "disabled_overhead_pct":
            100.0 * (default["fit_s"] / base["fit_s"] - 1.0),
        "enabled_overhead_pct":
            100.0 * (traced["fit_s"] / base["fit_s"] - 1.0),
        "traced_leg_events": n_spans,
    }
    ov = out["overhead"]
    print(f"[obs] overhead vs baseline: tracing-off "
          f"{ov['disabled_overhead_pct']:+.2f}% (budget "
          f"{DISABLED_BUDGET_PCT}%), tracing-on "
          f"{ov['enabled_overhead_pct']:+.2f}% (budget "
          f"{ENABLED_BUDGET_PCT}%)")
    out["partitioned_trace"] = run_partitioned_trace(n, trace_out)
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--run-child", action="store_true",
                    help=argparse.SUPPRESS)   # internal: one timed leg
    ap.add_argument("--n", type=int, default=4000)
    ap.add_argument("--repeats", type=int, default=2)
    ap.add_argument("--procs", type=int, default=3,
                    help="independent interpreters per leg (interleaved)")
    ap.add_argument("--out", default="bench_results/BENCH_PR10.json")
    ap.add_argument("--trace-out", default="bench_results/obs_trace.json")
    ap.add_argument("--gate", action="store_true",
                    help="exit non-zero when an overhead budget is blown")
    args = ap.parse_args()
    from repro.utils import use_compile_cache
    use_compile_cache()
    if args.run_child:
        _child(args.n, args.repeats)
        return
    res = run(args.n, args.repeats, args.procs, args.trace_out)
    failures = gate(res)
    res["gate_failures"] = failures
    if os.path.dirname(args.out):
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(res, f, indent=1)
    if args.gate:
        if failures:
            for msg in failures:
                print(f"[obs][GATE FAIL] {msg}", file=sys.stderr)
            sys.exit(1)
        print("[obs] gate passed: observability inside the overhead "
              "budgets, partitioned trace structurally valid")


if __name__ == "__main__":
    main()
