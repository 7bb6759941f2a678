"""The benchmark's harness: finds a cell by name, runs it once, prints the
result line.

Everything that belongs to one cell, configuration, traffic mix or metric
sits in a file of its own, found by name:

  BENCHMARK.json               the cells, their end-to-end and per-layer
                               metrics (units, sources, which cells)
  bench/cells/<cell>.json      the cell's configuration, traffic mix, its
                               own load (rows, rate) and its check limits
  bench/configs/<config>.json  the deployment: data shape, R, solver, source
  bench/traffic/<mix>.json     the traffic mix: ``kind`` plus its parameters
  bench/traffic/<kind>.py      the generator of that kind: ``setup``,
                               ``window``, ``traced``, ``check``
  bench/metrics/<metric>.py    one per-layer metric: ``read(ctx)`` (and an
                               optional ``probe(ctx)`` run under the profiler)

A run: set up (data, warm-up), measure for ``--seconds`` (or, with
``--trace 1``, run one traced slice of the traffic and the metrics' probes
under ``jax.profiler``), read the peak device memory, free the program's
state, compare what the timed path produced with the plain reference, and
print one JSON line.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import gc
import importlib.util
import json
import os
import sys
import time
from typing import Any, Callable, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
OUT_DIR = os.path.join(BENCH_DIR, "out")


class BenchError(RuntimeError):
    """A run that cannot give a result: no chip, a missing file, a compile
    inside the window. The command exits non-zero and prints no result."""


def say(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def _load_json(path: str) -> dict:
    if not os.path.isfile(path):
        raise BenchError(f"missing {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import a benchmark file by path (metric names hold dots)."""
    if not os.path.isfile(path):
        raise BenchError(f"missing {os.path.relpath(path, ROOT)}")
    spec = importlib.util.spec_from_file_location(
        "bench_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One cell, resolved from its files."""

    name: str
    spec: dict            # bench/cells/<cell>.json
    config: dict          # bench/configs/<config>.json
    mix: dict             # bench/traffic/<mix>.json
    kind: Any             # bench/traffic/<kind>.py, imported
    end_to_end: list      # BENCHMARK.json metrics this cell reports
    per_layer: list
    readers: dict         # per-layer metric name → imported reader module
    chips: int


def _reports(metric: dict, cell: str, e2e_names: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", metric["name"]) in e2e_names


def find_cell(name: str, *, bench_dir: str = BENCH_DIR,
              benchmark: Optional[dict] = None) -> Cell:
    """Resolve a cell by name from ``BENCHMARK.json`` and its files."""
    if benchmark is None:
        benchmark = _load_json(os.path.join(os.path.dirname(bench_dir),
                                            "BENCHMARK.json"))
    entry = next((w for w in benchmark["workloads"] if w["name"] == name),
                 None)
    if entry is None:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    spec = _load_json(os.path.join(bench_dir, "cells", f"{name}.json"))
    for key in ("config", "traffic"):
        if spec[key] != entry[key]:
            raise BenchError(f"cell {name}: {key} {spec[key]!r} in its file, "
                             f"{entry[key]!r} in BENCHMARK.json")
    config = _load_json(os.path.join(bench_dir, "configs",
                                     f"{entry['config']}.json"))
    mix = _load_json(os.path.join(bench_dir, "traffic",
                                  f"{entry['traffic']}.json"))
    kind = load_module(os.path.join(bench_dir, "traffic", f"{mix['kind']}.py"),
                       mix["kind"])
    e2e = [m for m in benchmark["end_to_end"]
           if m["name"] == "setup_s" or _reports(m, name, set())]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in benchmark["per_layer"]
                 if _reports(m, name, e2e_names)]
    readers = {m["name"]: load_module(
        os.path.join(bench_dir, "metrics", f"{m['name']}.py"), m["name"])
        for m in per_layer}
    return Cell(name, spec, config, mix, kind, e2e, per_layer, readers,
                int(entry["chips"]))


class CompileCounter:
    """Counts XLA compilations that the persistent cache did not serve."""

    def __init__(self):
        self.events = collections.Counter()
        self._on = False

    def install(self, jax) -> None:
        def on_duration(event, _secs, **_):
            if self._on:
                self.events[event] += 1

        def on_event(event, **_):
            if self._on:
                self.events[event] += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    @contextlib.contextmanager
    def counting(self):
        self.events.clear()
        self._on = True
        try:
            yield self
        finally:
            self._on = False

    @property
    def compiles(self) -> int:
        return (self.events["/jax/core/compile/backend_compile_duration"]
                - self.events["/jax/compilation_cache/cache_hits"])


@dataclasses.dataclass
class Context:
    """What a traffic kind and a metric reader are handed."""

    cell: Cell
    seed: int
    jax: Any
    out_dir: str
    state: dict = dataclasses.field(default_factory=dict)
    trace: Optional[dict] = None      # trace_reduce output of a traced run
    probes: dict = dataclasses.field(default_factory=dict)
    reference_s: float = 0.0          # set-up time spent in the reference,
    # which is the check's and not counted in ``setup_s``

    @property
    def config(self) -> dict:
        return self.cell.config

    @property
    def spec(self) -> dict:
        return self.cell.spec

    @property
    def mix(self) -> dict:
        return self.cell.mix


def device_info(jax, chips: int, *, require_tpu: bool = True) -> dict:
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX's first device is "
                         f"{devs[0].platform!r}")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX sees "
                         f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak_bytes(jax, chips: int) -> int:
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in jax.devices()[:chips]]
    return max(peaks)


def use_compile_cache(jax) -> None:
    """``$JAX_COMPILATION_CACHE_DIR`` if set (JAX reads it), else the
    checkout's fixed ``.jax_cache/``; every program is cached, however fast
    it compiled, so that only a checkout's first run compiles."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def _checks_text(checks: dict) -> list:
    return [f"{name} {v['value']!r} limit {v['limit']!r} "
            f"({'ok' if v['ok'] else 'FAIL'})" for name, v in checks.items()]


def run(cell_name: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, bench_dir: str = BENCH_DIR,
        require_tpu: bool = True, compile_cache: bool = True,
        benchmark: Optional[dict] = None,
        emit: Callable[[str], None] = print) -> dict:
    """One run of one cell; returns (and emits) the result dict."""
    cell = find_cell(cell_name, bench_dir=bench_dir, benchmark=benchmark)
    import jax

    device = device_info(jax, cell.chips, require_tpu=require_tpu)
    if compile_cache:
        use_compile_cache(jax)
    counter = CompileCounter()
    counter.install(jax)
    out_dir = os.path.join(bench_dir, "out", cell_name)
    os.makedirs(out_dir, exist_ok=True)
    ctx = Context(cell, int(seed), jax, out_dir)
    say(f"[{cell_name}] seed {seed}, device {device}")
    cell.kind.setup(ctx)
    setup_s = time.perf_counter() - t_start - ctx.reference_s
    say(f"[{cell_name}] setup_s {setup_s:.4f} (reference "
        f"{ctx.reference_s:.4f} s apart)")
    if trace:
        result = cell.kind.traced(ctx)
    else:
        with counter.counting():
            result = cell.kind.window(ctx, seconds)
        if counter.compiles:
            raise BenchError(f"{counter.compiles} compilation(s) inside the "
                             f"measured window: the warm-up missed a shape")
    device["memory_peak_bytes"] = peak_bytes(jax, cell.chips)
    cell.kind.release(ctx)
    gc.collect()
    checks = cell.kind.check(ctx)
    correct = all(v["ok"] for v in checks.values())
    if trace:
        metrics = {}
        for m in cell.per_layer:
            value = cell.readers[m["name"]].read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = ctx.trace["busy_s"]
        device["window_s"] = ctx.trace["window_s"]
    else:
        values = dict(result["metrics"], setup_s=setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    out = {"correct": correct, "attempted": result["attempted"],
           "failed": result["failed"], "metrics": metrics, "device": device}
    if trace:
        out["breakdown"] = ctx.trace["breakdown"]
    out["checks"] = {k: {"value": v["value"], "limit": v["limit"]}
                     for k, v in checks.items()}
    for line in _checks_text(checks):
        say(f"[{cell_name}] check {line}")
    emit(json.dumps(out))
    return out
