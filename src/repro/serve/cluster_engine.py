"""Continuous-batching predict serving engine over fitted ``SCRBModel``s.

The LM engine next door (``serve/engine.py``) serves a fixed-shape decode
step from fixed slots; predict serving inverts the problem — the *model*
state is tiny (O(D·K)) and fixed, the *requests* are ragged. ``ClusterEngine``
therefore batches on rows, not slots:

- **Bucketed jit cache** — requests for one (model, mode) are coalesced and
  padded up to a small geometric bucket grid (``model.BUCKET_GRID``), so each
  (model, bucket, mode) triple is AOT-compiled exactly once
  (``jax.jit(...).lower(...).compile()``) into ``_cells``. All out-of-sample
  ops are row-local, so zero rows in the pad tail never contaminate real
  rows; outputs are sliced back per request and are bit-identical to direct
  ``model.predict`` (gated in ``benchmarks/serve_bench.py``).
- **Staging ring** — each bucket shape owns a small ring of reusable host
  staging buffers (``_StagingRing``); batches are assembled into a ring
  slot and shipped H2D once, so steady-state serving allocates no new host
  buffers per request. The ring's ``allocations`` counter is the bench's
  "steady-state allocations" gate. The device copy is not donated to the
  cell: no predict output has the (bucket, d) float32 shape of the batch,
  so XLA cannot reuse it and only warns.
- **Multi-model LRU** — many artifacts are registered by name
  (``load_model`` takes an npz path or a fitted model; re-loading a name is
  a hot-swap). Device-resident O(D·K) states live in an LRU
  (``max_resident_models`` / ``device_budget_bytes``); eviction drops device
  buffers but *keeps compiled cells* — they close over shapes only, state is
  passed as arguments, so a re-faulted model pays one H2D, zero recompiles.

The engine is synchronous and single-threaded by design: ``submit`` enqueues
and returns a ticket, ``step`` runs one coalesced device batch, ``drain``
runs until idle, ``take`` collects a finished ticket. ``serve/server.py``
puts a stdlib-HTTP front end (with a lock) over the same loop, and
``predict``/``transform`` are one-call sync wrappers — benchmarks, tests,
and the server all exercise the identical path.
"""
from __future__ import annotations

import collections
import dataclasses
import itertools
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import model as _model
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace

MODES = ("predict", "transform")

#: The per-model counters behind ``stats()`` — one ``engine_<key>_total``
#: counter per key on the engine's private registry.
STAT_KEYS = ("compiles", "cache_hits", "resident_hits", "resident_misses",
             "evictions", "rows_served", "batches", "padded_rows")


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Knobs for ``ClusterEngine``. Defaults suit the CI smoke mix."""

    buckets: Tuple[int, ...] = _model.BUCKET_GRID
    max_resident_models: int = 4          # LRU capacity (count)
    device_budget_bytes: Optional[int] = None   # LRU capacity (bytes)
    ring_slots: int = 2                   # staging buffers per bucket shape
    max_batch_rows: Optional[int] = None  # coalescing cap per device launch;
    # None → top bucket
    impl: Optional[str] = None            # kmeans_assign impl override
    trace: Optional[str] = None           # directory for a jax.profiler
    # trace started at engine construction and written at process exit:
    # each step's engine.batch span and its phases on the host plane beside
    # the device lanes. None starts none; REPRO_TRACE=<dir> is the env
    # equivalent, and a session already live is recorded into instead.

    def __post_init__(self):
        if tuple(sorted(self.buckets)) != tuple(self.buckets) or \
                len(self.buckets) == 0 or self.buckets[0] < 1:
            raise ValueError(f"buckets must be ascending and ≥1: {self.buckets}")


class _StagingRing:
    """Per-(rows, dim) ring of reusable host staging buffers.

    ``get`` hands out the least-recently-used buffer once ``slots`` exist for
    a shape; before that it allocates (counted — the bench gates that the
    steady-state delta is zero).
    """

    def __init__(self, slots: int):
        self.slots = max(1, int(slots))
        self._rings: Dict[Tuple[int, int], collections.deque] = {}
        self.allocations = 0

    def get(self, rows: int, dim: int) -> np.ndarray:
        ring = self._rings.get((rows, dim))
        if ring is None:        # fill the whole ring up front so steady
            ring = collections.deque(   # state is exactly zero allocations
                np.empty((rows, dim), np.float32)
                for _ in range(self.slots))
            self._rings[(rows, dim)] = ring
            self.allocations += self.slots
        buf = ring.popleft()
        ring.append(buf)
        return buf


@dataclasses.dataclass
class _Resident:
    """Device-side O(D·K) serving state for one model."""

    fm: Any
    dual: jax.Array
    proj: jax.Array
    cents: Optional[jax.Array]
    nbytes: int


@dataclasses.dataclass
class _Request:
    ticket: int
    model: str
    mode: str
    x: np.ndarray
    out: np.ndarray
    submitted_at: float
    cursor: int = 0               # rows already served (oversize requests
    completed_at: Optional[float] = None   # span several batches)


@dataclasses.dataclass
class Result:
    """A finished request: output rows + timing for latency accounting."""

    ticket: int
    model: str
    mode: str
    values: np.ndarray
    submitted_at: float
    completed_at: float

    @property
    def latency(self) -> float:
        return self.completed_at - self.submitted_at


class ClusterEngine:
    """Long-lived multi-model serving loop; see module docstring.

    Observability: every counter that used to live in a hand-rolled
    ``_model_stats`` dict now lives on a *per-engine*
    ``repro.obs.metrics.MetricsRegistry`` (``self.registry`` — private so
    concurrent engines, e.g. a test suite's, never cross-talk), alongside a
    per-(model, mode) request-latency histogram. ``stats()`` reconstructs
    the historical dict shape from the registry — same keys, same ints —
    plus ``latency_*`` and ``queue_wait_*`` p50/p99 in ms (the queue wait
    runs from ``submit`` to the step that takes a request's first rows);
    ``metrics_text()`` renders
    the registry (plus the process-global one) in Prometheus format for
    ``GET /metrics``.
    """

    def __init__(self, config: Optional[EngineConfig] = None):
        self.config = config or EngineConfig()
        self._models: Dict[str, _model.SCRBModel] = {}
        self._dims: Dict[str, int] = {}
        self._resident: "collections.OrderedDict[str, _Resident]" = \
            collections.OrderedDict()
        self._cells: Dict[Tuple[str, int, str], Any] = {}
        self._ring = _StagingRing(self.config.ring_slots)
        self._pending: "collections.deque[_Request]" = collections.deque()
        self._results: Dict[int, _Request] = {}
        self._tickets = itertools.count()
        self.registry = obs_metrics.MetricsRegistry()
        self._counters: Dict[str, obs_metrics.Counter] = {
            key: self.registry.counter(
                f"engine_{key}_total", f"Engine per-model {key} events.",
                ("model",))
            for key in STAT_KEYS}
        self._requests_total = self.registry.counter(
            "engine_requests_total", "Requests completed by the engine.",
            ("model", "mode"))
        self._latency_hist = self.registry.histogram(
            "engine_request_latency_seconds",
            "Per-request submit→complete latency.", ("model", "mode"))
        self._queue_wait_hist = self.registry.histogram(
            "engine_queue_wait_seconds",
            "Per-request submit→first batch wait.", ("model", "mode"))
        self._batch_rows_hist = self.registry.histogram(
            "engine_batch_rows", "Real rows per coalesced device batch.",
            ("model",), buckets=obs_metrics.log_buckets(1.0, 2 ** 20, 2))
        self.total_compiles = 0
        obs_trace.start(self.config.trace)

    def _bump(self, name: str, key: str, amount: int = 1) -> None:
        self._counters[key].inc(amount, model=name)

    # -- model registry / LRU ---------------------------------------------
    def load_model(self, name: str, source) -> _model.SCRBModel:
        """Register (or hot-swap) a model under ``name``.

        ``source`` is an npz artifact path (``SCRBModel.load``) or an
        already-fitted ``SCRBModel``. Re-using a name drops the old device
        state *and* its compiled cells — the new model's arrays may differ
        in shape, so its cells are rebuilt on first traffic (or ``warmup``).
        """
        mdl = source if isinstance(source, _model.SCRBModel) \
            else _model.SCRBModel.load(source)
        if name in self._models:            # hot-swap
            self._resident.pop(name, None)
            self._dims.pop(name, None)
            for key in [k for k in self._cells if k[0] == name]:
                del self._cells[key]
        self._models[name] = mdl
        for key in STAT_KEYS:       # materialize zeroed series so the model
            self._counters[key].inc(0, model=name)   # shows in /metrics now
        return mdl

    def _ensure_resident(self, name: str) -> _Resident:
        res = self._resident.get(name)
        if res is not None:
            self._bump(name, "resident_hits")
            self._resident.move_to_end(name)
            return res
        self._bump(name, "resident_misses")
        mdl = self._models[name]
        fm = jax.tree_util.tree_map(jnp.asarray, mdl.feature_map)
        dual = jnp.asarray(mdl.degree_dual)
        proj = jnp.asarray(mdl._projection)
        cents = None if mdl.centroids is None else jnp.asarray(mdl.centroids)
        nbytes = int(sum(leaf.nbytes for leaf in
                         jax.tree_util.tree_leaves((fm, dual, proj, cents))))
        res = _Resident(fm, dual, proj, cents, nbytes)
        self._resident[name] = res
        self._evict()
        return res

    def _evict(self) -> None:
        """Pop least-recently-used device states until under budget; the
        newest entry always stays (serving it is the point)."""
        cfg = self.config

        def over() -> bool:
            if len(self._resident) > cfg.max_resident_models:
                return True
            if cfg.device_budget_bytes is None:
                return False
            return sum(r.nbytes for r in self._resident.values()) \
                > cfg.device_budget_bytes

        while len(self._resident) > 1 and over():
            victim, _ = self._resident.popitem(last=False)
            self._bump(victim, "evictions")

    # -- bucketed AOT jit cache -------------------------------------------
    def _cell(self, name: str, bucket: int, mode: str, res: _Resident,
              dim: int):
        key = (name, bucket, mode)
        cell = self._cells.get(key)
        if cell is not None:
            self._bump(name, "cache_hits")
            return cell
        mdl = self._models[name]
        xs = jax.ShapeDtypeStruct((bucket, dim), jnp.float32)
        if mode == "predict":
            fn = jax.jit(_model._oos_predict_impl,
                         static_argnames=("laplacian", "impl"))
            cell = fn.lower(res.fm, res.dual, res.proj, res.cents, xs,
                            laplacian=mdl.laplacian_normalize,
                            impl=self.config.impl or mdl.config.impl).compile()
        else:
            fn = jax.jit(_model._oos_embed_impl,
                         static_argnames=("laplacian",))
            cell = fn.lower(res.fm, res.dual, res.proj, xs,
                            laplacian=mdl.laplacian_normalize).compile()
        self._cells[key] = cell
        self._bump(name, "compiles")
        self.total_compiles += 1
        return cell

    def warmup(self, name: str, *, dim: Optional[int] = None,
               modes: Tuple[str, ...] = ("predict",)) -> int:
        """Precompile every bucket cell for ``name`` so first-request latency
        is pure execution. Returns the number of cells compiled now."""
        mdl = self._models[name]
        dim = dim or mdl.data_dim or self._dims.get(name)
        if dim is None:
            raise ValueError(
                f"cannot infer data_dim for {name!r}; pass warmup(dim=...)")
        res = self._ensure_resident(name)
        before = self.total_compiles
        for mode in modes:
            if mode == "predict" and mdl.centroids is None:
                continue
            for bucket in self.config.buckets:
                self._cell(name, bucket, mode, res, dim)
                self._ring.get(bucket, dim)     # pre-fill staging rings too
        return self.total_compiles - before

    # -- request loop ------------------------------------------------------
    def submit(self, name: str, x, mode: str = "predict") -> int:
        """Enqueue rows for ``name``; returns a ticket for ``take``."""
        if name not in self._models:
            raise KeyError(f"unknown model {name!r}; load_model() first")
        if mode not in MODES:
            raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
        mdl = self._models[name]
        if mode == "predict" and mdl.centroids is None:
            raise ValueError(f"model {name!r} has no centroids; "
                             "use mode='transform'")
        x = np.ascontiguousarray(np.asarray(x, np.float32))
        if x.ndim != 2:
            raise ValueError(f"expected (n, d) rows, got shape {x.shape}")
        expect = mdl.data_dim or self._dims.get(name)
        if expect is not None and x.shape[1] != expect:
            raise ValueError(f"model {name!r} expects {expect}-d rows, "
                             f"got {x.shape[1]}-d")
        self._dims.setdefault(name, x.shape[1])
        k = mdl.right_vectors.shape[1]
        out = np.empty((x.shape[0],), np.int32) if mode == "predict" \
            else np.empty((x.shape[0], k), np.float32)
        req = _Request(ticket=next(self._tickets), model=name, mode=mode,
                       x=x, out=out, submitted_at=time.perf_counter())
        if x.shape[0] == 0:                 # nothing to do on device
            req.completed_at = req.submitted_at
            self._results[req.ticket] = req
            self._requests_total.inc(model=name, mode=mode)
        else:
            self._pending.append(req)
        return req.ticket

    def step(self) -> int:
        """Serve one coalesced device batch for the oldest pending
        (model, mode) group; returns rows served (0 when idle).

        Under a profiler session the step is one ``engine.batch`` span
        (``requests``: the requests whose first rows it takes;
        ``queue_wait_us``: the sum of their waits from ``submit`` to the
        step's start) holding ``engine.stage`` (copy into the staging
        buffer), ``engine.h2d`` (``device_put``), ``engine.dispatch`` (the
        cell call) and ``engine.readback`` (the blocking read of the
        output). Each request's wait also feeds
        ``engine_queue_wait_seconds``.
        """
        if not self._pending:
            return 0
        started_at = time.perf_counter()
        head = self._pending[0]
        name, mode = head.model, head.mode
        with obs_trace.span("engine.batch", model=name, mode=mode) as batch:
            cap = self.config.max_batch_rows or self.config.buckets[-1]
            take: List[Tuple[_Request, int]] = []
            total = 0
            for req in self._pending:
                if req.model != name or req.mode != mode:
                    continue
                if total >= cap:
                    break
                n = min(req.x.shape[0] - req.cursor, cap - total)
                take.append((req, n))
                total += n
            bucket = _model.round_to_bucket(total, self.config.buckets)
            dim = take[0][0].x.shape[1]
            waits = [started_at - req.submitted_at for req, _ in take
                     if req.cursor == 0]
            for wait in waits:
                self._queue_wait_hist.observe(wait, model=name, mode=mode)
            batch.set_metadata(bucket=bucket, rows=total,
                               requests=len(waits),
                               queue_wait_us=1e6 * sum(waits))
            res = self._ensure_resident(name)
            cell = self._cell(name, bucket, mode, res, dim)
            with obs_trace.span("engine.stage"):
                buf = self._ring.get(bucket, dim)
                off = 0
                for req, n in take:
                    buf[off:off + n] = req.x[req.cursor:req.cursor + n]
                    off += n
                buf[off:] = 0.0     # mask: pad rows are zeros, sliced off
            with obs_trace.span("engine.h2d"):
                xdev = jax.device_put(buf)
            with obs_trace.span("engine.dispatch"):
                if mode == "predict":
                    out = cell(res.fm, res.dual, res.proj, res.cents, xdev)
                else:
                    out = cell(res.fm, res.dual, res.proj, xdev)
            with obs_trace.span("engine.readback"):
                out = np.asarray(out)       # blocks on the device result
            done_at = time.perf_counter()
            off = 0
            for req, n in take:
                req.out[req.cursor:req.cursor + n] = out[off:off + n]
                req.cursor += n
                off += n
                if req.cursor == req.x.shape[0]:
                    req.completed_at = done_at
                    self._results[req.ticket] = req
                    self._pending.remove(req)
                    self._requests_total.inc(model=name, mode=mode)
                    self._latency_hist.observe(done_at - req.submitted_at,
                                               model=name, mode=mode)
            self._bump(name, "rows_served", total)
            self._bump(name, "batches")
            self._bump(name, "padded_rows", bucket - total)
            self._batch_rows_hist.observe(total, model=name)
        return total

    def drain(self) -> int:
        """Run ``step`` until the queue is empty; returns rows served."""
        total = 0
        while self._pending:
            total += self.step()
        return total

    def take(self, ticket: int) -> Result:
        """Collect a finished ticket (once); KeyError if unknown/unfinished."""
        req = self._results.pop(ticket, None)
        if req is None:
            raise KeyError(f"ticket {ticket} is not finished (or was already "
                           "taken); call step()/drain() first")
        return Result(ticket=req.ticket, model=req.model, mode=req.mode,
                      values=req.out, submitted_at=req.submitted_at,
                      completed_at=req.completed_at)

    # -- sync convenience --------------------------------------------------
    def predict(self, name: str, x) -> np.ndarray:
        t = self.submit(name, x, "predict")
        self.drain()
        return self.take(t).values

    def transform(self, name: str, x) -> np.ndarray:
        t = self.submit(name, x, "transform")
        self.drain()
        return self.take(t).values

    # -- introspection -----------------------------------------------------
    @property
    def models(self) -> Tuple[str, ...]:
        return tuple(self._models)

    @property
    def resident_models(self) -> Tuple[str, ...]:
        return tuple(self._resident)

    def _model_stat_dict(self, name: str) -> Dict[str, int]:
        """One model's historical 8-key stats dict, reconstructed from the
        registry counters (same keys, same ints as the pre-registry dicts)."""
        if name not in self._models:
            raise KeyError(name)
        return {key: int(self._counters[key].get(model=name))
                for key in STAT_KEYS}

    def latency_quantiles(self, name: str, mode: str = "predict",
                          *, qs: Tuple[float, ...] = (0.5, 0.99)
                          ) -> Dict[float, Optional[float]]:
        """Per-request latency quantiles (seconds) for one (model, mode)
        from the engine's own log-bucketed histogram; values are ``None``
        until that series has traffic."""
        return {q: self._latency_hist.quantile(q, model=name, mode=mode)
                for q in qs}

    def stats(self, name: Optional[str] = None) -> Dict[str, Any]:
        if name is not None:
            return self._model_stat_dict(name)
        per = {}
        for m in self._models:
            d = self._model_stat_dict(m)
            for mode in MODES:
                for key, hist in (("latency", self._latency_hist),
                                  ("queue_wait", self._queue_wait_hist)):
                    p50 = hist.quantile(0.5, model=m, mode=mode)
                    p99 = hist.quantile(0.99, model=m, mode=mode)
                    if p50 is not None:
                        d[f"{key}_{mode}_p50_ms"] = p50 * 1e3
                        d[f"{key}_{mode}_p99_ms"] = p99 * 1e3
            per[m] = d
        return {
            "models": per,
            "total_compiles": self.total_compiles,
            "cells": len(self._cells),
            "resident": list(self._resident),
            "resident_bytes": sum(r.nbytes for r in self._resident.values()),
            "staging_allocations": self._ring.allocations,
            "pending": len(self._pending),
            "rows_served": sum(s["rows_served"] for s in per.values()),
            "batches": sum(s["batches"] for s in per.values()),
            "padded_rows": sum(s["padded_rows"] for s in per.values()),
            "evictions": sum(s["evictions"] for s in per.values()),
        }

    def metrics_text(self) -> str:
        """Prometheus text exposition: this engine's registry plus the
        process-global one (fit/prefetch/solver series) — the body served
        by ``GET /metrics``."""
        self.registry.gauge(
            "engine_resident_models",
            "Models with device-resident state.").set(len(self._resident))
        self.registry.gauge(
            "engine_resident_bytes",
            "Bytes of device-resident model state.").set(
            sum(r.nbytes for r in self._resident.values()))
        self.registry.gauge(
            "engine_pending_requests", "Queued unfinished requests.").set(
            len(self._pending))
        return obs_metrics.render_prometheus(
            [self.registry, obs_metrics.REGISTRY])
