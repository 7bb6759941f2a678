"""Shared small utilities: stage timers, rng plumbing, host-to-device
prefetch, meshes and the compile cache."""
from __future__ import annotations

import contextlib
import os
import time
import warnings
from typing import Any, Callable, Dict, Iterator

import jax

from repro.obs import metrics as _metrics
from repro.obs import trace as _trace

_STAGE_SECONDS = _metrics.REGISTRY.histogram(
    "repro_stage_seconds", "Pipeline stage wall-clock seconds.", ("stage",))


class StageTimer:
    """Wall-clock per-stage timer used by the SC_RB pipeline and benchmarks.

    Records {stage: seconds}; ``block_until_ready`` is applied to jax outputs
    so timings are honest under async dispatch.

    Since the observability subsystem landed this is a compatibility shim:
    each ``stage`` additionally opens a ``repro.obs.trace`` span (recorded
    while a profiler session is live) and feeds the ``repro_stage_seconds``
    histogram, but ``self.times`` is still populated from the timer's own
    ``perf_counter`` pair so the `{stage: seconds}` contract — and
    ``FitResult.timings`` built on it — is preserved bit-for-bit.
    """

    def __init__(self) -> None:
        self.times: Dict[str, float] = {}

    @contextlib.contextmanager
    def stage(self, name: str) -> Iterator[None]:
        with _trace.span(name):
            t0 = time.perf_counter()
            yield
            dt = time.perf_counter() - t0
        self.times[name] = self.times.get(name, 0.0) + dt
        _STAGE_SECONDS.observe(dt, stage=name)

    def timed(self, name: str, fn: Callable, *args, **kwargs):
        with self.stage(name):
            out = fn(*args, **kwargs)
            out = jax.block_until_ready(out)
        return out

    @property
    def total(self) -> float:
        return sum(self.times.values())

    def __repr__(self) -> str:
        inner = ", ".join(f"{k}={v:.3f}s" for k, v in self.times.items())
        return f"StageTimer({inner}, total={self.total:.3f}s)"


def fold_key(key: jax.Array, *names: str) -> jax.Array:
    """Deterministically derive a subkey from string tags (stable across hosts)."""
    for name in names:
        h = 2166136261
        for ch in name.encode():
            h = ((h ^ ch) * 16777619) & 0xFFFFFFFF
        key = jax.random.fold_in(key, int(h))
    return key


_PREFETCH_ITEMS = _metrics.REGISTRY.counter(
    "repro_prefetch_items_total", "Host pytrees uploaded by prefetch_to_device.")
_PREFETCH_BYTES = _metrics.REGISTRY.counter(
    "repro_prefetch_bytes_total", "Bytes uploaded by prefetch_to_device.")


def prefetch_to_device(
    items: Any, *, enabled: bool = True,
    stats: "Dict[str, int] | None" = None,
    measure: "Dict[str, int] | None" = None,
) -> Iterator[Any]:
    """Double-buffered H2D upload of an iterable of host pytrees.

    Yields each item with its leaves moved to device via ``jax.device_put``.
    With ``enabled=True`` the transfer for item ``i+1`` is *issued before*
    item ``i`` is handed to the consumer, so (on accelerators with async
    transfer engines) the upload of the next chunk overlaps the compute on
    the current one — note this keeps up to *two* chunks in flight, so
    worst-case instantaneous residency is 2× one chunk. ``enabled=False``
    uploads lazily at consume time — same values, same accumulation order,
    so results are bitwise identical either way; only the transfer/compute
    overlap changes.

    ``measure`` (optional dict) is updated in place with the *measured*
    upload sizes — ``max_item_bytes`` (largest single pytree uploaded) and
    ``items`` — so residency diagnostics can report what was actually
    streamed rather than a closed-form estimate. Every upload also feeds
    the process metrics registry (``repro_prefetch_items_total`` /
    ``repro_prefetch_bytes_total``, scrapable at ``GET /metrics``) and,
    under a profiler session, an ``h2d`` span per item (it covers the
    *issue*; the copy itself shows on the device lanes).

    .. deprecated:: the ``stats=`` keyword is the pre-observability name of
       ``measure=`` and now emits a ``DeprecationWarning``; it behaves
       identically.

    Shared by every chunk sweep in the streaming pipeline: the degree pass,
    the blocked Gram mat-vecs inside the LOBPCG loop, and the streaming
    k-means sweeps.
    """
    if stats is not None:
        warnings.warn(
            "prefetch_to_device(stats=...) is deprecated; use measure=... "
            "(same dict contract). Totals are also on the metrics registry "
            "as repro_prefetch_{items,bytes}_total.",
            DeprecationWarning, stacklevel=2)
        if measure is None:
            measure = stats

    def put(t):
        # prefetched items may carry scalar leaves (chunk indices)
        # alongside the arrays
        nbytes = sum(int(getattr(leaf, "nbytes", 0))
                     for leaf in jax.tree_util.tree_leaves(t))
        if measure is not None:
            measure["max_item_bytes"] = max(measure.get("max_item_bytes", 0),
                                            nbytes)
            measure["items"] = measure.get("items", 0) + 1
        _PREFETCH_ITEMS.inc()
        _PREFETCH_BYTES.inc(nbytes)
        with _trace.span("h2d", bytes=nbytes):
            return jax.tree_util.tree_map(jax.device_put, t)

    it = iter(items)
    if not enabled:
        for item in it:
            yield put(item)
        return
    try:
        cur = put(next(it))
    except StopIteration:
        return
    for item in it:
        nxt = put(item)     # issue H2D for i+1 before the consumer sees i
        yield cur
        cur = nxt
    yield cur


def make_auto_mesh(shape, axes) -> "jax.sharding.Mesh":
    """``jax.make_mesh`` with every axis ``Auto``: GSPMD propagates the
    shardings, and the SPMD pipeline names its collectives explicitly in
    ``shard_map``."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def use_compile_cache() -> None:
    """Persistent compilation cache for an entry point (never set at
    import): ``$JAX_COMPILATION_CACHE_DIR`` when it is set — JAX reads it
    itself — else ``.jax_cache/`` at the root of this checkout. The path is
    part of the cache key, so it is never built from a temp name, a PID or
    the clock."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))))
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(root, ".jax_cache"))
