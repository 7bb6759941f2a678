"""Observability subsystem: structured tracing, metrics, memory watermarks.

Three pillars, wired through every layer of the SC_RB stack:

``repro.obs.trace``
    Program spans as ``jax.profiler.TraceAnnotation``s with attributes:
    recorded on the profiler's host plane, on the device lanes' clock,
    whenever a profiler session is live, and a no-op otherwise.
    ``SCRBConfig(trace=<dir>)``, ``EngineConfig(trace=<dir>)`` and
    ``REPRO_TRACE=<dir>`` start a session into a directory (Perfetto opens
    the result).

``repro.obs.metrics``
    Process-wide registry (``repro.obs.metrics.REGISTRY``) of labeled
    counters, gauges, and log-bucketed histograms (p50/p90/p99 estimated
    from buckets — no sample storage), with ``snapshot``/``reset`` for
    tests and a Prometheus text-exposition encoder (served by
    ``serve.server`` at ``GET /metrics``). Always on: recording a metric is
    a dict update under a lock, nanoseconds next to any device work.

``repro.obs.memory``
    Device-memory and host-RSS watermark sampling; the executor records a
    fit's watermark in ``FitResult.diagnostics["memory"]``.

Kill switch: ``REPRO_OBS_DISABLED=1`` disables spans and metrics at import
time (spans become no-ops, instruments stop recording).
"""
from repro.obs import memory, metrics, trace

__all__ = ["memory", "metrics", "trace"]
