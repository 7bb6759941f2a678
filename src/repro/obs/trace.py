"""Program spans on the profiler's clock.

``span(name, **attrs)`` is a ``jax.profiler.TraceAnnotation``. Whenever a
``jax.profiler`` session is live — an operator's own, a benchmark's, or one
started by the switches below — the span is recorded on the profiler's host
plane, on the same clock as the device planes, so every device lane can be
read against the program span the host was in. The profiler records the
thread of each span itself: a partitioned fit's workers show as lanes of
their own. Attributes become the event's metadata; ``set_metadata(**attrs)``
adds more before the span closes (the eigensolver's iteration count).

With no session live, ``span`` returns a shared no-op after one
``TraceAnnotation.is_enabled()`` check: nothing is recorded, no attribute is
encoded and no file is written. A span does not synchronise with the device:
device work shows on the device lanes, where it ran.

Switches, each starting a session into a directory with
``create_perfetto_trace=True`` (Perfetto opens the ``perfetto_trace.json.gz``
it writes beside the ``.xplane.pb``, device lanes included) unless a session
is already live, in which case spans record into that one. Their sessions
leave out the profiler's Python tracer, which records every Python call and
would grow a long run's trace without bound; the host plane keeps the
program's spans and JAX's own host events:

- ``tracing(dir)``: for one scope, reentrant (``SCRBConfig(trace=dir)``);
- ``start(dir)``: until the process exits (``EngineConfig(trace=dir)``);
- ``REPRO_TRACE=<dir>``: until the process exits, from the first fit or
  engine of the process.

``REPRO_OBS_DISABLED=1`` turns spans into no-ops and the switches off.
"""
from __future__ import annotations

import atexit
import contextlib
import os
from typing import Optional

import jax
from jax.profiler import TraceAnnotation

_DISABLED = os.environ.get("REPRO_OBS_DISABLED", "") not in ("", "0")
ENV_DIR: Optional[str] = os.environ.get("REPRO_TRACE") or None

# Whether a profiler session is recording spans now.
active = TraceAnnotation.is_enabled


class _NullSpan:
    """Shared no-op span: what ``span`` returns with no session live."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set_metadata(self, **attrs) -> None:
        pass


NULL_SPAN = _NullSpan()


def span(name: str, **attrs):
    """A ``TraceAnnotation`` named ``name`` with ``attrs`` as metadata while
    a profiler session is live; the shared no-op span otherwise."""
    if _DISABLED or not active():
        return NULL_SPAN
    return TraceAnnotation(name, **attrs)


def _start(path: str) -> bool:
    """Start a session into ``path`` unless one is live; whether it did."""
    if _DISABLED or active():
        return False
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(path, create_perfetto_trace=True,
                             profiler_options=options)
    return True


def _stop_at_exit() -> None:
    try:
        jax.profiler.stop_trace()
    except RuntimeError:        # already stopped by its owner
        pass


def start(path: Optional[str] = None) -> bool:
    """Start a session into ``path`` (default ``REPRO_TRACE``) that lasts
    until the process exits, unless one is live or no directory is given.
    Returns whether this call started it."""
    path = path or ENV_DIR
    if not path or not _start(path):
        return False
    atexit.register(_stop_at_exit)
    return True


@contextlib.contextmanager
def tracing(path: Optional[str]):
    """Record the scope into a session written to ``path`` on exit.

    ``path=None`` records nothing of its own (``REPRO_TRACE``, when set,
    starts its process-wide session here). With a session already live —
    an enclosing ``tracing``, ``REPRO_TRACE``, the engine's, a benchmark's
    — this is a no-op and the spans land in that session.
    """
    if path is None:
        start()
        yield
        return
    started = _start(path)
    try:
        yield
    finally:
        if started:
            jax.profiler.stop_trace()
