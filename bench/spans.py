"""The program's own spans in a traced slice, read from its profiler trace.

``ClusterEngine.step`` records one ``engine.batch`` span per step, with the
attributes ``requests`` (the requests whose first rows the batch takes) and
``queue_wait_us`` (the sum of their waits from ``submit`` to the step's
start), holding ``engine.stage``, ``engine.h2d``, ``engine.dispatch`` and
``engine.readback``. They are host events on the profiler's clock, so they
can be laid over the device's busy intervals. ``load`` reads them, with
their attributes, from the slice's ``.xplane.pb``, keeping those inside the
slice's annotation (``serve``); the readers under ``metrics/`` summarise
them. A program without these spans gives no events, and every summary is
then ``None``.

Plane and line patterns are ``trace_reduce``'s, and the device's busy
intervals are computed as there, so a trace recorded on the CPU can stand in
for tests.
"""
from __future__ import annotations

import os
import re

import trace_reduce

NAMES = ("engine.batch", "engine.stage", "engine.h2d", "engine.dispatch",
         "engine.readback")


def device_busy(trace, lo: int, hi: int, patterns: dict) -> dict:
    """Per device plane, the merged intervals in which an operation ran,
    clipped to [lo, hi)."""
    per_device = {}
    for plane, evs in trace_reduce._lines(trace, patterns["device_plane"],
                                          patterns["ops_line"],
                                          patterns.get("skip")):
        per_device.setdefault(plane, []).extend(
            (max(s, lo), min(e, hi)) for _n, s, e in evs
            if min(e, hi) > max(s, lo))
    return {p: trace_reduce.union(iv) for p, iv in per_device.items() if iv}


def load(path: str, *, window: str = "serve",
         patterns: dict = trace_reduce.TPU, trace=None):
    """``{"window": (lo, hi), "events": {name: [(start, end, attrs)]},
    "busy": {device plane: intervals}}`` for the spans in ``NAMES`` that lie
    inside the first host annotation ``window``; ``None`` without that
    annotation. ``trace`` is ``trace_reduce.load(path)`` where the caller
    has it already."""
    from jax.profiler import ProfileData
    if trace is None:
        trace = trace_reduce.load(path)
    try:
        lo, hi = trace_reduce.annotation_window(trace, window,
                                                patterns["host_plane"])
    except ValueError:
        return None
    events = {name: [] for name in NAMES}
    for plane in ProfileData.from_file(path).planes:
        if not re.search(patterns["host_plane"], plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                found = events.get(ev.name)
                if found is None:
                    continue
                s = int(ev.start_ns)
                e = s + int(ev.duration_ns)
                if lo <= s and e <= hi:
                    found.append((s, e, dict(ev.stats)))
    for found in events.values():
        found.sort()
    return {"window": (lo, hi), "events": events,
            "busy": device_busy(trace, lo, hi, patterns)}


def of(ctx):
    """The traced serving slice's spans, loaded once per run; ``None`` in
    an untraced run."""
    if ctx.trace is None:
        return None
    if "spans" not in ctx.state:
        path = trace_reduce.xplane_file(os.path.join(ctx.out_dir, "trace"))
        ctx.state["spans"] = load(path, trace=ctx.trace["trace"])
    return ctx.state["spans"]


def mean_ms(sp, name: str):
    """Mean length of the ``name`` spans, in ms."""
    evs = sp["events"][name] if sp else []
    if not evs:
        return None
    return 1e-6 * sum(e - s for s, e, _ in evs) / len(evs)


def queue_wait_ms(sp):
    """Mean wait of a request for its first batch, in ms: Σ ``queue_wait_us``
    ÷ Σ ``requests`` over the ``engine.batch`` spans."""
    evs = sp["events"]["engine.batch"] if sp else []
    requests = sum(a["requests"] for _s, _e, a in evs)
    if not requests:
        return None
    return sum(a["queue_wait_us"] for _s, _e, a in evs) / requests / 1e3


def _overlap(a: list, b: list) -> int:
    """Length of the intersection of two merged interval lists."""
    total, i, j = 0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_engine_pct(sp):
    """Share of the window in which the device is idle while the host is
    inside an ``engine.batch`` span, averaged over the devices that ran
    anything (a trace with none counts one device, never busy)."""
    evs = sp["events"]["engine.batch"] if sp else []
    if not evs:
        return None
    lo, hi = sp["window"]
    engine = trace_reduce.union((s, e) for s, e, _ in evs)
    inside = sum(e - s for s, e in engine)
    busy = list(sp["busy"].values()) or [[]]
    idle = [inside - _overlap(engine, b) for b in busy]
    return 100.0 * sum(idle) / len(idle) / (hi - lo)
