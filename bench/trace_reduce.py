"""Reduce a ``jax.profiler`` trace (``*.xplane.pb``) to the benchmark's
device numbers.

- busy: the union of the intervals in which an operation ran on a device
  (the ops line of each device plane), clipped to the window, averaged over
  the devices that ran anything;
- the window: the first host annotation of the given name (``fit``,
  ``serve``), or explicit (start, end) nanoseconds;
- device time per XLA module (the modules line), summed over the whole
  trace, so a probe's jitted wrapper is found by its name;
- the top device ops by total time in the window;
- the longest idle gaps of the first busy device in the window, each
  labelled by what the host was doing at its middle: the benchmark's own
  annotation there and the innermost host event under it.

On a TPU the device planes are ``/device:TPU:<n>`` with lines ``XLA Ops``
and ``XLA Modules``; the patterns are parameters so that a trace recorded on
the CPU (whose only plane is the host's) can stand in for tests.
"""
from __future__ import annotations

import glob
import os
import re
import shutil

TPU = {"device_plane": r"^/device:TPU:\d+$", "ops_line": r"^XLA Ops$",
       "modules_line": r"^XLA Modules$", "host_plane": r"^/host:CPU$",
       "skip": None}
LABELS = ("fit", "serve", "engine.step", "loadgen.wait", "dispatch_probe")


def fresh_dir(parent: str, name: str) -> str:
    """An empty directory at a fixed path under ``parent``."""
    path = os.path.join(parent, name)
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path)
    return path


def xplane_file(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str) -> list:
    """[(plane, line, [(name, start_ns, end_ns), ...]), ...]."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        for line in plane.lines:
            evs = [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                   for e in line.events]
            out.append((plane.name, line.name, evs))
    return out


def union(intervals) -> list:
    """Merged, sorted (start, end) intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _lines(trace, plane_re: str, line_re: str, skip=None):
    """Events of the matching lines, less those whose name matches
    ``skip`` (bookkeeping events where a host thread stands in for a
    device)."""
    return [(p, [ev for ev in evs if not (skip and re.search(skip, ev[0]))])
            for p, l, evs in trace
            if re.search(plane_re, p) and re.search(line_re, l)]


def annotation_window(trace, name: str, host_plane: str) -> tuple:
    for p, _l, evs in trace:
        if re.search(host_plane, p):
            for ev, s, e in evs:
                if ev == name:
                    return s, e
    raise ValueError(f"no host annotation {name!r} in the trace")


def module_seconds(trace, pattern: str, patterns: dict = TPU) -> float:
    """Device seconds of the XLA modules whose name contains ``pattern``,
    summed over the trace (and over devices)."""
    total = 0
    for _p, evs in _lines(trace, patterns["device_plane"],
                          patterns["modules_line"]):
        total += sum(e - s for name, s, e in evs if pattern in name)
    return total * 1e-9


def module_calls(trace, pattern: str, patterns: dict = TPU) -> int:
    return sum(sum(1 for name, _s, _e in evs if pattern in name)
               for _p, evs in _lines(trace, patterns["device_plane"],
                                     patterns["modules_line"]))


def _host_label(trace, t: int, host_plane: str, labels) -> str:
    """The benchmark's innermost annotation covering ``t`` and the innermost
    host event under it, as ``annotation/event``."""
    best_label, best_event = None, None
    for p, _l, evs in trace:
        if not re.search(host_plane, p):
            continue
        for name, s, e in evs:
            if not s <= t < e:
                continue
            if name in labels:
                if best_label is None or e - s < best_label[1]:
                    best_label = (name, e - s)
            elif best_event is None or e - s < best_event[1]:
                best_event = (name, e - s)
    parts = [x[0] for x in (best_label, best_event) if x is not None]
    return "/".join(parts) if parts else "host idle"


def reduce(path: str, *, window, patterns: dict = TPU,
           labels=LABELS, top: int = 10) -> dict:
    """The numbers a traced run reports; see the module docstring."""
    trace = load(path)
    if isinstance(window, str):
        lo, hi = annotation_window(trace, window, patterns["host_plane"])
    else:
        lo, hi = window
    per_device = {}
    op_time = {}
    for plane, evs in _lines(trace, patterns["device_plane"],
                             patterns["ops_line"], patterns.get("skip")):
        inside = [(n, max(s, lo), min(e, hi)) for n, s, e in evs
                  if min(e, hi) > max(s, lo)]
        per_device.setdefault(plane, []).extend((s, e) for _n, s, e in inside)
        for n, s, e in inside:
            op_time[n] = op_time.get(n, 0) + (e - s)
    busy = {p: union(iv) for p, iv in per_device.items() if iv}
    busy_ns = [sum(e - s for s, e in iv) for iv in busy.values()]
    gaps = []
    if busy:
        first = busy[sorted(busy)[0]]
        edges = [lo] + [t for iv in first for t in iv] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps.append((e - s, s))
    gaps.sort(reverse=True)
    idle = [[_host_label(trace, s + g // 2, patterns["host_plane"], labels),
             g * 1e-9] for g, s in gaps[:top]]
    ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    return {"busy_s": (sum(busy_ns) / len(busy_ns) if busy_ns else 0.0)
            * 1e-9,
            "window_s": (hi - lo) * 1e-9,
            "devices": len(busy),
            "trace": trace,
            "breakdown": {"device_ops": [[n, t * 1e-9] for n, t in ops],
                          "idle_gaps": idle}}
