"""Host-RSS and device-memory watermark sampling.

Replaces the scattered residency bookkeeping with one sampling surface:

- ``host_rss_bytes()``    — current resident set size (``/proc/self/statm``).
- ``host_peak_rss_bytes()`` — lifetime RSS high-water mark (``getrusage``).
- ``device_bytes_in_use()`` — live device allocation, when the backend
  exposes ``Device.memory_stats()`` (GPU/TPU; ``None`` on CPU).
- ``sample()``            — one dict with all of the above; what the
  executor folds into ``FitResult.diagnostics["memory"]``.
- ``Watermark``           — scoped peak-delta helper for tests/benchmarks.
"""
from __future__ import annotations

import os
import resource
from typing import Dict, Optional

_PAGE = os.sysconf("SC_PAGE_SIZE") if hasattr(os, "sysconf") else 4096


def host_rss_bytes() -> int:
    """Current host resident set size in bytes (0 if unreadable)."""
    try:
        with open("/proc/self/statm") as f:
            return int(f.read().split()[1]) * _PAGE
    except (OSError, IndexError, ValueError):
        return 0


def host_peak_rss_bytes() -> int:
    """Lifetime peak RSS in bytes (``ru_maxrss`` is KiB on Linux)."""
    try:
        return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
    except Exception:
        return 0


def device_memory_stats(device=None) -> Optional[Dict[str, int]]:
    """Raw ``memory_stats()`` of ``device`` (default: first jax device), or
    ``None`` when the backend doesn't report (CPU) or jax is unavailable."""
    try:
        import jax
        dev = device if device is not None else jax.devices()[0]
        stats = dev.memory_stats()
    except Exception:
        return None
    return stats or None


def device_bytes_in_use(device=None) -> Optional[int]:
    """Bytes currently allocated on ``device``, or ``None`` when the
    backend doesn't report (the CPU backend has no allocator stats)."""
    stats = device_memory_stats(device)
    if not stats:
        return None
    return stats.get("bytes_in_use")


def device_peak_bytes(device=None) -> Optional[int]:
    stats = device_memory_stats(device)
    if not stats:
        return None
    return stats.get("peak_bytes_in_use")


def sample() -> Dict[str, Optional[int]]:
    """One watermark sample: host RSS + peak, device in-use + peak."""
    return {
        "rss_bytes": host_rss_bytes(),
        "peak_rss_bytes": host_peak_rss_bytes(),
        "device_bytes_in_use": device_bytes_in_use(),
        "device_peak_bytes": device_peak_bytes(),
    }


class Watermark:
    """Scoped memory watermark: RSS/device deltas across a ``with`` block.

    ``peak_rss_delta_bytes`` uses the process-lifetime high-water mark, so
    it is an upper bound credited to the block (exact when the block is
    where the peak actually occurred, which is what the residency tests
    arrange).
    """

    __slots__ = ("start", "end")

    def __init__(self):
        self.start: Dict[str, Optional[int]] = {}
        self.end: Dict[str, Optional[int]] = {}

    def __enter__(self) -> "Watermark":
        self.start = sample()
        return self

    def __exit__(self, *exc) -> bool:
        self.end = sample()
        return False

    @property
    def rss_delta_bytes(self) -> int:
        return (self.end.get("rss_bytes") or 0) - (self.start.get("rss_bytes") or 0)

    @property
    def peak_rss_delta_bytes(self) -> int:
        return (self.end.get("peak_rss_bytes") or 0) - (self.start.get("peak_rss_bytes") or 0)

    @property
    def device_delta_bytes(self) -> Optional[int]:
        a, b = self.start.get("device_bytes_in_use"), self.end.get("device_bytes_in_use")
        if a is None or b is None:
            return None
        return b - a

    def as_dict(self) -> Dict[str, Optional[int]]:
        return {
            "rss_bytes": self.end.get("rss_bytes"),
            "peak_rss_bytes": self.end.get("peak_rss_bytes"),
            "rss_delta_bytes": self.rss_delta_bytes,
            "peak_rss_delta_bytes": self.peak_rss_delta_bytes,
            "device_bytes_in_use": self.end.get("device_bytes_in_use"),
            "device_peak_bytes": self.end.get("device_peak_bytes"),
            "device_delta_bytes": self.device_delta_bytes,
        }
