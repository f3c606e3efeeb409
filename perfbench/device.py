"""The chip a run measures: its stamp, its peaks and its compile count.

Copied from ``chip_smoke.py`` (device stamp, compile counter) so that the
yardstick does not move when the program's own scripts change.
"""

from __future__ import annotations

import collections
import json
import os

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def stamp(chips: int) -> dict:
    """Platform, kind and count of the devices JAX sees; raises
    :class:`NoChip` unless they are at least ``chips`` TPUs."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise NoChip(f"no TPU found (JAX platform {platform!r})")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found "
                     f"{len(devices)}")
    return {"platform": platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def peaks(kind: str) -> dict:
    """The published peaks of one chip of ``kind``; a kind that is not in
    ``peaks.json`` is an error, never a default."""
    with open(_PEAKS) as f:
        table = json.load(f)
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def memory_peak_bytes() -> int | None:
    """Peak bytes in use on the fullest device, where the backend says."""
    import jax

    peaks_seen = [d.memory_stats().get("peak_bytes_in_use")
                  for d in jax.local_devices() if d.memory_stats()]
    peaks_seen = [p for p in peaks_seen if p is not None]
    return max(peaks_seen) if peaks_seen else None


class CompileCounter:
    """Counts backend compiles via ``jax.monitoring``."""

    def __init__(self):
        import jax

        self.counts = collections.Counter()
        jax.monitoring.register_event_duration_secs_listener(
            lambda name, _dur, **_kw: self.counts.update([name]))

    @property
    def compiles(self) -> int:
        return self.counts["/jax/core/compile/backend_compile_duration"]
