"""Reduction of a profiler trace (``.xplane.pb``) to device numbers.

Layout of a TPU trace as JAX 0.9 writes it: each chip is a plane named
``/device:TPU:<i>``.  Its line ``XLA Ops`` holds one event per executed HLO
instruction, named by the instruction's HLO text (``%fusion.55 = s32[...]
fusion(...), ...``); control-flow instructions (``while``, ``conditional``)
span the instructions they run, so the line nests.  Its line ``XLA
Modules`` holds one event per program execution, named
``jit_<function>(<fingerprint>)``.  Host threads are lines of the plane
``/host:CPU``; ``jax.profiler.TraceAnnotation`` spans land on its
``python`` line.  All event times are nanoseconds from the start of the
profile.

Busy time is the union of the ``XLA Ops`` intervals, averaged over the
chips; the idle share is one minus busy over the traced window.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
WINDOW_SPAN = "perfbench.window"
# instructions that only contain others; left out of the per-op ranking
CONTAINERS = frozenset({"while", "conditional", "call"})

_OPCODE = re.compile(r"[\]\})] ([a-z][a-z0-9\-]*)\(")
_MODULE = re.compile(r"^(.*?)(\(\d+\))?$")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    end_ns: float


def instruction(hlo_text: str) -> tuple[str, str]:
    """(instruction name, opcode) of an ``XLA Ops`` event name."""
    head = hlo_text.split(" = ", 1)
    name = head[0].lstrip("%")
    m = _OPCODE.search(head[1]) if len(head) == 2 else None
    return name, (m.group(1) if m else "")


def module_name(event_name: str) -> str:
    """``jit_f(123)`` -> ``jit_f``."""
    return _MODULE.match(event_name).group(1)


def merged(intervals) -> list[tuple[float, float]]:
    """The union of intervals as sorted disjoint (start, end) pairs."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def union_ns(intervals) -> float:
    """Total length of the union of (start, end) intervals."""
    return sum(e - s for s, e in merged(intervals))


def _clip(events, window) -> list[tuple[float, float]]:
    lo, hi = window
    return [(max(e.start_ns, lo), min(e.end_ns, hi)) for e in events
            if e.end_ns > lo and e.start_ns < hi]


@dataclasses.dataclass
class Trace:
    """The events of one trace that the reduction reads."""

    ops: dict            # device plane name -> [Event] of its XLA Ops line
    modules: dict        # device plane name -> [Event] of its XLA Modules line
    host_spans: list     # [Event] on the host's python line
    profile_start_ns: int | None   # wall-clock ns of the profile's start

    def window(self, name: str = WINDOW_SPAN):
        """(start, end) of the first host span called ``name``, or None."""
        for ev in self.host_spans:
            if ev.name == name:
                return ev.start_ns, ev.end_ns
        return None

    def busy_s(self, window) -> float | None:
        """Seconds in which an operation ran, averaged over the chips;
        None where the trace holds no device operation in the window."""
        per_chip = [union_ns(_clip(evs, window))
                    for evs in self.ops.values()]
        if not per_chip or not any(per_chip):
            return None
        return sum(per_chip) / len(per_chip) * 1e-9

    def module_s(self, function: str, window) -> float:
        """Device seconds of executions of the jitted ``function``
        (``XLA Modules`` events named ``jit_<function>``), summed over
        the chips."""
        want = "jit_" + function
        return sum(e - s for evs in self.modules.values()
                   for ev in evs if module_name(ev.name) == want
                   for s, e in _clip([ev], window)) * 1e-9

    def op_events(self, window):
        """Every device op event inside the window, over all chips."""
        lo, hi = window
        return [ev for evs in self.ops.values() for ev in evs
                if ev.start_ns >= lo and ev.end_ns <= hi]

    def top_ops(self, window, k: int = 10) -> list:
        """The ``k`` instructions with the most device time, as
        ``[module:instruction (opcode), seconds]``; containers left out."""
        total = collections.Counter()
        for plane, evs in self.ops.items():
            mods = sorted(self.modules.get(plane, []),
                          key=lambda ev: ev.start_ns)
            starts = [ev.start_ns for ev in mods]
            for ev in evs:
                if not (ev.start_ns >= window[0] and ev.end_ns <= window[1]):
                    continue
                name, opcode = instruction(ev.name)
                if opcode in CONTAINERS:
                    continue
                i = bisect.bisect_right(starts, ev.start_ns) - 1
                mod = (module_name(mods[i].name)
                       if i >= 0 and mods[i].end_ns >= ev.end_ns else "?")
                total[f"{mod}:{name} ({opcode})"] += ev.end_ns - ev.start_ns
        return [[n, ns * 1e-9] for n, ns in total.most_common(k)]

    def idle_gaps(self, window, label_of, k: int = 10) -> list:
        """The ``k`` longest stretches of the window in which no chip ran
        an operation, as ``[what the host did, seconds]``; ``label_of(lo,
        hi)`` names the host's work between two trace times."""
        busy = merged(s for evs in self.ops.values()
                      for s in _clip(evs, window))
        gaps = []
        cur = window[0]
        for s, e in busy:
            if s > cur:
                gaps.append((cur, s))
            cur = max(cur, e)
        if window[1] > cur:
            gaps.append((cur, window[1]))
        gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
        return [[label_of(lo, hi), (hi - lo) * 1e-9] for lo, hi in gaps[:k]]


def load(path: str) -> Trace:
    """Read the events the reduction needs from an ``.xplane.pb`` file."""
    with open(path, "rb") as f:
        return from_bytes(f.read())


def from_bytes(xspace: bytes) -> Trace:
    """The events of a serialized XSpace (an ``.xplane.pb``'s bytes)."""
    from jax.profiler import ProfileData

    return from_profile(ProfileData.from_serialized_xspace(xspace))


def from_profile(data) -> Trace:
    ops, modules, host, start = {}, {}, [], None
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[plane.name] = [Event(e.name, e.start_ns, e.end_ns)
                                       for e in line.events]
                elif line.name == MODULES_LINE:
                    modules[plane.name] = [
                        Event(e.name, e.start_ns, e.end_ns)
                        for e in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                if line.name == "python":
                    host = [Event(e.name, e.start_ns, e.end_ns)
                            for e in line.events]
        elif plane.name == "Task Environment":
            for key, value in plane.stats:
                if key == "profile_start_time":
                    start = int(value)
    return Trace(ops=ops, modules=modules, host_spans=host,
                 profile_start_ns=start)
