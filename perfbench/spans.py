"""The program's own host spans in a traced window, and the sums that the
span metrics read.

The program opens ``truss.<name>`` spans (``jax.profiler.TraceAnnotation``,
listed in ``src/repro/core/spans.py``) at its layer boundaries.  They land
on the host's ``python`` line, on the clock the device planes are aligned
to, and nest on the thread that opened them: a span's parent is the
innermost span that contains it.  A span's self time is its duration less
the time its direct children cover.  A program that opens no such span,
and a run whose trace holds no device operation (a run without a chip,
whose host is not the chip's host), give every reader here nothing to
read.
"""

from __future__ import annotations

import collections

PREFIX = "truss."
JOB = PREFIX + "job"
WAIT = PREFIX + "device_wait"


def on_chip(run) -> bool:
    """Whether the run's traced window holds a device operation."""
    return (run.trace is not None and run.trace_window is not None
            and run.trace.busy_s(run.trace_window) is not None)


def in_window(run) -> list:
    """The ``truss.*`` spans wholly inside the traced window, sorted so
    that a parent comes before its children; empty where the run was not
    traced on a chip or the program opened none."""
    if not on_chip(run):
        return []
    lo, hi = run.trace_window
    return sorted((ev for ev in run.trace.host_spans
                   if ev.name.startswith(PREFIX)
                   and ev.start_ns >= lo and ev.end_ns <= hi),
                  key=lambda ev: (ev.start_ns, -ev.end_ns))


def _parents(spans: list) -> list:
    """Index of each span's parent in ``spans`` (sorted as ``in_window``
    sorts), or -1."""
    out, stack = [], []
    for i, ev in enumerate(spans):
        while stack and spans[stack[-1]].end_ns <= ev.start_ns:
            stack.pop()
        out.append(stack[-1] if stack else -1)
        stack.append(i)
    return out


def self_ns(spans: list) -> list:
    """Each span's duration less what its direct children cover."""
    own = [ev.end_ns - ev.start_ns for ev in spans]
    for i, p in enumerate(_parents(spans)):
        if p >= 0:
            own[p] -= spans[i].end_ns - spans[i].start_ns
    return own


def per_job(run, seconds: float):
    """``seconds`` over the jobs the window completed, or None."""
    done = len(run.completed)
    return seconds / done if done else None


def self_seconds_per_job(run, names) -> float | None:
    """Self time a job of the spans called ``names`` (full names); None
    where the window holds none of them."""
    spans = in_window(run)
    own = [ns for ev, ns in zip(spans, self_ns(spans)) if ev.name in names]
    return per_job(run, sum(own) * 1e-9) if own else None


def innermost(spans: list, window) -> list:
    """The window cut where the innermost open span changes, as sorted
    ``(start, end, name)`` pieces; ``name`` is None outside every span."""
    lo, hi = window
    pieces, stack, cur = [], [], lo

    def cut(to):
        nonlocal cur
        if to > cur:
            pieces.append((cur, to, spans[stack[-1]].name if stack else None))
            cur = to

    for i, ev in enumerate(spans):
        while stack and spans[stack[-1]].end_ns <= ev.start_ns:
            cut(spans[stack[-1]].end_ns)
            stack.pop()
        cut(ev.start_ns)
        stack.append(i)
    while stack:
        cut(spans[stack[-1]].end_ns)
        stack.pop()
    cut(hi)
    return pieces


def overlap_by_name(pieces: list, gaps: list) -> dict:
    """Nanoseconds of the sorted disjoint ``gaps`` under each piece's
    name."""
    out = collections.Counter()
    j = 0
    for lo, hi in gaps:
        while j < len(pieces) and pieces[j][1] <= lo:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < hi:
            s, e, name = pieces[k]
            out[name] += min(e, hi) - max(s, lo)
            k += 1
    return out
