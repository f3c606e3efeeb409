"""Device seconds a job of collective instructions: the ``XLA Ops`` events
whose opcode is one of ``COLLECTIVES``, summed on each chip, averaged over
the chips and divided by the jobs completed in the window.

On a mesh the triangle-sharded candidate peels agree on each sub-round's
frontier prefix with a ``pmin`` and merge its decrements with a ``psum``;
both compile to all-reduces.  A run whose traced window holds no device
operation (no chip) or that completed no job reads nothing; a run on a
chip that issued no collective reads 0.
"""

from __future__ import annotations

from perfbench import spans, trace

COLLECTIVES = frozenset(
    op + suffix
    for op in ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")
    for suffix in ("", "-start", "-done"))
# each opcode above holds one of these; a four-chip window holds over a
# million events, and most skip the parse of their HLO text
_WORDS = ("all-", "collective-", "reduce-scatter")


def collective_ns(run):
    """Nanoseconds of collective instructions inside the traced window,
    averaged over the chips; None where the window holds no operation."""
    lo, hi = run.trace_window
    per_chip, ran = [], False
    for events in run.trace.ops.values():
        total = 0.0
        for ev in events:
            if ev.end_ns <= lo or ev.start_ns >= hi:
                continue
            ran = True
            name = ev.name
            if (any(w in name for w in _WORDS)
                    and trace.instruction(name)[1] in COLLECTIVES):
                total += min(ev.end_ns, hi) - max(ev.start_ns, lo)
        per_chip.append(total)
    return sum(per_chip) / len(per_chip) if ran else None


def read(run):
    if run.trace is None or run.trace_window is None:
        return None
    ns = collective_ns(run)
    return None if ns is None else spans.per_job(run, ns * 1e-9)
