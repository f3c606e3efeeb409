"""Share of the traced window, in %, in which no device operation ran and
the host was inside one of the program's ``truss.*`` spans other than
``truss.job`` and ``truss.device_wait``: the device idle for host work
the program names.

The idle stretches are those ``device_idle_share`` counts (no chip runs an
operation), each cut by the innermost span open on the host.  The value is
a part of ``device_idle_share``; the rest is in the extra keys, seconds a
completed job: ``by_span`` (the value's seconds by innermost span),
``job_glue_s`` (inside ``truss.job`` and no child), ``wait_idle_s``
(inside ``truss.device_wait``: the device idle while the host waits on it,
the copy back and the profiler's own cost) and ``outside_s`` (outside
every job: the benchmark's own time between jobs).  ``job_covered`` is the
share of ``truss.job`` time, in %, that its child spans cover."""

from perfbench import spans


def read(run):
    found = spans.in_window(run)
    if not found or not run.completed:
        return None
    lo, hi = run.trace_window
    # every idle stretch, as the breakdown finds them, in time order
    gaps = sorted(gap for gap, _ in run.trace.idle_gaps(
        run.trace_window, lambda a, b: (a, b), k=None))
    idle = spans.overlap_by_name(spans.innermost(found, run.trace_window),
                                 gaps)
    glue, wait, outside = (idle.pop(k, 0) for k in (spans.JOB, spans.WAIT,
                                                    None))
    jobs = [(ev.end_ns - ev.start_ns, own) for ev, own
            in zip(found, spans.self_ns(found)) if ev.name == spans.JOB]
    total = sum(d for d, _ in jobs)
    n = len(run.completed)
    return {"value": 100.0 * sum(idle.values()) / (hi - lo),
            "by_span": {k: v * 1e-9 / n for k, v in sorted(idle.items())},
            "job_glue_s": glue * 1e-9 / n,
            "wait_idle_s": wait * 1e-9 / n,
            "outside_s": outside * 1e-9 / n,
            "job_covered": (100.0 * (total - sum(o for _, o in jobs)) / total
                            if total else None)}
