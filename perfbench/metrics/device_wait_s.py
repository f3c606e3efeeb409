"""Seconds a job the host sat blocked on device results (and their copy
back): the duration of the program's ``truss.device_wait`` spans in the
traced window, over the jobs completed there."""

from perfbench import spans


def read(run):
    waits = [ev.end_ns - ev.start_ns for ev in spans.in_window(run)
             if ev.name == spans.WAIT]
    return spans.per_job(run, sum(waits) * 1e-9) if waits else None
