"""Device seconds per job of the in-memory frontier loop: executions of
the jitted ``peel_classes_fixedcap`` in the traced window, over the jobs
that completed there."""

FUNCTION = "peel_classes_fixedcap"


def read(run):
    if run.trace is None or run.trace_window is None or not run.completed:
        return None
    total = run.trace.module_s(FUNCTION, run.trace_window)
    if total <= 0:
        return None
    return total / len(run.completed)
