"""Share of the traced window, in %, in which no operation ran on the
device: 100 * (1 - busy / window), busy being the union of the ``XLA
Ops`` intervals averaged over the chips."""


def read(run):
    if run.trace is None or run.trace_window is None:
        return None
    busy = run.trace.busy_s(run.trace_window)
    lo, hi = run.trace_window
    if busy is None or hi <= lo:
        return None
    return 100.0 * (1.0 - busy / ((hi - lo) * 1e-9))
