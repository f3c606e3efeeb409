"""Host edge-to-triangle incidence builds, seconds a job: the self time of
the program's ``truss.incidence`` spans in the traced window, over the
jobs completed there."""

from perfbench import spans

NAMES = ("truss.incidence",)


def read(run):
    return spans.self_seconds_per_job(run, NAMES)
