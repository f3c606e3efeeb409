"""Host triangle listing and support counting, seconds a job: the self
time of the program's ``truss.list_triangles`` and ``truss.edge_support``
spans in the traced window, over the jobs completed there."""

from perfbench import spans

NAMES = ("truss.list_triangles", "truss.edge_support")


def read(run):
    return spans.self_seconds_per_job(run, NAMES)
