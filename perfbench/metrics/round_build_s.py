"""Host work of the out-of-core rounds and levels, seconds a job: the self
time of the program's ``truss.round_build`` (a partition round: NS sweep,
edge removal, triangle routing, lane packing), ``truss.candidate_build``
(a stage-2 or top-down level's candidate) and ``truss.support_credit``
(a round of top-down stage 1's triangle credits) spans in the traced
window, over the jobs completed there.  Listing and incidence inside them
are their own metrics."""

from perfbench import spans

NAMES = ("truss.round_build", "truss.candidate_build",
         "truss.support_credit")


def read(run):
    return spans.self_seconds_per_job(run, NAMES)
