"""Graph data copied from the host to the device, MB (10^6 bytes) a job:
the program's ``h2d_bytes`` counter (``PeelStats``, ``OocStats``), the
``bytes`` its ``truss.upload`` spans carry, over the jobs completed in the
window.  Read in a traced run on a chip only: without one nothing crosses
to a device."""

from perfbench import spans


def read(run):
    if not spans.on_chip(run):
        return None
    got = [j.counters.h2d_bytes for j in run.completed
           if hasattr(j.counters, "h2d_bytes")]
    return sum(got) / len(got) * 1e-6 if got else None
