"""Share, in %, of the lanes dispatched over a mesh that held no live edge:
100 * ``mesh_pad_lanes`` / ``mesh_lanes`` (``OocStats``), the dead lanes
that splitting each bucket's lanes evenly over the chips added, averaged
over the jobs completed in the window.  A program that does not count mesh
lanes, or a run whose jobs dispatched none, reads nothing."""


def read(run):
    shares = [100.0 * j.counters.mesh_pad_lanes / j.counters.mesh_lanes
              for j in run.completed
              if getattr(j.counters, "mesh_lanes", 0)]
    return sum(shares) / len(shares) if shares else None
