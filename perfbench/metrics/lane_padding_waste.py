"""Share of the lane slots, in %, that bottom-up bucket packing padded:
100 * (1 - real_edges / padded_slots) summed over the window's jobs."""


def read(run):
    stats = [j.counters for j in run.completed
             if hasattr(j.counters, "padded_slots")]
    padded = sum(s.padded_slots for s in stats)
    if not padded:
        return None
    return 100.0 * (1.0 - sum(s.real_edges for s in stats) / padded)
