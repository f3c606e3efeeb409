"""Device launches per out-of-core job (``OocStats.batches``), a count."""


def read(run):
    batches = [j.counters.batches for j in run.completed
               if hasattr(j.counters, "batches")]
    return sum(batches) / len(batches) if batches else None
