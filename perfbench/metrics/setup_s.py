"""Seconds from process start to the end of the warm job: imports, JAX
start-up, the graph from the seed and one job that compiles or loads
every program of the cell."""


def read(run):
    return run.setup_s
