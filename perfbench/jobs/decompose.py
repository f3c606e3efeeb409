"""A full decomposition: the trussness of every edge.

In memory through ``truss_decompose(n, edges)``; out of core through
``truss_decompose(engine="bottom-up", memory_budget=..., kernel=...)`` at
the configuration's part budget.
"""

from __future__ import annotations

import numpy as np


def working_set_entries(n: int, edges: np.ndarray) -> int:
    """Copied from ``chip_smoke.ooc_budget``'s arithmetic:
    ``truss_decompose`` takes ``memory_budget`` in working-set entries (4
    per edge plus 6 per oriented wedge) and turns it into parts of
    ``2 m * memory_budget / entries`` NS edge cost.  Computed here from
    the edge list alone, so the yardstick does not follow a change to
    the program's own estimate."""
    u = edges[:, 0].astype(np.int64)
    v = edges[:, 1].astype(np.int64)
    deg = np.bincount(np.concatenate([u, v]), minlength=n)
    rank = np.empty(n, np.int64)
    rank[np.lexsort((np.arange(n), deg))] = np.arange(n)
    src = np.where(rank[u] < rank[v], u, v)
    out_deg = np.bincount(src, minlength=n).astype(np.int64)
    return 4 * len(edges) + 6 * int((out_deg * out_deg).sum())


def memory_budget(part_budget: int, n: int, edges: np.ndarray) -> int:
    """The ``memory_budget`` that yields parts of ``part_budget`` cost."""
    m = len(edges)
    return max(1, part_budget * working_set_entries(n, edges) // (2 * m))


def make(cfg: dict, traffic: dict, n: int, edges: np.ndarray):
    from repro.core import peel

    if cfg["memory"] == "in_memory":
        kwargs = {}
    else:
        kwargs = {"engine": "bottom-up",
                  "memory_budget": memory_budget(cfg["part_budget"], n, edges),
                  "kernel": cfg["kernel"]}

    def run():
        phi, stats = peel.truss_decompose(n, edges, with_stats=True, **kwargs)
        return np.asarray(phi), stats

    return run


def mismatches(answer, ref_phi: np.ndarray, traffic: dict) -> int:
    if answer.shape != ref_phi.shape:
        return len(ref_phi)
    return int((answer != ref_phi).sum())
