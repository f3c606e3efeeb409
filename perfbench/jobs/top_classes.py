"""The top-t non-empty k-classes (k >= 3) through the paper's top-down
algorithm, ``top_down_decompose(t=..., budget=..., kernel=...)``."""

from __future__ import annotations

import numpy as np


def make(cfg: dict, traffic: dict, n: int, edges: np.ndarray):
    from repro.core import top_down

    budget = cfg.get("part_budget")
    kernel = cfg.get("kernel", "auto")
    t = int(traffic["classes"])

    def run():
        res = top_down.top_down_decompose(n, edges, t=t, budget=budget,
                                          kernel=kernel)
        return (np.asarray(res.phi), list(res.classes)), res.stats

    return run


def mismatches(answer, ref_phi: np.ndarray, traffic: dict) -> int:
    """Edges whose class in the top t differs: an edge of a top class must
    carry that class, and no other edge may carry one."""
    phi, classes = answer
    if phi.shape != ref_phi.shape:
        return len(ref_phi)
    top = np.unique(ref_phi[ref_phi >= 3])[::-1][:int(traffic["classes"])]
    want = np.where(np.isin(ref_phi, top), ref_phi, 0)
    got = np.where(np.isin(phi, classes), phi, 0)
    return int((want != got).sum()) + len(set(top.tolist()) ^ set(classes))
