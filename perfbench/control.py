"""The control: the plain reference in the program's place, computed with
one removal sweep per level, which breaks the exact-trussness guarantee
the configurations state.  A sound check has to call it not correct.

    python3 perfbench/control.py --workload <cell> --seeds 1 2 3

prints, per seed, the number the check compares (``edges_wrong``) for
the control's answer at the cell's own size.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np


def answer(mix: dict, n: int, edges: np.ndarray):
    """The control's answer in the form the cell's job kind returns."""
    from perfbench import reference

    phi = reference.control_phi(n, edges)
    if mix["job"] == "top_classes":
        classes = np.unique(phi[phi >= 3])[::-1][:int(mix["classes"])]
        return np.where(np.isin(phi, classes), phi, 0), classes.tolist()
    return phi


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from perfbench import graph500, harness, reference

    bench = harness.benchmark()
    cell = harness.cell(bench, args.workload)
    cfg = harness.config(bench, cell["config"])
    mix = harness.traffic(cell["traffic"])
    jobmod = harness.load_module("jobs", mix["job"])
    for seed in args.seeds:
        t0 = time.perf_counter()
        n, edges, _ = graph500.graph(cfg, seed)
        ref = reference.phi(n, edges)
        wrong = jobmod.mismatches(answer(mix, n, edges), ref, mix)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "m": len(edges), "control_edges_wrong": wrong,
                          "seconds": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
