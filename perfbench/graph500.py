"""Graph500 Kronecker edge lists, made on the host from a seed.

The generator follows "Graph 500 Benchmark 1", section "Graph generation":
``edge_factor * 2**scale`` edges, each placed by ``scale`` independent
quadrant draws of the initiator (A, B, C, D = 1 - A - B - C), then a random
relabelling of the vertices.  Edges are made undirected: self loops and
duplicates are dropped and each edge is written once as (u, v) with u < v,
the list sorted, which is the canonical order the decomposition's φ is
indexed by.

The structure comes from ``graph_seed``, a fixed number of the
configuration.  The vertex labels come from the run's ``--seed`` where the
configuration's ``labels`` is ``"run_seed"``, and from ``graph_seed`` where
it is ``"graph_seed"``: out of core, the labels decide the partition
(contiguous label ranges), so a new labelling is new work.  Either way the
run's seed then shuffles the edge tuples and turns each one a random way
round, as the specification's generator does, and the program receives
them in that order.  So every run of a cell decomposes the same graph, up
to vertex names where they are the seed's, with the same edge and triangle
counts and the same trussness classes.  The quadrant draws match
``repro.data.graphgen.rmat`` draw for draw, so scale 13 with
``graph_seed`` 0 has 102,075 edges.
"""

from __future__ import annotations

import numpy as np


def kronecker_edges(scale: int, edge_factor: int, a: float, b: float,
                    c: float, graph_seed: int) -> np.ndarray:
    """Directed (src, dst) Kronecker draws, before relabelling."""
    rng = np.random.default_rng(graph_seed)
    m = (1 << scale) * edge_factor
    src = np.zeros(m, np.int64)
    dst = np.zeros(m, np.int64)
    for bit in range(scale):
        r = rng.random(m)
        go_right = (r >= a) & (r < a + b) | (r >= a + b + c)
        go_down = r >= a + b
        src |= go_down.astype(np.int64) << bit
        dst |= go_right.astype(np.int64) << bit
    return np.stack([src, dst], 1)


def canonical(n: int, pairs: np.ndarray) -> np.ndarray:
    """Undirected simple edge list: u < v, unique, lexicographically sorted."""
    u = np.minimum(pairs[:, 0], pairs[:, 1])
    v = np.maximum(pairs[:, 0], pairs[:, 1])
    keep = u != v
    key = np.unique(u[keep] * np.int64(n) + v[keep])
    return np.stack([key // n, key % n], 1).astype(np.int32)


def graph(cfg: dict, seed: int) -> tuple[int, np.ndarray, np.ndarray]:
    """(n, canonical edges, edges as the program receives them) of the
    configuration's graph for the run's ``seed`` (any non-negative
    integer, also above 2**32)."""
    scale = int(cfg["scale"])
    n = 1 << scale
    graph_seed = int(cfg["graph_seed"])
    pairs = kronecker_edges(scale, int(cfg["edge_factor"]),
                            float(cfg["initiator_a"]),
                            float(cfg["initiator_b"]),
                            float(cfg["initiator_c"]), graph_seed)
    labels = {"run_seed": seed, "graph_seed": graph_seed}[cfg["labels"]]
    perm = np.random.default_rng([labels, 1]).permutation(n)
    edges = canonical(n, perm[pairs])
    rng = np.random.default_rng([seed, 2])
    given = edges[rng.permutation(len(edges))]
    flip = rng.random(len(given)) < 0.5
    given[flip] = given[flip][:, ::-1]
    return n, edges, given
