"""Truss-based graph utilities built on the decomposition:

* ``truss_filter``       — keep only edges of the k-truss (the paper's
                           visualization / fingerprinting use case);
* ``trussness_features`` — per-edge trussness as a normalized feature;
* ``sampling_weights``   — trussness-proportional edge-sampling weights
                           (strong ties first);
* ``clique_upper_bound`` — k_max bound on the maximum clique (Section 7.4).
"""

from __future__ import annotations

import numpy as np

from repro.core import graph as glib
from repro.core.peel import truss_decompose


def truss_filter(n: int, edges: np.ndarray, k: int) -> np.ndarray:
    """Edge list of the k-truss T_k."""
    edges = glib.canonical_edges(edges, n)
    phi = truss_decompose(n, edges)
    return edges[phi >= k]


def trussness_features(n: int, edges: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """(canonical edges, normalized trussness in [0, 1]) per edge."""
    edges = glib.canonical_edges(edges, n)
    phi = truss_decompose(n, edges).astype(np.float32)
    kmax = max(phi.max(), 3.0)
    return edges, (phi - 2.0) / (kmax - 2.0)


def sampling_weights(n: int, edges: np.ndarray, alpha: float = 1.0) -> np.ndarray:
    """Per-edge neighbor-sampling weight ∝ (trussness - 1) ** alpha."""
    edges = glib.canonical_edges(edges, n)
    phi = truss_decompose(n, edges).astype(np.float64)
    w = np.maximum(phi - 1.0, 1.0) ** alpha
    return (w / w.sum()).astype(np.float32)


def clique_upper_bound(n: int, edges: np.ndarray) -> int:
    """Max-clique size is at most k_max (tighter than c_max + 1; §7.4)."""
    phi = truss_decompose(n, edges)
    return int(phi.max()) if len(phi) else 2
