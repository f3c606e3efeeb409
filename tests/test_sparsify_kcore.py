"""Coverage for the small exposed modules: ``sparsify`` (truss-based graph
utilities) and ``kcore`` (the paper's Section
7.4 comparison structure)."""

import numpy as np
import pytest

from repro.core import graph as glib
from repro.core.kcore import cmax_core, core_decompose
from repro.core.serial import alg2_truss
from repro.core.sparsify import (clique_upper_bound, sampling_weights,
                                 truss_filter, trussness_features)
from tests.conftest import (clique_edges, clustered_cliques, random_graph,
                            star_hub_graph, triangle_free_graph)


def _max_clique_bruteforce(n, edges):
    """Exact maximum clique by recursion over adjacency bitmasks (small n)."""
    adj = [0] * n
    for u, v in np.asarray(edges, dtype=np.int64).tolist():
        adj[u] |= 1 << v
        adj[v] |= 1 << u

    best = 0

    def grow(cand, size):
        nonlocal best
        if size + bin(cand).count("1") <= best:
            return
        if cand == 0:
            best = max(best, size)
            return
        v = cand.bit_length() - 1
        grow(cand & adj[v], size + 1)       # take v
        grow(cand & ~(1 << v), size)        # skip v

    grow((1 << n) - 1, 0)
    return best


# ---------------------------------------------------------------------------
# sparsify properties
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("alpha", [0.5, 1.0, 2.0])
def test_sampling_weights_normalized_and_positive(rng, alpha):
    for n, p in ((20, 0.3), (28, 0.15)):
        edges = random_graph(rng, n, p)
        if len(edges) < 3:
            continue
        w = sampling_weights(n, edges, alpha=alpha)
        ce = glib.canonical_edges(edges, n)
        assert w.shape == (len(ce),)
        assert (w > 0).all()
        assert w.sum() == pytest.approx(1.0, abs=1e-5)


def test_sampling_weights_monotone_in_trussness(rng):
    """Higher-trussness edges never get smaller weight (strong ties
    sampled first)."""
    n, ce = clustered_cliques(3, 6, seed=5)
    phi = alg2_truss(n, ce)
    w = sampling_weights(n, ce)
    order = np.argsort(phi)
    assert (np.diff(w[order]) >= -1e-9).all()


def test_truss_filter_is_k_truss(rng):
    n = 24
    edges = random_graph(rng, n, 0.35)
    ce = glib.canonical_edges(edges, n)
    phi = alg2_truss(n, ce)
    for k in (3, 4, 5):
        tk = truss_filter(n, edges, k)
        ref = ce[phi >= k]
        assert tk.shape == ref.shape
        assert (tk == ref).all()


def test_trussness_features_range(rng):
    n = 22
    edges = random_graph(rng, n, 0.3)
    ce, feat = trussness_features(n, edges)
    assert len(ce) == len(feat)
    assert (feat >= 0.0).all() and (feat <= 1.0).all()
    # a clique's internal edges are the strongest ties
    n2, ce2 = clustered_cliques(2, 7, seed=1)
    _, feat2 = trussness_features(n2, ce2)
    assert feat2.max() == pytest.approx(1.0)


def test_clique_upper_bound_vs_bruteforce(rng):
    """k_max bounds the maximum clique size from above (Section 7.4), and
    is tight on a clique."""
    for trial in range(4):
        n = 10 + 2 * trial
        edges = glib.canonical_edges(random_graph(rng, n, 0.4), n)
        if len(edges) < 3:
            continue
        ub = clique_upper_bound(n, edges)
        exact = _max_clique_bruteforce(n, edges)
        assert ub >= exact
    s = 7
    assert clique_upper_bound(s, clique_edges(0, s)) == s
    assert _max_clique_bruteforce(s, clique_edges(0, s)) == s


def test_clique_upper_bound_degenerate():
    # triangle-free: kmax == 2, max clique == 2 (any edge)
    n, ce = triangle_free_graph(16)
    assert clique_upper_bound(n, ce) == 2
    # empty graph
    assert clique_upper_bound(4, np.zeros((0, 2), np.int64)) == 2


# ---------------------------------------------------------------------------
# kcore edge cases
# ---------------------------------------------------------------------------

def test_core_decompose_empty_graph():
    core = core_decompose(5, np.zeros((0, 2), np.int64))
    assert core.shape == (5,)
    assert (core == 0).all()
    cmax, ce = cmax_core(5, np.zeros((0, 2), np.int64))
    assert cmax == 0 and len(ce) == 0


def test_core_decompose_multigraph_input():
    """Duplicate edges and self loops are canonicalized away — the core
    numbers match the simple-graph result."""
    simple = np.array([[0, 1], [1, 2], [0, 2], [2, 3]])
    noisy = np.concatenate([simple, simple[::-1], simple,
                            np.array([[1, 1], [3, 3]])])
    a = core_decompose(4, simple)
    b = core_decompose(4, noisy)
    assert (a == b).all()
    assert (a == np.array([2, 2, 2, 1])).all()


def test_cmax_core_on_clique():
    s = 8
    core = core_decompose(s, clique_edges(0, s))
    assert (core == s - 1).all()
    cmax, ce = cmax_core(s, clique_edges(0, s))
    assert cmax == s - 1
    assert len(ce) == s * (s - 1) // 2


def test_core_vs_truss_containment(rng):
    """A k-truss is a (k-1)-core (paper Section 7.4): every vertex of the
    k_max-truss has core number >= k_max - 1."""
    n, ce = clustered_cliques(3, 6, seed=2)
    phi = alg2_truss(n, ce)
    core = core_decompose(n, ce)
    kmax = int(phi.max())
    tk = ce[phi >= kmax]
    verts = np.unique(tk.reshape(-1))
    assert (core[verts] >= kmax - 1).all()


def test_core_star_and_path():
    n, ce = star_hub_graph(20, 12)
    core = core_decompose(n, ce)
    assert core.max() == 1          # star + path are 1-degenerate
    cmax, edges_c = cmax_core(n, ce)
    assert cmax == 1 and len(edges_c) == len(ce)


def test_truss_sparsify_features():
    from repro.data import graphgen
    edges = graphgen.planted_cliques(60, 2, 6, 60, seed=0)
    t6 = truss_filter(60, edges, 6)
    assert len(t6) >= 2 * 15 - 15  # at least one clique survives
    _, feats = trussness_features(60, edges)
    assert feats.min() >= 0 and feats.max() <= 1
    w = sampling_weights(60, edges)
    assert abs(w.sum() - 1.0) < 1e-5
    assert clique_upper_bound(60, edges) >= 6
