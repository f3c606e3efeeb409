"""Core truss decomposition: paper Figure 2, oracle agreement, invariants."""

import numpy as np
import pytest

from repro.core import graph as glib
from repro.core.kcore import cmax_core, core_decompose
from repro.core.peel import kmax_truss, truss_decompose
from repro.core.serial import alg1_truss, alg2_truss, verify_truss
from repro.core.support import edge_support_jax, edge_support_np
from tests.conftest import random_graph

NAMES = {c: i for i, c in enumerate("abcdefghijkl")}
FIG2 = """a b;a c;a d;a e;b c;b d;b e;c d;c e;d e;d g;d k;d l;e f;e g;f g;
g h;g k;g l;f h;f i;f j;h i;h j;i j;i k"""
FIG2_EDGES = np.array([[NAMES[x] for x in p.split()]
                       for p in FIG2.replace("\n", "").split(";") if p.strip()])
FIG2_CLASSES = {
    2: {"ik"},
    3: set("dg dk dl ef eg fg gh gk gl".split()),
    4: set("fh fi fj hi hj ij".split()),
    5: set("ab ac ad ae bc bd be cd ce de".split()),
}


def test_canonical_edges_rejects_out_of_range_ids():
    """Regression: an explicit n smaller than the max vertex id used to
    wrap ids through the u*n+v dedup key and silently corrupt the edge
    list; it must raise instead."""
    bad = np.array([[0, 1], [2, 5]])
    with pytest.raises(ValueError, match=r"vertex id 5 but n=3"):
        glib.canonical_edges(bad, 3)
    # boundary: ids in [0, n) are fine
    ok = glib.canonical_edges(bad, 6)
    assert ok.max() == 5


def test_canonical_edges_rejects_negative_ids():
    with pytest.raises(ValueError, match="negative vertex id"):
        glib.canonical_edges(np.array([[0, 1], [-2, 3]]), 10)
    # negatives are rejected even when n is inferred
    with pytest.raises(ValueError, match="negative vertex id"):
        glib.canonical_edges(np.array([[-1, 2]]))


def test_canonical_edges_valid_inputs_unchanged():
    e = np.array([[3, 1], [1, 3], [2, 2], [0, 3]])
    ce = glib.canonical_edges(e, 4)
    # dedup, self-loop drop, u < v orientation, lexicographic order
    assert ce.tolist() == [[0, 3], [1, 3]]
    assert glib.canonical_edges(np.zeros((0, 2), np.int64), 4).shape == (0, 2)


def test_figure2_exact():
    """Reproduces the paper's running example (Figure 2) exactly."""
    n = 12
    ce = glib.canonical_edges(FIG2_EDGES, n)
    phi = truss_decompose(n, ce)
    inv = {v: k for k, v in NAMES.items()}
    got = {}
    for eid, (u, v) in enumerate(ce):
        got.setdefault(int(phi[eid]), set()).add(inv[u] + inv[v])
    assert got == FIG2_CLASSES
    assert phi.max() == 5  # k_max


def test_figure2_no_6truss():
    n = 12
    ce = glib.canonical_edges(FIG2_EDGES, n)
    kmax, t = kmax_truss(n, ce)
    assert kmax == 5 and len(t) == 10  # the 5-clique


@pytest.mark.parametrize("trial", range(10))
def test_all_algorithms_agree(rng, trial):
    for _ in range(trial + 1):
        n = int(rng.integers(5, 60))
        p = rng.uniform(0.05, 0.6)
    e = random_graph(rng, n, p)
    if len(e) == 0:
        return
    ce = glib.canonical_edges(e, n)
    a1 = alg1_truss(n, ce)
    a2 = alg2_truss(n, ce)
    bulk = truss_decompose(n, ce)
    assert (a1 == a2).all()
    assert (a2 == bulk).all()


def test_support_np_equals_jax(rng):
    e = random_graph(rng, 80, 0.15)
    g = glib.build_graph(80, glib.canonical_edges(e, 80))
    assert (edge_support_np(g) == np.asarray(edge_support_jax(g))).all()


def test_truss_definition_holds(rng):
    e = random_graph(rng, 40, 0.3)
    ce = glib.canonical_edges(e, 40)
    phi = truss_decompose(40, ce)
    assert verify_truss(40, ce, phi)


def test_truss_in_core(rng):
    """A k-truss is a (k-1)-core (paper Section 1)."""
    e = random_graph(rng, 50, 0.25)
    ce = glib.canonical_edges(e, 50)
    phi = truss_decompose(50, ce)
    core = core_decompose(50, ce)
    for eid, (u, v) in enumerate(ce):
        assert core[u] >= phi[eid] - 1
        assert core[v] >= phi[eid] - 1


def test_clique_gives_truss():
    """A planted q-clique is exactly a q-truss (paper Section 7.4)."""
    q = 7
    iu = np.triu_indices(q, 1)
    e = np.stack(iu, 1)
    phi = truss_decompose(q, e)
    assert (phi == q).all()


def test_kmax_bounds_clique(rng):
    """max-clique size <= k_max <= c_max + 1 relationships (Section 7.4)."""
    e = random_graph(rng, 40, 0.4)
    ce = glib.canonical_edges(e, 40)
    kmax, _ = kmax_truss(40, ce)
    cmax, _ = cmax_core(40, ce)
    assert kmax <= cmax + 1
