"""End-to-end behaviour tests for the paper's system."""

from repro.core.bottom_up import bottom_up_decompose
from repro.core.peel import truss_decompose
from repro.core.serial import alg2_truss
from repro.core.top_down import top_down_decompose
from repro.data import graphgen


def test_end_to_end_decomposition_paths_agree():
    """The full production story on one power-law graph: in-memory bulk
    peel == bottom-up (restricted memory) == top-down == serial oracle."""
    n, edges = graphgen.rmat(scale=9, edge_factor=8, seed=11)
    oracle = alg2_truss(n, edges)
    assert (truss_decompose(n, edges) == oracle).all()
    bu = bottom_up_decompose(n, edges, budget=max(64, len(edges) // 6))
    assert (bu.phi == oracle).all()
    td = top_down_decompose(n, edges, t=3)
    for k in td.classes:
        assert ((td.phi == k) == (oracle == k)).all()

