"""I/O-efficient (partitioned) algorithms vs the serial oracle."""

import numpy as np
import pytest

from repro.core import graph as glib
from repro.core import partition as plib
from repro.core.bottom_up import (bottom_up_decompose, lower_bounding,
                                  partitioned_support)
from repro.core.serial import alg2_truss
from repro.core.support import edge_support_np
from repro.core.top_down import top_down_decompose, upper_bounds
from tests.conftest import random_graph


def _graph(rng, n=40, p=0.3):
    return glib.canonical_edges(random_graph(rng, n, p), n), n


@pytest.mark.parametrize("partitioner", ["sequential", "random", "locality"])
@pytest.mark.parametrize("budget_frac", [0.2, 0.5])
def test_bottom_up_exact(rng, partitioner, budget_frac):
    ce, n = _graph(rng)
    oracle = alg2_truss(n, ce)
    budget = max(8, int(len(ce) * budget_frac))
    res = bottom_up_decompose(n, ce, budget, partitioner=partitioner)
    assert (res.phi == oracle).all()
    assert res.kmax == oracle.max()


def test_lower_bounds_valid(rng):
    ce, n = _graph(rng)
    oracle = alg2_truss(n, ce)
    res = lower_bounding(n, ce, budget=max(8, len(ce) // 3))
    assert (res.lb <= np.maximum(oracle, 2)).all()
    # exact round-1 Phi_2 never mislabels
    assert (oracle[res.phi == 2] == 2).all()


def test_upper_bounds_valid(rng):
    ce, n = _graph(rng)
    oracle = alg2_truss(n, ce)
    sup = edge_support_np(glib.build_graph(n, ce))
    psi = upper_bounds(n, ce, sup)
    assert (psi >= oracle).all()  # Lemma 2


def test_partitioned_support_exact(rng):
    ce, n = _graph(rng)
    sup = edge_support_np(glib.build_graph(n, ce))
    for part in ("sequential", "random"):
        ps = partitioned_support(n, ce, budget=max(8, len(ce) // 4),
                                 partitioner=part)
        assert (ps == sup).all()


def test_top_down_all_classes(rng):
    ce, n = _graph(rng)
    oracle = alg2_truss(n, ce)
    td = top_down_decompose(n, ce)
    assert (td.phi == oracle).all()


def test_top_down_top_t(rng):
    ce, n = _graph(rng)
    oracle = alg2_truss(n, ce)
    td = top_down_decompose(n, ce, t=2)
    assert len(td.classes) <= 2
    for k in td.classes:
        assert set(np.nonzero(td.phi == k)[0]) == \
            set(np.nonzero(oracle == k)[0])
    # undecided edges stay 0 (except Phi_2 which stage 1 decides exactly)
    undecided = td.phi == 0
    assert (oracle[undecided] < min(td.classes, default=3)).all()


def test_top_down_with_budget(rng):
    ce, n = _graph(rng, n=35, p=0.35)
    oracle = alg2_truss(n, ce)
    td = top_down_decompose(n, ce, t=1, budget=max(8, len(ce) // 4))
    k = td.classes[0]
    assert set(np.nonzero(td.phi == k)[0]) == set(np.nonzero(oracle == k)[0])


def test_faithful_proc8_only_overreports(rng):
    """The paper's literal Procedure 8 can only inflate classes (never
    deflate) — the direction predicted by the analysis in DESIGN.md §7."""
    over = under = 0
    for t in range(6):
        ce, n = _graph(rng, n=30, p=0.35)
        oracle = alg2_truss(n, ce)
        tdf = top_down_decompose(n, ce, faithful_proc8=True)
        d = tdf.phi - oracle
        over += int((d > 0).sum())
        under += int((d < 0).sum())
    assert under == 0


def test_budget_respected(rng):
    ce, n = _graph(rng, n=60, p=0.2)
    budget = max(8, len(ce) // 4)
    res = lower_bounding(n, ce, budget)
    # sequential partitioner keeps each NS within ~budget plus one vertex
    assert res.max_part_edges <= 2 * budget + int(
        glib.degrees(n, ce).max())
    # OocStats mirrors the legacy accounting fields
    assert res.stats is not None
    assert res.stats.max_part_edges == res.max_part_edges
    assert res.stats.rounds == res.rounds
    assert res.stats.scans == res.scans
    assert res.stats.parts >= 1
    assert 0.0 <= res.stats.padding_waste < 1.0


def test_sequential_partition_over_budget_warns(rng):
    """A hub vertex whose NS exceeds the budget must be reported, and the
    driver's max_part_edges accounting must record the actual overshoot."""
    from repro.core.partition import PartitionBudgetWarning

    n = 30
    hub = np.stack([np.zeros(n - 1, np.int64), np.arange(1, n)], axis=1)
    ce = glib.canonical_edges(hub, n)         # star: deg(0) = n - 1
    budget = 5
    g = glib.build_graph(n, ce)
    with pytest.warns(PartitionBudgetWarning) as rec:
        parts = plib.sequential_partition(g, budget)
    w = rec[0].message
    assert w.n_over == 1 and w.budget == budget
    assert w.max_cost == n - 1
    # every vertex still lands in exactly one part
    assert sum(len(P) for P in parts) == n
    with pytest.warns(PartitionBudgetWarning):
        res = lower_bounding(n, ce, budget)
    # the hub's NS is the whole star: accounting must reflect the overshoot
    assert res.max_part_edges == n - 1
    assert res.max_part_edges > budget
