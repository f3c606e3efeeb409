"""Host triangle listing against the per-row binary search it replaced.

The host path finds each wedge's closing edge with one ``searchsorted`` of
composite keys ``row * n + nbr`` over exact wedges.  The reference below is
the earlier enumeration: a padded (C, D) wedge tensor whose members are
found by a numpy binary search inside row b.  Both must give the same
triangle rows in the same order, and the same supports, for every chunking.
"""

import math

import numpy as np
import pytest

from repro.core import graph as glib
from repro.core.support import (WEDGE_BUDGET, edge_support_np,
                                list_triangles, list_triangles_np,
                                wedge_bucket_plan)
from repro.data import graphgen
from tests.conftest import clique_edges


# ---------------------------------------------------------------------------
# reference: per-wedge binary search over padded (C, D) wedge tensors
# ---------------------------------------------------------------------------

def _ref_lower_bound(nbrs, lo, hi, target, iters):
    lo, hi = lo.copy(), hi.copy()
    last = max(len(nbrs) - 1, 0)
    for _ in range(iters):
        mid = (lo + hi) >> 1
        less = np.where(lo < hi, nbrs[np.minimum(mid, last)] < target, False)
        lo = np.where(less, mid + 1, lo)
        hi = np.where(less, hi, np.where(lo < hi, mid, hi))
    return lo


def _ref_hits(g, eids, D):
    a = g.src[eids].astype(np.int64)
    b = g.dst[eids].astype(np.int64)
    C = len(a)
    if C == 0 or D == 0:
        return np.zeros((0, 3), np.int64)
    last = max(len(g.nbrs) - 1, 0)
    slot = np.arange(D, dtype=np.int64)[None, :]
    row_start = g.indptr[a].astype(np.int64)[:, None]
    valid = slot < (g.indptr[a + 1] - g.indptr[a]).astype(np.int64)[:, None]
    pos_aw = np.minimum(row_start + slot, last)
    w = g.nbrs[pos_aw].astype(np.int64)
    b_end = g.indptr[b + 1].astype(np.int64)[:, None]
    lo = np.broadcast_to(g.indptr[b].astype(np.int64)[:, None], (C, D))
    hi = np.broadcast_to(b_end, (C, D))
    m = g.max_out_deg
    iters = max(1, math.ceil(math.log2(m + 1))) if m > 0 else 1
    p = _ref_lower_bound(g.nbrs, lo.reshape(-1), hi.reshape(-1),
                         w.reshape(-1), iters).reshape(C, D)
    pc = np.minimum(p, last)
    hit = valid & (p < b_end) & (g.nbrs[pc] == w)
    eid = np.broadcast_to(np.asarray(eids, np.int64)[:, None], (C, D))
    return np.stack([eid[hit], g.nbr_eid[pos_aw][hit].astype(np.int64),
                     g.nbr_eid[pc][hit].astype(np.int64)], axis=1)


def _ref_rows(parts):
    rows = [r for r in parts if len(r)]
    return (np.concatenate(rows).astype(np.int32) if rows
            else np.zeros((0, 3), np.int32))


def ref_list_triangles_np(g, chunk=1 << 16):
    return _ref_rows(_ref_hits(g, np.arange(lo, min(lo + chunk, g.m)),
                               g.max_out_deg)
                     for lo in range(0, g.m, chunk))


def ref_list_triangles(g, chunk=1 << 14, budget=1 << 18):
    parts = []
    for bucket in wedge_bucket_plan(g, chunk, budget):
        ids = bucket.eids[: bucket.n_real].astype(np.int64)
        for lo in range(0, len(ids), bucket.chunk):
            parts.append(_ref_hits(g, ids[lo : lo + bucket.chunk], bucket.D))
    return _ref_rows(parts)


def ref_edge_support(g):
    sup = np.zeros(g.m, np.int64)
    np.add.at(sup, ref_list_triangles_np(g).reshape(-1), 1)
    return sup


# ---------------------------------------------------------------------------
# graphs
# ---------------------------------------------------------------------------

def _kronecker(scale):
    return graphgen.rmat(scale, 16, seed=scale)


def _clique():
    return 12, glib.canonical_edges(clique_edges(0, 12), 12)


def _star():
    hub = np.stack([np.zeros(30, np.int64), np.arange(1, 31)], axis=1)
    return 31, glib.canonical_edges(hub, 31)


def _empty():
    return 5, np.zeros((0, 2), np.int32)


def _triangle_free():
    """A 5x6 grid: bipartite, so wedges but no triangles."""
    idx = np.arange(30).reshape(5, 6)
    e = np.concatenate([np.stack([idx[:, :-1].ravel(), idx[:, 1:].ravel()], 1),
                        np.stack([idx[:-1].ravel(), idx[1:].ravel()], 1)])
    return 40, glib.canonical_edges(e, 40)


def _hub_and_isolated(hub_deg=12, leaves=11):
    """Vertex 0 joins a ring of ``hub_deg`` vertices that each carry
    ``leaves`` private leaves, so every ring vertex outranks 0 and 0's
    out-row holds the whole ring; ids past the last leaf stay isolated."""
    ring = np.arange(1, hub_deg + 1)
    e = [np.stack([np.zeros(hub_deg, np.int64), ring], 1),
         np.stack([ring, np.roll(ring, -1)], 1)]
    leaf = hub_deg + 1
    for v in ring:
        e.append(np.stack([np.full(leaves, v), np.arange(leaf, leaf + leaves)],
                          1))
        leaf += leaves
    n = leaf + 20
    return n, glib.canonical_edges(np.concatenate(e), n)


BIG = WEDGE_BUDGET
# (case id, graph factory, wedge budget): the small budgets put chunk
# boundaries inside rows and between them; budget 5 is below the hub's
# out-row of 12, so one row spans several chunks
CASES = [
    ("kron8", lambda: _kronecker(8), BIG),
    ("kron9", lambda: _kronecker(9), BIG),
    ("kron10", lambda: _kronecker(10), BIG),
    ("kron8-budget997", lambda: _kronecker(8), 997),
    ("clique", _clique, BIG),
    ("clique-budget3", _clique, 3),
    ("star", _star, BIG),
    ("star-budget1", _star, 1),
    ("empty", _empty, BIG),
    ("triangle-free", _triangle_free, BIG),
    ("triangle-free-budget4", _triangle_free, 4),
    ("hub-isolated", _hub_and_isolated, BIG),
    ("hub-isolated-budget5", _hub_and_isolated, 5),
]


@pytest.mark.parametrize("make,budget", [c[1:] for c in CASES],
                         ids=[c[0] for c in CASES])
def test_listing_matches_binary_search(make, budget):
    n, edges = make()
    g = glib.build_graph(n, edges)
    tris = list_triangles_np(g, budget=budget)
    ref = ref_list_triangles_np(g)
    assert tris.dtype == np.int32 and tris.shape == (len(ref), 3)
    assert np.array_equal(tris, ref)
    assert np.array_equal(list_triangles(g, budget=budget),
                          ref_list_triangles(g))
    assert np.array_equal(edge_support_np(g, budget=budget),
                          ref_edge_support(g))
    if budget < BIG:  # the case does cut the wedges into several chunks
        assert (np.diff(g.indptr).astype(np.int64) ** 2).sum() > 2 * budget
