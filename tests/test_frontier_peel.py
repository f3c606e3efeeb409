"""Frontier-compacted peel engine + skew-aware support (DESIGN.md §3-§4)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import graph as glib
from repro.core import peel as peel_mod
from repro.core.peel import (peel_classes, peel_classes_dense, peel_threshold,
                             peel_threshold_dense, truss_decompose)
from repro.core.serial import alg2_truss
from repro.core.support import (edge_support_jax, edge_support_np,
                                list_triangles_np, support_from_triangle_list,
                                triangle_incidence_np, wedge_bucket_plan)
from tests.conftest import random_graph


def _star_plus_clique(hub_deg=2000, q=30):
    """One hub vertex of degree ``hub_deg`` plus a disjoint q-clique — the
    skew shape that blows up a global-max-out-degree wedge tensor."""
    star = np.stack([np.zeros(hub_deg, np.int64),
                     np.arange(1, hub_deg + 1)], 1)
    iu = np.triu_indices(q, 1)
    clique = np.stack(iu, 1) + hub_deg + 1
    n = hub_deg + 1 + q
    return n, glib.canonical_edges(np.concatenate([star, clique]), n)


def _prep(n, ce):
    g = glib.build_graph(n, ce)
    tris = list_triangles_np(g)
    sup = support_from_triangle_list(tris, g.m).astype(np.int32)
    if len(tris) == 0:
        tris = np.full((1, 3), g.m, np.int32)
    return g, tris, sup


class TestSkewAwareSupport:
    def test_star_plus_clique_matches_np(self):
        n, ce = _star_plus_clique()
        g = glib.build_graph(n, ce)
        assert (edge_support_np(g) == np.asarray(edge_support_jax(g))).all()

    def test_bucketed_capacity_bounded(self):
        """The wedge-tensor capacity must not track the hub's degree."""
        n, ce = _star_plus_clique()
        g = glib.build_graph(n, ce)
        plan = wedge_bucket_plan(g)
        cap = sum(b.capacity for b in plan)
        # global-D capacity pays max_out_deg slots for every edge
        assert cap * 3 < g.m * g.max_out_deg
        # each bucket's D covers its own rows: no row longer than D, and D
        # never more than 2x the longest row it serves
        row_len = g.indptr[g.src + 1] - g.indptr[g.src]
        for b in plan:
            lens = row_len[b.eids[: b.n_real]]
            assert lens.max() <= b.D
            assert b.D <= max(2 * int(lens.max()), 1)

    def test_bucketed_equals_global_d(self, rng):
        e = random_graph(rng, 120, 0.1)
        g = glib.build_graph(120, glib.canonical_edges(e, 120))
        a = np.asarray(edge_support_jax(g, bucketed=True))
        b = np.asarray(edge_support_jax(g, bucketed=False))
        assert (a == b).all()

    def test_skew_trussness_exact(self):
        n, ce = _star_plus_clique(hub_deg=300, q=12)
        assert (truss_decompose(n, ce) == alg2_truss(n, ce)).all()


class TestFrontierPeel:
    @pytest.mark.parametrize("trial", range(8))
    def test_matches_serial_random(self, rng, trial):
        for _ in range(trial + 1):
            n = int(rng.integers(8, 70))
            p = rng.uniform(0.05, 0.5)
        e = random_graph(rng, n, p)
        ce = glib.canonical_edges(e, n)
        if len(ce) == 0:
            return
        oracle = alg2_truss(n, ce)
        g, tris, sup = _prep(n, ce)
        for engine in ("frontier", "auto"):
            phi, alive = peel_classes(
                jnp.asarray(sup), jnp.asarray(tris), jnp.ones(g.m, bool),
                engine=engine)
            assert (np.asarray(phi) == oracle).all()
            assert not np.asarray(alive).any()

    def test_matches_dense_engine(self, rng):
        e = random_graph(rng, 60, 0.3)
        ce = glib.canonical_edges(e, 60)
        g, tris, sup = _prep(60, ce)
        args = (jnp.asarray(sup), jnp.asarray(tris), jnp.ones(g.m, bool))
        phi_f, _ = peel_classes(*args, engine="frontier")
        phi_d, _ = peel_classes_dense(*args)
        assert (np.asarray(phi_f) == np.asarray(phi_d)).all()

    def test_max_k_stops_early(self, rng):
        e = random_graph(rng, 50, 0.4)
        ce = glib.canonical_edges(e, 50)
        g, tris, sup = _prep(50, ce)
        oracle = alg2_truss(50, ce)
        args = (jnp.asarray(sup), jnp.asarray(tris), jnp.ones(g.m, bool))
        kcut = int(oracle.max()) - 1
        if kcut < 2:
            return
        phi, alive = peel_classes(*args, max_k=kcut, engine="frontier")
        phi, alive = np.asarray(phi), np.asarray(alive)
        assert (phi[oracle <= kcut] == oracle[oracle <= kcut]).all()
        assert (phi[oracle > kcut] == 0).all()
        assert (alive == (oracle > kcut)).all()

    def test_threshold_matches_dense(self, rng):
        e = random_graph(rng, 50, 0.35)
        ce = glib.canonical_edges(e, 50)
        g, tris, sup = _prep(50, ce)
        removable = jnp.asarray(rng.random(g.m) < 0.7)
        args = (jnp.asarray(sup), jnp.asarray(tris), jnp.ones(g.m, bool),
                removable, jnp.int32(2))
        a_f, s_f, r_f = peel_threshold(*args, engine="frontier")
        a_d, s_d, r_d = peel_threshold_dense(*args)
        assert (np.asarray(a_f) == np.asarray(a_d)).all()
        assert (np.asarray(r_f) == np.asarray(r_d)).all()
        assert (np.asarray(s_f)[np.asarray(a_f)]
                == np.asarray(s_d)[np.asarray(a_d)]).all()

    def test_scatter_work_scales_with_frontier(self, rng):
        """Total gathered incidence slots == 3T for a full decomposition —
        each (edge, triangle) pair is touched exactly once, in the round its
        edge dies; the dense engine would touch rounds * 3T slots."""
        e = random_graph(rng, 90, 0.25)
        ce = glib.canonical_edges(e, 90)
        g, tris, sup = _prep(90, ce)
        T = int((tris < g.m).all(axis=1).sum())
        phi, _, stats = peel_classes(
            jnp.asarray(sup), jnp.asarray(tris), jnp.ones(g.m, bool),
            with_stats=True)
        assert stats.gathered == 3 * T
        assert stats.removed == g.m
        assert stats.rounds > 1
        # the dense engine's scatter work for the same decomposition
        assert stats.gathered < stats.rounds * 3 * T
        assert stats.max_frontier <= g.m

    def test_capacity_overflow_resume(self, rng):
        """Undersized explicit capacities must recover via host doubling."""
        e = random_graph(rng, 40, 0.5)
        ce = glib.canonical_edges(e, 40)
        oracle = alg2_truss(40, ce)
        g, tris, sup = _prep(40, ce)
        phi, _, stats = peel_classes(
            jnp.asarray(sup), jnp.asarray(tris), jnp.ones(g.m, bool),
            cap_f=4, cap_t=1, with_stats=True)
        assert (np.asarray(phi) == oracle).all()
        assert stats.resumes > 0

    def test_incidence_csr_shape(self, rng):
        e = random_graph(rng, 60, 0.3)
        ce = glib.canonical_edges(e, 60)
        g, tris, _ = _prep(60, ce)
        indptr, tids = triangle_incidence_np(tris, g.m)
        T = int((tris < g.m).all(axis=1).sum())
        assert indptr[-1] == 3 * T == len(tids)
        # row e lists exactly the triangles containing e
        for eid in rng.integers(0, g.m, 5):
            row = tids[indptr[eid]:indptr[eid + 1]]
            assert set(row) == {t for t in range(len(tris))
                                if eid in tris[t]}


# ---------------------------------------------------------------------------
# the round's slot-to-segment lookup (ragged -> flat expansion)
# ---------------------------------------------------------------------------

_M = 1000  # edge-id pad of the compacted frontier (f_ids == m past its end)


def _segments(rng, case, cap_f, cap_t):
    """``(f_ids, lens)`` as ``_frontier_round`` builds them: ascending edge
    ids, then the pad id with length 0."""
    nf = {"empty": 0, "fills_cap_t": 1}.get(case, int(rng.integers(1, cap_f)))
    f_ids = np.full(cap_f, _M, np.int32)
    f_ids[:nf] = np.sort(rng.choice(_M, nf, replace=False))
    lens = np.zeros(cap_f, np.int32)
    if case == "ragged":     # empty rows among them, all ends inside cap_t
        lens[:nf] = rng.integers(0, 4, nf) * (rng.random(nf) < 0.6)
    elif case == "past_cap_t":
        lens[:nf] = rng.integers(0, 3 * cap_t // max(nf, 1), nf)
        lens[: nf // 4] = 0
    elif case == "fills_cap_t":
        lens[0] = cap_t
    return f_ids, lens


def _lookup_by_search(f_ids, lens, indptr, cap_t):
    """The binary-search form: the segment index, its edge id, its start,
    and its edge's incidence row start."""
    offs = jnp.cumsum(lens)
    s = jnp.arange(cap_t, dtype=jnp.int32)
    jc = jnp.minimum(jnp.searchsorted(offs, s, side="right"),
                     f_ids.shape[0] - 1).astype(jnp.int32)
    f = f_ids[jc]
    return jc, f, offs[jc] - lens[jc], indptr[jnp.minimum(f, _M - 1)]


def _lookup_by_scan(f_ids, lens, indptr, cap_t):
    starts = jnp.cumsum(lens) - lens
    seg = jnp.arange(f_ids.shape[0], dtype=jnp.int32)
    rows = indptr[jnp.minimum(f_ids, _M - 1)]
    return tuple(peel_mod._slot_owner(starts, v, cap_t)
                 for v in (seg, f_ids, starts, rows))


@pytest.mark.parametrize("batched", [False, True], ids=["one", "vmap"])
@pytest.mark.parametrize("case", ["ragged", "past_cap_t", "empty",
                                  "fills_cap_t"])
def test_slot_owner_matches_searchsorted(rng, case, batched):
    """Every gather slot gets the same segment, owner edge id, segment
    start and incidence row start from the scatter-and-prefix-sum lookup
    as from the search."""
    cap_f, cap_t = 64, 256
    draws = [_segments(rng, case, cap_f, cap_t)
             for _ in range(6 if batched else 1)]
    f_ids = jnp.asarray(np.stack([d[0] for d in draws]))
    lens = jnp.asarray(np.stack([d[1] for d in draws]))
    indptr = jnp.asarray(np.cumsum(rng.integers(0, 5, (len(draws), _M + 1)),
                                   axis=1, dtype=np.int32))
    args = (f_ids, lens, indptr)
    want_fn = lambda *a: _lookup_by_search(*a, cap_t)  # noqa: E731
    got_fn = lambda *a: _lookup_by_scan(*a, cap_t)  # noqa: E731
    if batched:
        want, got = jax.vmap(want_fn)(*args), jax.vmap(got_fn)(*args)
    else:
        want, got = (want_fn(*(a[0] for a in args)),
                     got_fn(*(a[0] for a in args)))
    for w, g in zip(want, got):
        assert np.array_equal(np.asarray(w), np.asarray(g))


@pytest.mark.parametrize("n", [1, 127, 128, 129, 16384 + 5, 65536])
def test_prefix_sum_matches_cumsum(rng, n):
    """The matmul prefix sum equals ``cumsum`` bit for bit, wrapping on
    int32 overflow alike."""
    x = rng.integers(-2**31, 2**31, n, dtype=np.int64).astype(np.int32)
    assert np.array_equal(np.asarray(peel_mod._prefix_sum(jnp.asarray(x))),
                          np.cumsum(x, dtype=np.int32))


def _lower_peel(name, m=48, T=32, cap_f=16, cap_t=128, lanes=4):
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    b = lambda *s: jax.ShapeDtypeStruct(s, jnp.bool_)  # noqa: E731
    caps = dict(cap_f=cap_f, cap_t=cap_t)
    graph = (i32(T, 3), i32(m + 1), i32(3 * T))
    if name == "peel_classes_fixedcap":
        return peel_mod.peel_classes_fixedcap.lower(
            i32(m), *graph, b(m), i32(m), i32(), i32(peel_mod.N_STATS),
            **caps)
    if name == "peel_threshold_fixedcap":
        return peel_mod.peel_threshold_fixedcap.lower(
            i32(m), *graph, b(m), b(m), i32(), i32(peel_mod.N_STATS), **caps)
    return peel_mod._peel_classes_vmapped.lower(
        i32(lanes, m), *(jax.ShapeDtypeStruct((lanes,) + g.shape, g.dtype)
                         for g in graph), b(lanes, m), **caps)


@pytest.mark.parametrize("name", ["peel_classes_fixedcap",
                                  "peel_threshold_fixedcap",
                                  "_peel_classes_vmapped"])
def test_peel_program_has_no_loop_inside_the_round(name):
    """Each peel program is its outer frontier loop and nothing loops
    inside a round: a per-slot search (``jnp.searchsorted`` lowers to a
    while loop) must not come back into the round unseen."""
    assert _lower_peel(name).as_text().count("stablehlo.while") == 1
