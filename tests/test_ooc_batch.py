"""Batched out-of-core engine: partition batches, incremental maintenance,
batched local peels (DESIGN.md §8) — against the serial oracle and a
per-part reference peel."""

import numpy as np
import pytest

from repro.core import graph as glib
from repro.core.bottom_up import (OocStats, bottom_up_decompose,
                                  lower_bounding, partitioned_support)
from repro.core.partition import (build_partition_batch, ns_edge_lists,
                                  sequential_partition)
from repro.core.peel import (PendingPeel, estimate_working_set,
                             local_threshold_peel, peel_classes_batched,
                             truss_decompose)
from repro.core.serial import alg2_truss
from repro.core.support import edge_support_np, list_triangles, list_triangles_np
from tests.conftest import clique_edges, random_graph, rmat_graph


def _local_truss(sub_edges: np.ndarray, n: int) -> np.ndarray:
    """Trussness of every edge of one NS subgraph, peeled on its own: the
    reference each packed lane slice must match."""
    import jax.numpy as jnp

    from repro.core.peel import peel_classes
    from repro.core.support import support_from_triangle_list

    g = glib.build_graph(n, sub_edges)
    if g.m == 0:
        return np.zeros(0, np.int64)
    tris = list_triangles_np(g)
    sup = support_from_triangle_list(tris, g.m).astype(np.int32)
    if len(tris) == 0:
        tris = np.full((1, 3), g.m, np.int32)
    phi, _ = peel_classes(jnp.asarray(sup), jnp.asarray(tris),
                          jnp.ones(g.m, bool))
    return np.asarray(phi).astype(np.int64)


# ---------------------------------------------------------------------------
# deterministic oracle corpus (the hypothesis sweep lives in
# test_ooc_property.py; this subset runs without hypothesis installed)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("partitioner", ["sequential", "random"])
@pytest.mark.parametrize("budget_frac", [0.15, 0.5])
def test_batched_engines_match_oracle(rng, partitioner, budget_frac):
    from repro.core.top_down import top_down_decompose

    for trial in range(3):
        n = 20 + 6 * trial
        ce = glib.canonical_edges(random_graph(rng, n, 0.3), n)
        if len(ce) < 3:
            continue
        oracle = alg2_truss(n, ce)
        budget = max(4, int(len(ce) * budget_frac))
        res = bottom_up_decompose(n, ce, budget, partitioner=partitioner)
        assert (res.phi == oracle).all()
        assert res.stats is not None and res.stats.parts >= 1
        td = top_down_decompose(n, ce, budget=budget, partitioner=partitioner)
        assert (td.phi == oracle).all()
        sup = edge_support_np(glib.build_graph(n, ce))
        ps, stats = partitioned_support(n, ce, budget,
                                        partitioner=partitioner,
                                        with_stats=True)
        assert (ps == sup).all()
        assert stats.rounds >= 1


# ---------------------------------------------------------------------------
# batch construction: compaction, bucketing, padding
# ---------------------------------------------------------------------------

def test_ns_edge_lists_matches_reference(rng):
    n = 48
    ce = glib.canonical_edges(random_graph(rng, n, 0.25), n)
    g = glib.build_graph(n, ce)
    parts = sequential_partition(g, budget=max(8, len(ce) // 5))
    assert len(parts) >= 3
    lists = ns_edge_lists(g, parts)
    for P, (ids, internal) in zip(parts, lists):
        ids_ref, _, int_ref = glib.neighborhood_subgraph(g, P)
        assert (ids == ids_ref).all()
        assert (internal == int_ref).all()


def test_bucket_padding_never_contributes_support(rng):
    """Padded lanes and padded edge slots are inert: zero support in, zero
    phi out; every packed part slice reproduces a standalone peel of its
    NS exactly."""
    n = 40
    ce = glib.canonical_edges(random_graph(rng, n, 0.3), n)
    g = glib.build_graph(n, ce)
    parts = sequential_partition(g, budget=max(8, len(ce) // 4))
    batch = build_partition_batch(g, parts)
    assert batch.n_parts == len(parts)
    assert batch.real_edges <= batch.padded_slots
    seen_parts = set()
    for bucket in batch.buckets:
        B = bucket.n_lanes
        # padded lanes are fully dead
        for lane in range(bucket.n_real_lanes, B):
            assert not bucket.alive[lane].any()
            assert (bucket.edge_ids[lane] == -1).all()
            assert (bucket.tris[lane] == bucket.cap_e).all()
            assert (bucket.sup[lane] == 0).all()
        phi_b, _, _ = peel_classes_batched(
            bucket.sup, bucket.tris, bucket.indptr, bucket.tids, bucket.alive)
        assert (phi_b[bucket.n_real_lanes:] == 0).all()
        for lane in range(bucket.n_real_lanes):
            real = bucket.edge_ids[lane] >= 0
            assert (real == (bucket.part_of[lane] >= 0)).all()
            # padded edge slots: dead, zero support, zero phi
            assert not bucket.alive[lane][~real].any()
            assert (bucket.sup[lane][~real] == 0).all()
            assert (phi_b[lane][~real] == 0).all()
            # padding triangles all point at the drop slot; support totals
            # 3 * (real triangle count) — padding contributed nothing
            n_tri = int((bucket.tris[lane][:, 0] < bucket.cap_e).sum())
            assert int(bucket.sup[lane].sum()) == 3 * n_tri
            # every part slice packed into the lane equals a standalone
            # peel of that NS
            for p in np.unique(bucket.part_of[lane][real]):
                sl = bucket.part_of[lane] == p
                ref = _local_truss(g.edges[bucket.edge_ids[lane][sl]], g.n)
                assert (phi_b[lane][sl] == ref).all()
                seen_parts.add(int(p))
    assert len(seen_parts) == batch.n_parts


def test_bottom_up_lane_padding_waste_rmat10():
    """Waste-aware lane packing on one device: bottom-up on rmat-10 at a
    1/32 budget pads less than the 0.674 share of lane slots the packer
    wasted before it, with φ equal to the oracle."""
    import warnings

    from repro.core.partition import PartitionBudgetWarning

    n, ce = rmat_graph(scale=10, edge_factor=6, seed=7)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", PartitionBudgetWarning)
        res = bottom_up_decompose(n, ce, max(len(ce) // 32, 256))
    assert (res.phi == alg2_truss(n, ce)).all()
    assert res.stats.padding_waste < 0.674


def test_local_threshold_peel_matches_dense(rng):
    """Pow2-padded compacted threshold peel == dense full-shape peel."""
    import jax.numpy as jnp

    from repro.core.peel import peel_threshold_dense
    from repro.core.support import support_from_triangle_list

    n = 24
    ce = glib.canonical_edges(random_graph(rng, n, 0.4), n)
    g = glib.build_graph(n, ce)
    tris = list_triangles_np(g)
    sup = support_from_triangle_list(tris, g.m).astype(np.int32)
    removable = rng.random(g.m) < 0.7
    for thresh in (0, 1, 2, 4):
        cache: set = set()
        alive, removed, _ = local_threshold_peel(
            sup, tris, removable, thresh, shape_cache=cache)
        tris_j = jnp.asarray(tris if len(tris) else
                             np.full((1, 3), g.m, np.int32))
        a_ref, _, r_ref = peel_threshold_dense(
            jnp.asarray(sup), tris_j, jnp.ones(g.m, bool),
            jnp.asarray(removable), jnp.int32(thresh))
        assert (alive == np.asarray(a_ref)).all()
        assert (removed == np.asarray(r_ref)).all()


# ---------------------------------------------------------------------------
# incremental graph maintenance
# ---------------------------------------------------------------------------

def test_remove_edges_equivalent_to_rebuild(rng):
    n = 45
    ce = glib.canonical_edges(random_graph(rng, n, 0.25), n)
    g = glib.build_graph(n, ce)
    edges = ce
    for _ in range(5):
        if g.m == 0:
            break
        rm = rng.random(g.m) < 0.35
        g = g.remove_edges(rm)
        edges = edges[~rm]
        ref = glib.build_graph(n, edges)
        assert (g.edges == ref.edges).all()
        assert (g.deg == ref.deg).all()
        assert g.indptr[-1] == g.m
        # orientation may differ (ranks are reused, not recomputed), but
        # wedge enumeration must see the same triangles/supports
        assert (edge_support_np(g) == edge_support_np(ref)).all()
        s_inc = np.zeros(g.m, np.int64)
        tl = list_triangles(g)
        if len(tl):
            np.add.at(s_inc, tl.reshape(-1), 1)
        assert (s_inc == edge_support_np(ref)).all()


def test_remove_all_edges(rng):
    ce = glib.canonical_edges(random_graph(rng, 10, 0.5), 10)
    g = glib.build_graph(10, ce)
    g2 = g.remove_edges(np.ones(g.m, bool))
    assert g2.m == 0 and g2.max_out_deg == 0
    assert (g2.deg == 0).all()


# ---------------------------------------------------------------------------
# stage-2 class-k skip + dispatch
# ---------------------------------------------------------------------------

def test_stage2_skips_empty_classes():
    """Disjoint K12 + K5 + a path: the only classes are {2, 5, 12}, and the
    lower bounds are exact, so stage 2 must probe exactly two k values (5
    then 12) instead of every k in [2, 12] as the seed did."""
    edges = np.concatenate([
        clique_edges(0, 12), clique_edges(12, 5),
        np.array([[17, 18], [18, 19], [19, 20]]),
    ])
    n = 21
    ce = glib.canonical_edges(edges, n)
    budget = 4 * len(ce)                 # one part: exact lower bounds
    lbres = lower_bounding(n, ce, budget)
    assert lbres.in_gnew.any()
    assert int(lbres.lb[lbres.in_gnew].min()) == 5
    res = bottom_up_decompose(n, ce, budget)
    assert (res.phi == alg2_truss(n, ce)).all()
    assert res.kmax == 12
    stage2_iters = res.scans - lbres.scans
    assert stage2_iters == 2             # seed would have probed 11 k values


def test_truss_decompose_ooc_dispatch(rng):
    n = 40
    ce = glib.canonical_edges(random_graph(rng, n, 0.3), n)
    oracle = alg2_truss(n, ce)
    g = glib.build_graph(n, ce)
    est = estimate_working_set(g)
    assert est > 4 * g.m
    # small budget -> auto routes out of core and returns OocStats
    phi, stats = truss_decompose(n, ce, engine="auto", memory_budget=64,
                                 with_stats=True)
    assert (phi == oracle).all()
    assert isinstance(stats, OocStats) and stats.rounds >= 1
    # a budget below the working set but above 2m must still partition:
    # the NS budget is rescaled from working-set entries to edge cost
    mid = max(2 * len(ce) + 1, est // 2)
    if mid < est:
        phi_mid, stats_mid = truss_decompose(
            n, ce, engine="auto", memory_budget=mid, with_stats=True)
        assert (phi_mid == oracle).all()
        assert stats_mid.parts > 1
    # large budget -> stays in memory
    phi2 = truss_decompose(n, ce, engine="auto", memory_budget=10 * est)
    assert (phi2 == oracle).all()
    # forced engines
    for eng in ("bottom-up", "top-down"):
        phi3 = truss_decompose(n, ce, engine=eng, memory_budget=48)
        assert (phi3 == oracle).all(), eng


def test_pending_peel_result_not_retried_after_error():
    """Regression (ISSUE 4): if finalize raises, the handle must be
    cleared/poisoned — a retry must NOT re-invoke the kernel, whose support
    buffers were donated at dispatch and no longer exist."""
    calls = []

    def finalize():
        calls.append(1)
        raise ValueError("boom")

    handle = PendingPeel(finalize, new_compile=False)
    with pytest.raises(ValueError, match="boom"):
        handle.result()
    # the poisoned handle re-raises WITHOUT running finalize again
    with pytest.raises(RuntimeError, match="cannot be retried") as exc:
        handle.result()
    assert len(calls) == 1
    assert isinstance(exc.value.__cause__, ValueError)


def test_pending_peel_result_cached_on_success():
    calls = []

    def finalize():
        calls.append(1)
        return ("phi", "st")

    handle = PendingPeel(finalize, new_compile=True)
    assert handle.result() == ("phi", "st")
    assert handle.result() is handle.result()
    assert calls == [1]
    assert handle.new_compile and not handle.sharded


# ---------------------------------------------------------------------------
# spilled-triangle streaming: reload peak bounded below the spilled total
# ---------------------------------------------------------------------------

def test_spilled_triangle_reload_peak_bounded(tmp_path, rng):
    """Satellite-2 regression (DESIGN.md §16): rounds over a disk-spilled
    triangle list must stream it chunk-wise — the recorded reload peak has
    to stay strictly below the largest spilled list, which the old
    load-it-whole path could never satisfy."""
    import warnings

    from repro.core.store import ChunkedDiskStore

    n = 300
    ce = glib.canonical_edges(random_graph(rng, n, 0.05), n)
    oracle = alg2_truss(n, ce)
    with ChunkedDiskStore(str(tmp_path / "store"),
                          chunk_bytes=1 << 10) as store:
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            res = bottom_up_decompose(n, ce, budget=80, store=store)
    assert (res.phi == oracle).all()
    s = res.stats
    assert s.tri_rescans_avoided > 0          # spilled rounds actually ran
    assert s.tri_spill_rows > 0
    assert 0 < s.tri_reload_peak_rows < s.tri_spill_rows


def test_disk_store_at_an_eighth_of_the_graph(tmp_path):
    """Bottom-up on rmat-10 with the graph behind a ChunkedDiskStore held
    to 1/8 of the graph's bytes: φ equals the oracle, the graph really
    spills and streams back, the prefetcher serves at least half of the
    chunk requests, and residency never passes the budget."""
    import warnings

    from repro.core.partition import PartitionBudgetWarning
    from repro.core.store import ChunkedDiskStore

    n, ce = rmat_graph(scale=10, edge_factor=6, seed=7)
    g = glib.build_graph(n, ce)
    graph_bytes = sum(
        int(getattr(g, a).nbytes)
        for a in ("edges", "deg", "rank", "src", "dst", "indptr", "nbrs",
                  "nbr_eid"))
    host_budget = graph_bytes // 8
    with ChunkedDiskStore(str(tmp_path / "store"),
                          host_memory_budget=host_budget,
                          chunk_bytes=max(host_budget // 16, 4096)) as store:
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", PartitionBudgetWarning)
            res = bottom_up_decompose(n, ce, max(len(ce) // 32, 256),
                                      store=store)
        peak = store.stats.peak_resident_bytes
    assert (res.phi == alg2_truss(n, ce)).all()
    s = res.stats
    assert s.bytes_spilled > 0
    assert s.chunk_reads > 0 and s.chunk_writes > 0
    assert s.prefetch_hit_rate >= 0.5, s.prefetch_hit_rate
    assert peak <= host_budget, (peak, host_budget)
