"""Top-down truss decomposition for the top-t classes (paper Section 6).

``upper_bounds`` implements Procedure 6 / Lemma 2: for e = (u, v),
``psi(e) = min(sup(e), x_u, x_v) + 2`` where ``x_w`` is the largest x such
that x edges incident to w (excluding e) have support >= x — an h-index over
incident supports, computed vectorized for all edges at once.

``top_down_decompose`` implements Algorithm 7: classes are extracted from
k = max(psi) downward.  Per k it extracts the candidate H = NS(U_k) with
``U_k = {v : exists unclassified alive e at v with psi(e) >= k}`` and peels it
at threshold (k-3) (i.e. removes sup < k-2, Procedure 8); the surviving
internal unclassified edges are Phi_k.  Classified edges that no longer share
any triangle with an undecided edge are pruned from the working graph
(Algorithm 7 Steps 7-9).

The per-k candidate peel runs on the batch-engine machinery (DESIGN.md §8):
H is compacted to candidate-local edge ids, its triangle list filtered from
the one static G_new list, and the peel executes on pow4-padded shapes
(``peel.local_threshold_peel``) so consecutive k values reuse one compiled
kernel — the seed path instead recomputed an m-wide support scatter and ran
an m-sized peel per k.  The peel is dispatched non-blocking (DESIGN.md §9,
§11): while the device works, the host pre-builds the NEXT level's
candidate from the pre-result masks (a superset — provably sound: newly
classified edges flip to support-only externals and pruned edges die via
the peel's ``alive0`` mask at use time; ``OocStats.stage2_overlapped``)
and runs the O(T) alive-triangle sweep the Steps-7-9 pruning needs.  With
a ``budget``, stage-1 supports come from the
batched ``partitioned_support`` (whose partition rounds share the
double-buffered producer of ``bottom_up._partition_rounds``).
``TopDownResult.stats`` carries the ``OocStats`` counters of both stages.

Deviation from the paper (DESIGN.md §7): Procedure 8 counts support
contributed by *external unclassified* edges of H — edges whose own upper
bound rules them out of T_k (psi < k at every vertex outside U_k) — which can
keep a non-T_k internal edge alive and over-report Phi_k.  We exclude
external unclassified edges from the candidate peel, which makes the result
provably exact: survivors S satisfy "every edge of S ∪ T_k has support
>= k-2 within S ∪ T_k", so S ⊆ T_k by maximality, and S ⊇ Phi_k because a
T_k edge's triangles inside T_k use only classified or Phi_k (internal)
co-edges, all present.  ``faithful_proc8=True`` restores the paper's literal
procedure for comparison.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np

from repro.core import graph as glib
from repro.core import spans
from repro.core.bottom_up import (OocStats, RoundJournal, _Engine,
                                  _retry_candidate_peel, _run_key,
                                  partitioned_support)
from repro.core.peel import local_threshold_peel
from repro.core.support import (edge_support_auto, list_triangles,
                                support_from_triangle_list)


def upper_bounds(n: int, edges: np.ndarray, sup: np.ndarray) -> np.ndarray:
    """Procedure 6: psi(e) upper bound on trussness, vectorized."""
    m = len(edges)
    if m == 0:
        return np.zeros(0, np.int64)
    sup = np.asarray(sup, dtype=np.int64)
    inc_v = np.concatenate([edges[:, 0], edges[:, 1]]).astype(np.int64)
    inc_e = np.concatenate([np.arange(m), np.arange(m)]).astype(np.int64)
    inc_s = sup[inc_e]
    order = np.lexsort((-inc_s, inc_v))
    v_sorted = inc_v[order]
    s_sorted = inc_s[order]
    # segment starts per vertex
    seg_start = np.zeros(n + 1, dtype=np.int64)
    np.add.at(seg_start, v_sorted + 1, 1)
    seg_start = np.cumsum(seg_start)
    r = np.arange(len(v_sorted), dtype=np.int64) - seg_start[v_sorted] + 1
    # h0(v) = #{r : s_r >= r}; s_r - r strictly decreasing within a segment.
    cond = (s_sorted >= r).astype(np.int64)
    h0 = np.zeros(n, dtype=np.int64)
    np.add.at(h0, v_sorted, cond)
    # s_{h0+1}(v): the (h0+1)-th largest incident support (0 if none).
    deg = seg_start[1:] - seg_start[:-1]
    idx = seg_start[:-1] + h0  # position of rank h0+1
    has_next = h0 < deg
    s_next = np.where(has_next, s_sorted[np.minimum(idx, len(s_sorted) - 1)], 0)
    # x_v(e): exclude e from v's h-index.
    def x_at(vcol):
        v = edges[:, vcol].astype(np.int64)
        h = h0[v]
        drop = (sup >= h) & ~(s_next[v] >= np.maximum(h, 1))
        # if sup(e) < h0: exclusion doesn't affect counts at threshold h0;
        # x >= 0 always (the empty set satisfies x = 0).
        x = np.where(sup < h, h, np.where(drop, h - 1, h))
        return np.maximum(x, 0)

    x_u = x_at(0)
    x_v = x_at(1)
    return np.minimum(sup, np.minimum(x_u, x_v)) + 2


@dataclasses.dataclass
class TopDownResult:
    edges: np.ndarray
    phi: np.ndarray          # 0 = undecided (beyond the requested top-t)
    classes: List[int]       # the k values emitted, descending
    kmax: int
    candidate_sizes: List[int]
    pruned: int              # edges pruned by Steps 7-9
    stats: Optional[OocStats] = None


@spans.job("top-down")
def top_down_decompose(
    n: int,
    edges: np.ndarray,
    t: Optional[int] = None,
    budget: Optional[int] = None,
    partitioner: str = "sequential",
    faithful_proc8: bool = False,
    *,
    partitioner_seed: int = 0,
    mesh=None,
    mesh_axis="data",
    kernel: str = "auto",
    checkpoint_dir=None,
    checkpoint_every: "int | str" = 1,
    resume: bool = False,
    checkpoint_keep: int = 3,
    max_retries: int = 2,
    store=None,
) -> TopDownResult:
    """Algorithm 7: top-t k-classes (all classes if t is None).

    With a ``mesh``, every per-k candidate peel runs with its triangle
    list sharded over ``mesh_axis`` (DESIGN.md §10); ``OocStats.devices``
    / ``sharded_rounds`` record the routing.  A ``(lane, tri)`` tuple
    ``mesh_axis`` shards the triangle sweep over the flattened product of
    both axes (DESIGN.md §13).  ``kernel`` routes the candidate peel
    engine (``"pallas" | "xla" | "auto"``, forwarded to
    ``peel.local_threshold_peel``); it never changes φ, so it is not part
    of the checkpoint run key.  ``partitioner_seed`` offsets the
    randomized partitioner's per-round reseed in stage 1.

    With a ``checkpoint_dir`` the run journals round state (DESIGN.md §12):
    stage-1 partition rounds as ``"sup"`` snapshots and each completed class
    level as a ``"td"`` snapshot; ``resume=True`` restores the newest intact
    one and continues to a phi bit-identical to an uninterrupted run.  The
    derived level structure (psi, G_new, its triangle list) is recomputed
    deterministically from the journaled supports rather than stored.
    Failed candidate peels walk the retry ladder of
    ``bottom_up._retry_candidate_peel``; failed stage-1 credit rounds walk
    ``bottom_up._retry_support_round`` (``max_retries`` bounds both).
    ``checkpoint_every`` also accepts a duration string (``"30s"``).

    ``store`` routes stage 1's working graph through a
    :class:`~repro.core.store.GraphStore` (requires a ``budget`` — the
    unbudgeted whole-graph support path is in-memory by construction);
    the per-k class walk operates on G_new, which the top-down algorithm
    assumes host-resident (DESIGN.md §15).
    """
    edges = glib.canonical_edges(edges, n)
    m = len(edges)
    phi = np.zeros(m, dtype=np.int64)
    stats = OocStats()
    eng = _Engine(mesh=mesh, mesh_axis=mesh_axis, kernel=kernel)
    if mesh is not None:
        stats.devices = eng.devices
    if store is not None and budget is None:
        raise ValueError(
            "store= requires a working-set budget (the unbudgeted support "
            "path computes over the whole resident graph)")
    if m == 0:
        return TopDownResult(edges, phi, [], 2, [], 0, stats)

    journal = snap = None
    if checkpoint_dir is not None:
        key = _run_key("top_down", n, edges, budget, partitioner,
                       partitioner_seed, t=t, faithful=bool(faithful_proc8),
                       devices=eng.devices)
        journal = RoundJournal(checkpoint_dir, key, every=checkpoint_every,
                               keep=checkpoint_keep, store=store)
        if resume:
            snap = journal.load_latest()
    td_snap = snap if snap is not None and snap[1].get("stage") == "td" else None

    # Stage 1 (Alg 3 variant): exact supports; Phi_2 = zero-support edges.
    # edge_support_auto routes dense cores to the matmul/Pallas path and
    # sparse graphs to the bucketed wedge scan (DESIGN.md §2); with a budget
    # the batched triangle-credit counter runs under the working-set cap.
    # A "td" snapshot carries the finished supports, so stage 1 is skipped.
    if td_snap is not None:
        sup = np.asarray(td_snap[0]["sup"], dtype=np.int64)
        stats = OocStats.from_dict(td_snap[1]["stats"])
        stats.resumed_round = int(td_snap[1]["index"])
        if mesh is not None:
            stats.devices = eng.devices
    elif budget is None:
        g = glib.build_graph(n, edges)
        sup = edge_support_auto(g)
    else:
        sup, stats = partitioned_support(
            n, edges, budget,
            partitioner=partitioner,
            partitioner_seed=partitioner_seed,
            mesh=mesh, mesh_axis=mesh_axis,
            with_stats=True, journal=journal,
            restored=snap if snap is not None
            and snap[1].get("stage") == "sup" else None,
            max_retries=max_retries, store=store)
    phi[sup == 0] = 2
    alive = sup > 0                      # G_new
    psi = upper_bounds(n, edges, sup)

    # One static triangle list over G_new (skew-aware enumeration); every
    # per-k candidate filters it instead of re-enumerating wedges.
    gnew = glib.build_graph(n, edges[alive])
    gnew_ids = np.nonzero(alive)[0]
    tris_l = np.asarray(list_triangles(gnew), dtype=np.int64).reshape(-1, 3)
    shape_cache: set = set()
    # masks below are in G_new-local edge ids
    alive_l = np.ones(gnew.m, dtype=bool)
    classified_l = np.zeros(gnew.m, dtype=bool)
    psi_l = psi[gnew_ids]
    edges_l = edges[gnew_ids]

    classes: List[int] = []
    cand_sizes: List[int] = []
    pruned_total = 0
    k = int(psi_l.max()) if gnew.m else 2
    if td_snap is not None:
        # Continue below the journaled level: the snapshot's masks are the
        # state AFTER level ``index`` completed, so the next level is
        # ``index - 1``.  phi already holds every emitted class.
        tree, meta = td_snap
        phi = np.asarray(tree["phi"], dtype=np.int64)
        alive_l = np.asarray(tree["alive_l"], dtype=bool)
        classified_l = np.asarray(tree["classified_l"], dtype=bool)
        classes = [int(c) for c in meta.get("classes", [])]
        cand_sizes = [int(c) for c in meta.get("cand_sizes", [])]
        pruned_total = int(meta.get("pruned", 0))
        k = int(meta["index"]) - 1

    def build_candidate(k_b: int):
        """Host half of one top-down level: U_k from the CURRENT alive /
        classified masks, the candidate compacted and its triangles
        filtered from the static G_new list.

        Called one level ahead while the device still peels level k
        (DESIGN.md §11), when ``classified_l`` / ``alive_l`` miss the
        pending level's classifications and prunes — which only makes U
        and the candidate *supersets* of the true ones, and that is sound:
        a Φ_{k-1} edge is undecided and alive with psi >= k-1 now and
        after the pending level (classification only touches survivors of
        level k, pruning only classified edges off every undecided
        triangle), so it stays tentative with its T_{k-1} triangles
        present; extra tentative edges can only peel away or survive into
        S, and the S ∪ T_k maximality argument of the module docstring
        never assumed U was minimal.  At use time the masks are re-read:
        newly classified edges flip from removable to support-only,
        pruned edges die via the ``alive0`` mask of
        ``local_threshold_peel``.  Returns None when no undecided alive
        edge has psi >= k_b.
        """
        with spans.span("candidate_build", k=int(k_b)) as sp:
            undecided_b = alive_l & ~classified_l
            elig = undecided_b & (psi_l >= k_b)
            if not elig.any():
                return None
            u_k = np.zeros(n, dtype=bool)
            eg = edges_l[elig]
            u_k[eg[:, 0]] = True
            u_k[eg[:, 1]] = True
            u_in = u_k[edges_l[:, 0]]
            v_in = u_k[edges_l[:, 1]]
            in_h = alive_l & (u_in | v_in)
            internal = u_in & v_in           # re-masked by alive at use time
            if faithful_proc8:
                cand_set = in_h
            else:
                # exclude external unclassified support (see module docstring)
                cand_set = ((internal & alive_l & ~classified_l)
                            | (classified_l & in_h))
            # Compact the candidate to local edge ids and filter its triangles
            # (part-local compaction shared with the partition-batch engine).
            h_l = np.nonzero(cand_set)[0]
            tmask = (cand_set[tris_l[:, 0]] & cand_set[tris_l[:, 1]]
                     & cand_set[tris_l[:, 2]])
            tris_loc = glib.compact_index(h_l, tris_l[tmask])
            sp.count(edges=len(h_l))
        return k_b, h_l, tris_loc, internal, int(in_h.sum())

    pre = None          # candidate pre-built while the previous level peeled
    while k >= 3 and (t is None or len(classes) < t):
        undecided = alive_l & ~classified_l
        if not undecided.any():
            break
        elig = undecided & (psi_l >= k)
        if not elig.any():
            k = int(psi_l[undecided].max())
            continue
        if pre is not None and pre[0] == k and not faithful_proc8:
            cand = pre               # built while level k+1 was peeling
            stats.stage2_overlapped += 1
        else:
            cand = build_candidate(k)
        pre = None
        _, h_l, tris_loc, internal, in_h_size = cand
        tentative = internal & alive_l & ~classified_l
        cand_sizes.append(in_h_size)
        stats.scans += 1
        # kill candidate edges pruned after a pre-build; supports count
        # fully-alive triangles (newly classified edges stay as
        # support-only externals — they were tentative at build time)
        alive_h = alive_l[h_l]
        if len(tris_loc):
            t_alive = (alive_h[tris_loc[:, 0]] & alive_h[tris_loc[:, 1]]
                       & alive_h[tris_loc[:, 2]])
            sup0 = support_from_triangle_list(
                tris_loc[t_alive], len(h_l)).astype(np.int32)
        else:
            sup0 = np.zeros(len(h_l), np.int32)
        # Double-buffered candidate peel (DESIGN.md §9, §11): dispatch
        # without blocking, then build the NEXT level's candidate and do
        # the O(T) alive-triangle sweep the prune step needs while the
        # device peels — both depend only on masks the peel result cannot
        # change before it is consumed.
        handle = dispatch_exc = None
        try:
            handle = local_threshold_peel(
                sup0, tris_loc, tentative[h_l], k - 3, alive0=alive_h,
                shape_cache=shape_cache, blocking=False, mesh=eng.mesh,
                mesh_axis=eng.mesh_axis, kernel=eng.kernel,
                fault_ctx={"stage": "td", "k": int(k), "retry": 0})
            stats.compiles += int(handle.new_compile)
            stats.count_lanes(handle)
            stats.batches += 1
            stats.sharded_rounds += int(handle.sharded)
        except Exception as exc:
            dispatch_exc = exc          # enters the retry ladder below
        if not faithful_proc8:
            pre = build_candidate(k - 1)
        # the prune's alive-triangle sweep, while the device peels
        with spans.span("prune", k=int(k)):
            ta = (alive_l[tris_l[:, 0]] & alive_l[tris_l[:, 1]]
                  & alive_l[tris_l[:, 2]])
        try:
            if dispatch_exc is not None:
                raise dispatch_exc
            surv_l, _ = handle.result()
        except Exception as exc:
            # Candidate host arrays survive the donation, so a retry is a
            # plain re-dispatch of the same level (DESIGN.md §12).
            def redispatch(retry, e, _sup=sup0, _tris=tris_loc,
                           _rm=tentative[h_l], _k=k, _alive=alive_h):
                h = local_threshold_peel(
                    _sup, _tris, _rm, _k - 3, alive0=_alive,
                    shape_cache=shape_cache, blocking=False, mesh=e.mesh,
                    mesh_axis=e.mesh_axis, kernel=e.kernel,
                    fault_ctx={"stage": "td", "k": int(_k), "retry": retry})
                stats.compiles += int(h.new_compile)
                stats.count_lanes(h)
                stats.batches += 1
                stats.sharded_rounds += int(h.sharded)
                s, _ = h.result()
                return s
            surv_l = _retry_candidate_peel(eng, stats, exc, redispatch,
                                           max_retries, stage="td")
        phi_k = np.zeros(gnew.m, dtype=bool)
        phi_k[h_l[surv_l]] = True
        phi_k &= tentative
        if phi_k.any():
            classes.append(k)
            classified_l |= phi_k
            phi[gnew_ids[phi_k]] = k
            # Steps 7-9: prune classified edges with no undecided triangle.
            with spans.span("prune", k=int(k)) as sp:
                und = alive_l & ~classified_l
                tri_needs = ta & (und[tris_l[:, 0]] | und[tris_l[:, 1]]
                                  | und[tris_l[:, 2]])
                needs = np.zeros(gnew.m, dtype=np.int64)
                np.add.at(needs, tris_l.reshape(-1),
                          np.repeat(tri_needs, 3))
                prunable = alive_l & classified_l & (needs == 0)
                pruned = int(prunable.sum())
                sp.count(pruned=pruned)
            pruned_total += pruned
            alive_l &= ~prunable
        if journal is not None:
            journal.record(
                "td", k,
                {"phi": phi, "sup": sup, "alive_l": alive_l,
                 "classified_l": classified_l},
                stats,
                classes=[int(c) for c in classes],
                cand_sizes=[int(c) for c in cand_sizes],
                pruned=int(pruned_total))
        k -= 1

    kmax = classes[0] if classes else 2
    return TopDownResult(
        edges=edges, phi=phi, classes=classes, kmax=kmax,
        candidate_sizes=cand_sizes, pruned=pruned_total, stats=stats,
    )
