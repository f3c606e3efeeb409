"""Benchmark harness — one function per paper table.

Prints ``name,us_per_call,derived`` CSV rows (and JSON records with
``--json``, which also carry structured counters such as the frontier
engine's round/frontier-size statistics):

  table3_*  — in-memory decomposition: Alg 1 (TD-inmem) vs Alg 2
              (TD-inmem+) vs the vectorized bulk peel (ours).  The paper's
              headline speedup (2.2–73x) is algorithmic; we report the
              same comparison on power-law graphs.
  table4_*  — out-of-memory regime on the rmat graphs: batched OOC engine
              vs the seed per-part path vs the global-iterate baseline
              (the MapReduce [16] stand-in); ``--only table4 --json
              BENCH_ooc.json`` records the OocStats counters.  The
              ``table4_*_partitioner_*`` rows compare sequential vs
              random vs locality-aware partitioning by counters (rounds,
              scans, batches, compiles, triangle locality) — wall-clock
              is too noisy on shared CPU to compare across runs.  The
              ``table4shard_*`` rows route each round's bucket lanes
              through shard_map over every local device (DESIGN.md §10)
              and record devices / sharded_rounds / padding_waste against
              the single-device batched engine.
  table5_*  — top-down top-t vs bottom-up full decomposition.
  table6_*  — k_max-truss vs c_max-core statistics (sizes, clustering).
  peel_*    — frontier-compacted engine vs the seed dense engine
              (DESIGN.md §3) and skew-aware vs global-D support (§4).
  kernel_*  — Pallas kernel microbenches (interpret mode, correctness-
              scaled shapes; TPU wall-times come from the roofline).

Usage: ``run.py [--json BENCH_peel.json] [--only PREFIX ...] [--smoke]``.
``--smoke`` restricts the peel and table4 comparisons to their smallest
dataset (CI).
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np


ROWS = []


def emit(name: str, us: float, derived: str = "", **extra):
    ROWS.append({"name": name, "us_per_call": us, "derived": derived, **extra})
    print(f"{name},{us:.1f},{derived}", flush=True)


def _time(fn, repeats=1):
    best = float("inf")
    out = None
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn()
        best = min(best, time.perf_counter() - t0)
    return best * 1e6, out


def table3_inmemory():
    from benchmarks.datasets import SMALL, load
    from repro.core.peel import truss_decompose
    from repro.core.serial import alg1_truss, alg2_truss

    for name in SMALL:
        n, edges = load(name)
        us1, phi1 = _time(lambda: alg1_truss(n, edges))
        us2, phi2 = _time(lambda: alg2_truss(n, edges))
        usb, phib = _time(lambda: truss_decompose(n, edges))
        assert (phi1 == phi2).all() and (phi2 == phib).all()
        kmax = int(phi2.max())
        emit(f"table3_{name}_alg1_TDinmem", us1,
             f"m={len(edges)};kmax={kmax}")
        emit(f"table3_{name}_alg2_TDinmem+", us2,
             f"speedup_vs_alg1={us1/us2:.2f}")
        emit(f"table3_{name}_bulkpeel_ours", usb,
             f"speedup_vs_alg1={us1/usb:.2f}")


def table4_bottom_up(smoke: bool = False):
    """Out-of-memory regime: batched OOC engine (DESIGN.md §8) vs the seed
    per-part path vs the global-iterate baseline (MapReduce [16] stand-in).

    The rmat graphs are the paper's web/social shape; the budget (1/32 of
    the graph, the deep out-of-core regime) forces hundreds of partitions
    per round, so the rows measure exactly the regime the batch engine
    targets: the seed path pays one host subgraph build + one freshly
    shaped compile per part, the batched engine a handful of pow2 shapes
    per run.  ``--json BENCH_ooc.json`` captures the OocStats counters
    (rounds, scans, batches, compiles, padding waste, triangle locality,
    stage-2 pipeline depth).  A ``TDtopdown_batched`` row runs the second
    driver at the same budget — both drivers' rows record
    ``stage2_overlapped`` (DESIGN.md §11).
    """
    from benchmarks.datasets import load
    from repro.core.bottom_up import bottom_up_decompose
    from repro.core.graph import build_graph
    from repro.core.peel import peel_recompute
    from repro.core.support import list_triangles_np
    from repro.core.top_down import top_down_decompose

    names = ["hep-like"] if smoke else ["hep-like", "amazon-like", "wiki-like"]
    for name in names:
        # cold-run isolation per graph; the perpart seed rows compile one
        # executable PER PART (thousands of mmap regions), and letting them
        # accumulate across graphs runs into vm.max_map_count
        jax.clear_caches()
        n, edges = load(name)
        budget = max(len(edges) // 32, 1024)  # "memory" = 1/32 of the graph
        usb, res = _time(lambda: bottom_up_decompose(n, edges, budget))
        usp, res_p = _time(
            lambda: bottom_up_decompose(n, edges, budget, engine="perpart"))
        # global-iterate baseline (MapReduce stand-in): recompute supports
        # from scratch every round over the whole graph
        g = build_graph(n, edges)
        tris = list_triangles_np(g)
        if len(tris) == 0:
            tris = np.full((1, 3), g.m, np.int32)
        tj = jnp.asarray(tris)
        usm, phim = _time(
            lambda: np.asarray(peel_recompute(tj, jnp.ones(g.m, bool))))
        # cross-check the three paths against each other (the serial oracle
        # is exercised on these sizes in table3 / tests; python-oracle runs
        # on 300k+ edge graphs would dominate the harness wall time)
        assert (res.phi == phim).all() and (res.phi == res_p.phi).all()
        st, st_p = res.stats, res_p.stats
        emit(f"table4_{name}_TDbottomup_batched", usb,
             f"m={len(edges)};rounds={res.rounds};parts={st.parts};"
             f"batches={st.batches};compiles={st.compiles};"
             f"tri_locality={st.tri_locality:.3f};"
             f"stage2_overlapped={st.stage2_overlapped};"
             f"speedup_vs_perpart={usp/usb:.2f};budget={budget}",
             m=len(edges), budget=budget, rounds=res.rounds,
             scans=res.scans, parts=st.parts, batches=st.batches,
             compiles=st.compiles, max_part_edges=st.max_part_edges,
             padding_waste=st.padding_waste,
             tri_locality=st.tri_locality,
             stage2_overlapped=st.stage2_overlapped,
             tri_est_error=st.tri_est_error,
             speedup_vs_perpart=usp / usb)
        emit(f"table4_{name}_TDbottomup_perpart_seed", usp,
             f"rounds={res_p.rounds};scans={res_p.scans};"
             f"parts={st_p.parts};budget={budget}",
             m=len(edges), budget=budget, rounds=res_p.rounds,
             scans=res_p.scans, parts=st_p.parts)
        emit(f"table4_{name}_globaliter_MRstandin", usm,
             f"slowdown_vs_batched={usm/usb:.2f}",
             slowdown_vs_batched=usm / usb)
        # the second driver at the same deep budget: its per-k candidate
        # peels ride the same stage-2 pipeline (DESIGN.md §11)
        ust, res_t = _time(lambda: top_down_decompose(n, edges,
                                                      budget=budget))
        assert (res_t.phi == res.phi).all()
        st_t = res_t.stats
        emit(f"table4_{name}_TDtopdown_batched", ust,
             f"rounds={st_t.rounds};scans={st_t.scans};"
             f"tri_locality={st_t.tri_locality:.3f};"
             f"stage2_overlapped={st_t.stage2_overlapped};budget={budget}",
             m=len(edges), budget=budget, rounds=st_t.rounds,
             scans=st_t.scans, parts=st_t.parts, batches=st_t.batches,
             compiles=st_t.compiles, tri_locality=st_t.tri_locality,
             stage2_overlapped=st_t.stage2_overlapped,
             tri_est_error=st_t.tri_est_error)


def table4_partitioners(smoke: bool = False):
    """Partitioner comparison at memory = m/32 (DESIGN.md §9): sequential
    vs rebalanced-random vs locality-aware on the rmat graphs.

    Wall-clock on this box is too noisy to compare runs, so the rows
    record the OocStats *counters* — partition rounds, NS/candidate
    scans, device batches, distinct compiles, triangle locality — which
    are deterministic per (graph, partitioner, budget).  phi is asserted
    identical across partitioners (Lemma 1 holds for any partition).
    """
    from benchmarks.datasets import load
    from repro.core.bottom_up import bottom_up_decompose

    names = ["hep-like"] if smoke else ["hep-like", "amazon-like", "wiki-like"]
    for name in names:
        n, edges = load(name)
        budget = max(len(edges) // 32, 1024)
        phi_ref = None
        for part in ("sequential", "random", "locality"):
            us, res = _time(lambda: bottom_up_decompose(
                n, edges, budget, partitioner=part))
            if phi_ref is None:
                phi_ref = res.phi
            else:
                assert (res.phi == phi_ref).all(), part
            st = res.stats
            emit(f"table4_{name}_partitioner_{part}", us,
                 f"rounds={res.rounds};ns_sweeps={st.ns_sweeps};"
                 f"tri_routes={st.tri_routes};scans={res.scans};"
                 f"batches={st.batches};compiles={st.compiles};"
                 f"tri_locality={st.tri_locality:.3f};"
                 f"tri_est_error={st.tri_est_error:.2f};"
                 f"stage2_overlapped={st.stage2_overlapped};"
                 f"overlapped={st.overlapped};budget={budget}",
                 m=len(edges), budget=budget, rounds=res.rounds,
                 ns_sweeps=st.ns_sweeps, tri_routes=st.tri_routes,
                 scans=res.scans, parts=st.parts, batches=st.batches,
                 compiles=st.compiles, tri_total=st.tri_total,
                 tri_assigned=st.tri_assigned,
                 tri_locality=st.tri_locality,
                 tri_est_error=st.tri_est_error,
                 stage2_overlapped=st.stage2_overlapped,
                 overlapped=st.overlapped,
                 max_part_edges=st.max_part_edges,
                 padding_waste=st.padding_waste)


def table4_sharded(smoke: bool = False):
    """Pod-spanning OOC rounds (DESIGN.md §10): the batched bottom-up
    engine with bucket lanes routed through shard_map over every local
    device vs the single-device batched engine.

    On CPU the shards are virtual (forced host devices in CI), so the rows
    record the sharding *counters* — devices spanned, sharded rounds,
    padding waste from the lane-multiple rule — and assert identical phi;
    wall-clock speedups only mean something on a real mesh.

    Timing is ONE cold end-to-end run per row: an out-of-core
    decomposition of a massive graph is a one-shot workload, so trace +
    compile time is part of what the user waits for.  That makes the
    ``compiles`` column load-bearing — the sharded path's shape ladder
    (DESIGN.md §13) pins bucket shapes run-wide so the pod compiles O(1)
    executables, while the single-device path re-traces every pow4
    shape class it meets; ``speedup_vs_1dev`` is dominated by that
    dispatch-chain gap (virtual host devices share the physical cores,
    so lane parallelism itself cannot show up in CPU wall-clock).
    """
    from benchmarks.datasets import load
    from repro.core.bottom_up import bottom_up_decompose

    n_dev = len(jax.devices())
    mesh = jax.make_mesh((n_dev,), ("data",))
    names = ["hep-like"] if smoke else ["hep-like", "amazon-like",
                                        "wiki-like"]
    for name in names:
        jax.clear_caches()      # per-graph cold-run isolation
        n, edges = load(name)
        budget = max(len(edges) // 32, 1024)
        uss, res_s = _time(lambda: bottom_up_decompose(
            n, edges, budget, mesh=mesh))
        usb, res_b = _time(lambda: bottom_up_decompose(n, edges, budget))
        assert (res_s.phi == res_b.phi).all()
        st = res_s.stats
        emit(f"table4shard_{name}_TDbottomup_sharded", uss,
             f"devices={st.devices};sharded_rounds={st.sharded_rounds};"
             f"rounds={res_s.rounds};batches={st.batches};"
             f"compiles={st.compiles};padding_waste={st.padding_waste:.3f};"
             f"speedup_vs_1dev={usb/uss:.2f};budget={budget}",
             m=len(edges), budget=budget, devices=st.devices,
             sharded_rounds=st.sharded_rounds, rounds=res_s.rounds,
             scans=res_s.scans, batches=st.batches, compiles=st.compiles,
             overlapped=st.overlapped, padding_waste=st.padding_waste,
             speedup_vs_1dev=usb / uss)
        emit(f"table4shard_{name}_TDbottomup_1dev", usb,
             f"rounds={res_b.rounds};"
             f"padding_waste={res_b.stats.padding_waste:.3f}",
             m=len(edges), budget=budget, rounds=res_b.rounds,
             compiles=res_b.stats.compiles,
             padding_waste=res_b.stats.padding_waste)


def table4_kernel(smoke: bool = False):
    """Fused frontier-peel kernel rows (DESIGN.md §13).

    Two row kinds:

    * ``table4kernel_micro_*`` — one pow2-padded bucket of R-MAT lanes
      peeled by the fused Pallas kernel (interpret mode off-TPU —
      correctness-scaled, NOT a TPU wall-time) vs the XLA vmapped
      frontier engine on identical lanes, phi asserted equal.
    * ``table4kernel_rmat_*`` — the batched bottom-up driver on an R-MAT
      graph small enough for the python serial oracle: single device vs
      the full local device mesh — one single-axis row and, when the
      device count factors, one multi-axis (lane, tri) row (DESIGN.md
      §13) — phi pinned to ``alg2_truss``, with ``speedup_vs_1dev``,
      ``compiles`` and ``padding_waste`` recorded per mesh row.
    """
    from repro.core import graph as glib
    from repro.core.bottom_up import bottom_up_decompose
    from repro.core.peel import _peel_classes_vmapped
    from repro.core.serial import alg2_truss
    from repro.core.support import (list_triangles_np,
                                    support_from_triangle_list,
                                    triangle_incidence_np)
    from repro.data import graphgen
    from repro.kernels.frontier_peel import ops as fops

    # --- micro bucket: fused (interpret) vs the XLA frontier engine
    cap_e, B = 512, 4
    sup_b = np.zeros((B, cap_e), np.int32)
    alive_b = np.zeros((B, cap_e), np.int32)
    tris_l, incs = [], []
    for i in range(B):
        n_l, e_l = graphgen.rmat(6, 3, seed=20 + i)
        ce = glib.canonical_edges(e_l, n_l)[: cap_e]
        m = len(ce)
        g = glib.build_graph(n_l, ce)
        tris = np.asarray(list_triangles_np(g), np.int64).reshape(-1, 3)
        sup_b[i, :m] = support_from_triangle_list(tris, m)
        alive_b[i, :m] = 1
        tris_l.append(np.asarray(tris, np.int32))
    t_max = max(max(len(t) for t in tris_l), 1)
    tris_b = np.full((B, t_max, 3), cap_e, np.int32)
    for i, t in enumerate(tris_l):
        tris_b[i, : len(t)] = t
        incs.append(triangle_incidence_np(tris_b[i], cap_e))
    indptr_b = np.stack([ip for ip, _ in incs])
    l_max = max(max(len(ti) for _, ti in incs), 1)
    tids_b = np.zeros((B, l_max), np.int32)
    for i, (_, ti) in enumerate(incs):
        tids_b[i, : len(ti)] = ti
    cap_t = 1
    while cap_t < 3 * t_max:
        cap_t *= 2

    bt = fops.resolve_tile(cap_e, t_max, "auto", True)
    us_f, (phi_f, _) = _time(
        lambda: jax.block_until_ready(
            fops.peel_classes_fused(sup_b, tris_b, alive_b,
                                    bt=bt, interpret=True)),
        repeats=2)
    us_x, (phi_x, _) = _time(
        lambda: jax.block_until_ready(_peel_classes_vmapped(
            jnp.asarray(sup_b), jnp.asarray(tris_b), jnp.asarray(indptr_b),
            jnp.asarray(tids_b), jnp.asarray(alive_b),
            cap_f=cap_e, cap_t=cap_t)),
        repeats=2)
    assert (np.asarray(phi_f) == np.asarray(phi_x)).all()
    interp = jax.default_backend() != "tpu"
    emit("table4kernel_micro_fused" + ("_interp" if interp else ""), us_f,
         f"B={B};cap_e={cap_e};T={t_max};bt={bt};"
         f"fused_vs_xla={us_x/us_f:.3f}",
         B=B, cap_e=cap_e, triangles=t_max, bt=bt, interpret=interp,
         fused_vs_xla=us_x / us_f)
    emit("table4kernel_micro_xla_frontier", us_x,
         f"cap_f={cap_e};cap_t={cap_t}", B=B, cap_e=cap_e, cap_t=cap_t)

    # --- driver rows: 1dev vs the local mesh, phi vs the serial oracle
    n, edges = graphgen.rmat(10, 6, seed=7)
    ce = glib.canonical_edges(edges, n)
    oracle = alg2_truss(n, ce)
    budget = max(len(ce) // 32, 256)
    n_dev = len(jax.devices())
    meshes = [(jax.make_mesh((n_dev,), ("data",)), "data", f"mesh{n_dev}")]
    if n_dev >= 4 and n_dev % 2 == 0:
        meshes.append((jax.make_mesh((2, n_dev // 2), ("data", "tri")),
                       ("data", "tri"), f"mesh2x{n_dev // 2}"))
    # one COLD end-to-end run per row (same contract as table4shard): the
    # OOC workload is one-shot, so the single-device trace/compile churn
    # vs the sharded shape ladder's O(1) executables is exactly what
    # speedup_vs_1dev should see
    us1, r1 = _time(lambda: bottom_up_decompose(n, ce, budget))
    assert (r1.phi == oracle).all()
    for mesh, axes, kind in meshes:
        uss, rs = _time(lambda: bottom_up_decompose(
            n, ce, budget, mesh=mesh, mesh_axis=axes))
        assert (rs.phi == oracle).all()
        st = rs.stats
        emit(f"table4kernel_rmat10_TDbottomup_{kind}", uss,
             f"devices={st.devices};sharded_rounds={st.sharded_rounds};"
             f"compiles={st.compiles};"
             f"padding_waste={st.padding_waste:.3f};"
             f"speedup_vs_1dev={us1/uss:.2f};budget={budget}",
             m=len(ce), budget=budget, devices=st.devices,
             sharded_rounds=st.sharded_rounds, compiles=st.compiles,
             padding_waste=st.padding_waste, speedup_vs_1dev=us1 / uss)
    emit("table4kernel_rmat10_TDbottomup_1dev", us1,
         f"rounds={r1.rounds};"
         f"padding_waste={r1.stats.padding_waste:.3f}",
         m=len(ce), budget=budget, rounds=r1.rounds,
         padding_waste=r1.stats.padding_waste)


def table4_resilience(smoke: bool = False):
    """Crash-safety cost model (DESIGN.md §12): the batched bottom-up
    engine with round journaling at ``checkpoint_every=1`` (every completed
    partition round and class level snapshotted) vs the unjournaled run,
    plus a fault-injected run (one device OOM in each stage) exercising the
    retry ladder.

    The ``checkpoint_overhead`` column is the journaled run's wall-clock
    overhead fraction — the acceptance target is < 0.15 at every-round
    granularity on the smoke rows; ``retries`` / ``degraded`` /
    ``checkpoints`` record the recovery counters.  phi is asserted
    identical across all three runs.
    """
    import shutil
    import tempfile

    from benchmarks.datasets import load
    from repro.core import faults
    from repro.core.bottom_up import bottom_up_decompose

    names = ["hep-like"] if smoke else ["hep-like", "amazon-like",
                                        "wiki-like"]
    for name in names:
        n, edges = load(name)
        budget = max(len(edges) // 32, 1024)
        usb, res = _time(lambda: bottom_up_decompose(n, edges, budget),
                         repeats=2)

        def journaled():
            d = tempfile.mkdtemp(prefix="bench_ckpt_")
            try:
                return bottom_up_decompose(n, edges, budget,
                                           checkpoint_dir=d,
                                           checkpoint_every=1)
            finally:
                shutil.rmtree(d, ignore_errors=True)

        usj, res_j = _time(journaled, repeats=2)
        assert (res_j.phi == res.phi).all()
        overhead = max(usj - usb, 0.0) / usb
        st = res_j.stats
        emit(f"table4resil_{name}_TDbottomup_journaled", usj,
             f"checkpoint_overhead={overhead:.3f};"
             f"checkpoints={st.checkpoints};rounds={res_j.rounds};"
             f"budget={budget}",
             m=len(edges), budget=budget, rounds=res_j.rounds,
             checkpoints=st.checkpoints, checkpoint_overhead=overhead,
             retries=st.retries, degraded=st.degraded)

        def faulted():
            plan = faults.FaultPlan([
                faults.FaultRule(site=faults.DISPATCH, kind="oom",
                                 where={"stage": 1}, times=1),
                faults.FaultRule(site=faults.DISPATCH, kind="oom",
                                 where={"stage": 2}, times=1),
            ])
            with faults.active(plan):
                return bottom_up_decompose(n, edges, budget)

        usf, res_f = _time(faulted)
        assert (res_f.phi == res.phi).all()
        st_f = res_f.stats
        assert st_f.retries >= 2, st_f
        emit(f"table4resil_{name}_TDbottomup_oom_injected", usf,
             f"retries={st_f.retries};degraded={st_f.degraded};"
             f"slowdown_vs_clean={usf/usb:.2f};budget={budget}",
             m=len(edges), budget=budget, retries=st_f.retries,
             degraded=st_f.degraded, checkpoints=st_f.checkpoints,
             slowdown_vs_clean=usf / usb)


def table4_disk(smoke: bool = False):
    """Out-of-core graph STORAGE rows (DESIGN.md §15): the batched
    bottom-up engine with every graph array behind a ChunkedDiskStore
    capped at 1/8 of the packed graph's bytes, vs the same run with the
    graph host-resident.

    The acceptance row: phi bit-identical, store-resident graph bytes
    never exceed the budget, bytes actually spilled (the chunk-wise
    ``remove_edges`` makes aliased chunks free), and the background
    prefetcher serving at least half of all chunk requests — the counters
    land in the ``table4disk`` rows of ``BENCH_ooc.json``.
    """
    import shutil
    import tempfile

    from benchmarks.datasets import load
    from repro.core.bottom_up import bottom_up_decompose
    from repro.core.graph import build_graph
    from repro.core.store import ChunkedDiskStore

    names = ["hep-like"] if smoke else ["hep-like", "amazon-like",
                                        "wiki-like"]
    for name in names:
        jax.clear_caches()      # per-graph cold-run isolation
        n, edges = load(name)
        budget = max(len(edges) // 32, 1024)
        g = build_graph(n, edges)
        graph_bytes = sum(
            int(getattr(g, a).nbytes)
            for a in ("edges", "deg", "rank", "src", "dst", "indptr",
                      "nbrs", "nbr_eid"))
        host_budget = graph_bytes // 8          # the paper's regime: RAM
        chunk_bytes = max(host_budget // 16, 4096)   # keep a real window
        usb, res_b = _time(lambda: bottom_up_decompose(n, edges, budget))
        d = tempfile.mkdtemp(prefix="bench_store_")
        try:
            store = ChunkedDiskStore(d, host_memory_budget=host_budget,
                                     chunk_bytes=chunk_bytes)
            with store:
                usd, res_d = _time(lambda: bottom_up_decompose(
                    n, edges, budget, store=store))
                peak = store.stats.peak_resident_bytes
        finally:
            shutil.rmtree(d, ignore_errors=True)
        assert (res_d.phi == res_b.phi).all()
        st = res_d.stats
        hit_rate = st.prefetch_hit_rate
        assert st.bytes_spilled > 0, st
        assert peak <= host_budget, (peak, host_budget)
        assert hit_rate >= 0.5, (hit_rate, st)
        emit(f"table4disk_{name}_TDbottomup_diskstore", usd,
             f"graph_bytes={graph_bytes};host_budget={host_budget};"
             f"spilled={st.bytes_spilled};reads={st.chunk_reads};"
             f"writes={st.chunk_writes};hit_rate={hit_rate:.3f};"
             f"peak_resident={peak};slowdown_vs_inmem={usd/usb:.2f};"
             f"budget={budget}",
             m=len(edges), budget=budget, graph_bytes=graph_bytes,
             host_memory_budget=host_budget, chunk_bytes=chunk_bytes,
             chunk_reads=st.chunk_reads, chunk_writes=st.chunk_writes,
             bytes_spilled=st.bytes_spilled,
             prefetch_hits=st.prefetch_hits,
             prefetch_misses=st.prefetch_misses,
             prefetch_hit_rate=hit_rate, peak_resident_bytes=peak,
             rounds=res_d.rounds, checkpoints=st.checkpoints,
             slowdown_vs_inmem=usd / usb)
        emit(f"table4disk_{name}_TDbottomup_inmem_ref", usb,
             f"rounds={res_b.rounds};graph_bytes={graph_bytes}",
             m=len(edges), budget=budget, graph_bytes=graph_bytes,
             rounds=res_b.rounds)


def table5_top_down():
    from benchmarks.datasets import MEDIUM, load
    from repro.core.bottom_up import bottom_up_decompose
    from repro.core.top_down import top_down_decompose

    for name in MEDIUM:
        n, edges = load(name)
        budget = max(len(edges) // 8, 1024)
        ust, res_t = _time(lambda: top_down_decompose(n, edges, t=5))
        usa, res_a = _time(lambda: top_down_decompose(n, edges))
        usb, res_b = _time(lambda: bottom_up_decompose(n, edges, budget))
        for k in res_t.classes:
            assert (res_t.phi == k).sum() == (res_b.phi == k).sum()
        emit(f"table5_{name}_TDtopdown_top5", ust,
             f"classes={res_t.classes};cand={max(res_t.candidate_sizes or [0])}")
        emit(f"table5_{name}_TDtopdown_all", usa,
             f"kmax={res_a.kmax};pruned={res_a.pruned}")
        emit(f"table5_{name}_TDbottomup_all", usb,
             f"top5_speedup_vs_bottomup={usb/ust:.2f}")


def table5_maintenance(smoke: bool = False):
    """Incremental maintenance vs full recompute (DESIGN.md §16).

    For each rmat benchmark graph and edit-batch size b, a random batch of
    b edits (half deletions of existing edges, the rest insertions of new
    ones; b=1 is the paper's streaming single-insert case) is applied with
    :func:`truss_maintain` against a precomputed phi, and the wall-clock is
    compared with the fastest recompute available (the in-memory bulk
    peel) on the final edge set.  phi is asserted bit-identical to the
    recompute — the differential suite pins the same equality across the
    conformance corpus, this row pins it at benchmark scale and prices it.

    The acceptance row: ``speedup_vs_recompute >= 5`` at b=1 (gated in
    CI from ``BENCH_maint.json``).  Speedup decays with b — maintenance
    is sequential-exact, so cost is linear in b while the recompute is
    flat — and the crossover batch size is exactly what the column
    communicates.
    """
    from benchmarks.datasets import load
    from repro.core.maintain import truss_maintain
    from repro.core.peel import truss_decompose

    names = ["hep-like"] if smoke else ["hep-like", "amazon-like"]
    batches = (1, 8) if smoke else (1, 8, 64)
    for name in names:
        jax.clear_caches()
        n, edges = load(name)
        # the maintained state: NOT timed into either side of the row
        phi0 = truss_decompose(n, edges)
        present = {tuple(e) for e in np.asarray(edges).tolist()}
        rng = np.random.default_rng(9)
        for b in batches:
            n_del = b // 2
            steps = [("delete", int(u), int(v))
                     for u, v in (edges[i] for i in rng.choice(
                         len(edges), n_del, replace=False))]
            while len(steps) < b:
                u, v = (int(x) for x in rng.integers(0, n, 2))
                lo, hi = min(u, v), max(u, v)
                if lo == hi or (lo, hi) in present:
                    continue
                present.add((lo, hi))
                steps.append(("insert", lo, hi))
            us_m, res = _time(lambda: truss_maintain((n, edges), phi0,
                                                     steps))
            us_r, phi_r = _time(
                lambda: truss_decompose(res.graph.n, res.graph.edges))
            assert (res.phi == phi_r).all()
            st = res.stats
            emit(f"table5maint_{name}_maintain_b{b}", us_m,
                 f"m={len(edges)};edits={st.edits_applied};"
                 f"levels={st.maintain_levels};"
                 f"affected={st.affected_edges};"
                 f"speedup_vs_recompute={us_r/us_m:.2f}",
                 m=len(edges), batch=b, edits_applied=st.edits_applied,
                 maintain_levels=st.maintain_levels,
                 affected_edges=st.affected_edges,
                 speedup_vs_recompute=us_r / us_m)
            emit(f"table5maint_{name}_recompute_b{b}", us_r,
                 f"m={res.graph.m}", m=res.graph.m, batch=b)


def table6_truss_vs_core():
    from benchmarks.datasets import MEDIUM, SMALL, load
    from repro.core.graph import clustering_coefficient, incident_vertices
    from repro.core.kcore import cmax_core
    from repro.core.peel import kmax_truss

    for name in list(SMALL) + list(MEDIUM):
        n, edges = load(name)
        us, (kmax, t_edges) = _time(lambda: kmax_truss(n, edges))
        cmax, c_edges = cmax_core(n, edges)
        vt = len(incident_vertices(t_edges))
        vc = len(incident_vertices(c_edges))
        cct = clustering_coefficient(n, t_edges) if len(t_edges) else 0.0
        ccc = clustering_coefficient(n, c_edges) if len(c_edges) else 0.0
        emit(f"table6_{name}_kmaxtruss_vs_cmaxcore", us,
             f"VT/VC={vt}/{vc};ET/EC={len(t_edges)}/{len(c_edges)};"
             f"kmax/cmax={kmax}/{cmax};CCT/CCC={cct:.2f}/{ccc:.2f}")


def peel_engines(smoke: bool = False):
    """Frontier-compacted engine vs the seed dense engine (DESIGN.md §3).

    Same supports, same triangle list, identical phi asserted; the emitted
    counters show scatter work scaling with the frontier (gathered == 3T)
    instead of with rounds * 3T.
    """
    from benchmarks.datasets import MEDIUM, SMALL, load
    from repro.core.graph import build_graph
    from repro.core.peel import (_pick_engine, peel_classes,
                                 peel_classes_dense)
    from repro.core.support import (edge_support_jax, list_triangles_np,
                                    support_from_triangle_list,
                                    triangle_incidence_np)

    names = ["p2p-like"] if smoke else list(SMALL) + list(MEDIUM)
    for name in names:
        n, edges = load(name)
        g = build_graph(n, edges)
        tris = list_triangles_np(g)
        sup = support_from_triangle_list(tris, g.m).astype(np.int32)
        if len(tris) == 0:
            tris = np.full((1, 3), g.m, np.int32)
        supj = jnp.asarray(sup)
        trisj = jnp.asarray(tris)
        alivej = jnp.ones(g.m, bool)

        t0 = time.perf_counter()
        inc = triangle_incidence_np(tris, g.m)
        inc_us = (time.perf_counter() - t0) * 1e6

        def dense():
            phi, _ = peel_classes_dense(supj, trisj, alivej)
            return jax.block_until_ready(phi)

        def frontier():
            phi, _, st = peel_classes(supj, trisj, alivej, incidence=inc,
                                      with_stats=True)
            return jax.block_until_ready(phi), st

        us_d, phi_d = _time(dense, repeats=2)
        us_f, (phi_f, st) = _time(frontier, repeats=2)
        assert (np.asarray(phi_f) == np.asarray(phi_d)).all()
        # what the production entry points would pick
        auto = _pick_engine("auto", tris, g.m, with_stats=False)
        emit(f"peel_{name}_dense_seed", us_d,
             f"m={g.m};T={len(tris)}", m=g.m, triangles=int(len(tris)))
        emit(f"peel_{name}_frontier", us_f,
             f"speedup_vs_dense={us_d/us_f:.2f};rounds={st.rounds};"
             f"gathered={st.gathered};auto_picks={auto}",
             m=g.m, triangles=int(len(tris)),
             speedup_vs_dense=us_d / us_f, rounds=st.rounds,
             removed=st.removed, gathered=st.gathered,
             max_frontier=st.max_frontier, cap_f=st.cap_f, cap_t=st.cap_t,
             resumes=st.resumes, incidence_build_us=inc_us,
             auto_picks=auto)

        # skew-aware support vs the seed global-D wedge scan (§4)
        def sup_global():
            return jax.block_until_ready(edge_support_jax(g, bucketed=False))

        def sup_bucketed():
            return jax.block_until_ready(edge_support_jax(g, bucketed=True))

        us_g, s_g = _time(sup_global, repeats=2)
        us_b, s_b = _time(sup_bucketed, repeats=2)
        assert (np.asarray(s_g) == np.asarray(s_b)).all()
        emit(f"support_{name}_globalD_seed", us_g, f"D={g.max_out_deg}")
        emit(f"support_{name}_bucketed", us_b,
             f"speedup_vs_globalD={us_g/us_b:.2f}",
             speedup_vs_globalD=us_g / us_b)


def kernel_micro():
    from repro.core.graph import canonical_edges
    from repro.data import graphgen
    from repro.kernels.triangle_count.ops import (adjacency_from_edges,
                                                  dense_support)
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.embedding_bag.ops import embedding_bag

    rng = np.random.default_rng(0)
    n = 256
    edges = graphgen.erdos_renyi(n, 4000, seed=5)
    A = jnp.asarray(adjacency_from_edges(n, edges))
    us, S = _time(lambda: jax.block_until_ready(
        dense_support(A, block=128, interpret=True)), repeats=2)
    emit("kernel_triangle_count_256", us,
         f"triangles={float(np.asarray(S).sum())/6:.0f}")

    q = jnp.asarray(rng.standard_normal((1, 4, 256, 64)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((1, 2, 256, 64)).astype(np.float32))
    us, _ = _time(lambda: jax.block_until_ready(
        flash_attention(q, k, k, bq=128, bk=128, interpret=True)), repeats=2)
    emit("kernel_flash_attention_256", us, "GQA4:2,d64")

    tbl = jnp.asarray(rng.standard_normal((4096, 18)).astype(np.float32))
    idx = jnp.asarray(rng.integers(0, 4096, (64, 100)).astype(np.int32))
    us, _ = _time(lambda: jax.block_until_ready(
        embedding_bag(tbl, idx, interpret=True)), repeats=2)
    emit("kernel_embedding_bag_64x100", us, "din bag shape")


def roofline_summary():
    """Read dry-run results if present (launch/dryrun.py --out)."""
    import json
    import os
    path = os.environ.get("DRYRUN_JSON", "results/dryrun_all.json")
    if not os.path.exists(path):
        emit("roofline_summary_skipped", 0.0, f"no {path}")
        return
    with open(path) as f:
        recs = json.load(f)
    for r in recs:
        if not r.get("ok"):
            continue
        name = f"roofline_{r['arch']}_{r['shape']}_{r['mesh']}"
        t = max(r["t_compute"], r["t_memory"], r["t_collective"])
        emit(name, t * 1e6,
             f"bottleneck={r['bottleneck']};frac={r['roofline_fraction']:.3f}")


TABLES = {
    "table3": table3_inmemory,
    "table4": table4_bottom_up,
    "table4part": table4_partitioners,
    "table4shard": table4_sharded,
    "table4kernel": table4_kernel,
    "table4resil": table4_resilience,
    "table4disk": table4_disk,
    "table5": table5_top_down,
    "table5maint": table5_maintenance,
    "table6": table6_truss_vs_core,
    "peel": peel_engines,
    "kernel": kernel_micro,
    "roofline": roofline_summary,
}

# tables that accept smoke= (smallest-dataset variant); shared with hillclimb
SMOKE_TABLES = ("peel", "table4", "table4part", "table4shard",
                "table4kernel", "table4resil", "table4disk", "table5maint")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also write records as a JSON array (BENCH_*.json)")
    ap.add_argument("--only", action="append", default=None, metavar="PREFIX",
                    help="run only tables whose key starts with PREFIX "
                         "(repeatable); default: all")
    ap.add_argument("--smoke", action="store_true",
                    help="smallest-dataset smoke run of the peel and "
                         "table4 (OOC engine) comparisons")
    args = ap.parse_args(argv)

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    print("name,us_per_call,derived")
    if args.smoke and args.only is None:
        args.only = ["peel"]
    for key, fn in TABLES.items():
        if args.only is not None and not any(key.startswith(p)
                                             for p in args.only):
            continue
        if key in SMOKE_TABLES:
            fn(smoke=args.smoke)
        else:
            fn()
        # every row means to time a COLD one-shot run, so drop the compiled
        # executables between tables — it also keeps the process under
        # vm.max_map_count on full multi-graph sweeps (each XLA executable
        # holds tens of mappings; the per-part seed rows alone compile
        # thousands)
        jax.clear_caches()
    if args.json:
        with open(args.json, "w") as f:
            json.dump(ROWS, f, indent=1)
        print(f"# wrote {len(ROWS)} records to {args.json}", flush=True)


if __name__ == "__main__":
    main()
