"""Pod-spanning OOC rounds (DESIGN.md §10): the batched engines with bucket
lanes routed through shard_map must produce phi identical to the
single-device batched engine (and the serial oracle).

The in-process tests run on a mesh over whatever devices the ambient
process has (1 locally; 8 in the CI sharded job, which sets
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` before jax init) —
the shard_map code path is identical either way.  The 8-device corpus
equality, the uneven-lane bucket split and the non-blocking double-buffered
round are additionally forced in a subprocess (device count locks at first
jax init), mirroring ``test_distributed.py``.
"""

import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.core import graph as glib
from repro.core.bottom_up import bottom_up_decompose
from repro.core.partition import build_partition_batch, sequential_partition
from repro.core.peel import (local_threshold_peel, peel_classes_batched,
                             truss_decompose)
from repro.core.serial import alg2_truss
from repro.core.support import list_triangles_np, support_from_triangle_list
from repro.core.top_down import top_down_decompose
from tests.conftest import er_graph, random_graph

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def mesh():
    return jax.make_mesh((len(jax.devices()),), ("data",))


def _graph(rng, n=26, p=0.3):
    n, ce = er_graph(rng, n, p)
    assert len(ce) >= 3
    return ce, n


def test_bottom_up_sharded_matches_oracle_and_single(rng, mesh):
    ce, n = _graph(rng)
    oracle = alg2_truss(n, ce)
    budget = max(8, len(ce) // 4)
    res_s = bottom_up_decompose(n, ce, budget, mesh=mesh)
    res_1 = bottom_up_decompose(n, ce, budget)
    assert (res_s.phi == oracle).all()
    assert (res_s.phi == res_1.phi).all()
    # the double-buffered (blocking=False) path IS the driver's only path,
    # so overlapped rounds prove the PendingPeel pipeline ran sharded
    assert res_s.stats.sharded_rounds > 0
    assert res_s.stats.devices == len(jax.devices())
    assert res_1.stats.sharded_rounds == 0 and res_1.stats.devices == 1
    # the stage-2 candidate pipeline (DESIGN.md §11) is control-flow
    # identical across the mesh: same levels prebuilt either way
    assert res_s.stats.stage2_overlapped == res_1.stats.stage2_overlapped


def test_top_down_sharded_matches_oracle(rng, mesh):
    ce, n = _graph(rng)
    oracle = alg2_truss(n, ce)
    budget = max(8, len(ce) // 4)
    td = top_down_decompose(n, ce, budget=budget, mesh=mesh)
    assert (td.phi == oracle).all()
    assert td.stats.sharded_rounds > 0
    assert td.stats.devices == len(jax.devices())
    # without a budget the candidate peels still span the mesh
    td2 = top_down_decompose(n, ce, mesh=mesh)
    assert (td2.phi == oracle).all()
    assert td2.stats.sharded_rounds > 0


def test_truss_decompose_mesh_dispatch(rng, mesh):
    ce, n = _graph(rng)
    oracle = alg2_truss(n, ce)
    for engine in ("bottom-up", "top-down"):
        phi, st = truss_decompose(n, ce, engine=engine, memory_budget=48,
                                  mesh=mesh, with_stats=True)
        assert (phi == oracle).all(), engine
        assert st.sharded_rounds > 0, engine


def test_bucket_sharded_matches_single_device(rng, mesh):
    """Direct bucket-level equality, including uneven lane counts: with
    ``pad_lanes_pow2=False`` the lane count is whatever the packer produced,
    so the sharded dispatcher must pad to a device multiple and slice the
    result back to the caller's B."""
    ce, n = _graph(rng, n=40)
    g = glib.build_graph(n, ce)
    parts = sequential_partition(g, budget=max(8, len(ce) // 6))
    batch = build_partition_batch(g, parts, pad_lanes_pow2=False)
    assert batch.buckets
    for bucket in batch.buckets:
        phi_s, st_s, _ = peel_classes_batched(
            bucket.sup, bucket.tris, bucket.indptr, bucket.tids,
            bucket.alive, mesh=mesh)
        phi_1, st_1, _ = peel_classes_batched(
            bucket.sup, bucket.tris, bucket.indptr, bucket.tids,
            bucket.alive)
        assert phi_s.shape == phi_1.shape == bucket.sup.shape
        assert (phi_s == phi_1).all()
        assert st_s.shape == st_1.shape


def test_sharded_nonblocking_pending(rng, mesh):
    ce, n = _graph(rng)
    g = glib.build_graph(n, ce)
    parts = sequential_partition(g, budget=max(8, len(ce) // 3))
    batch = build_partition_batch(g, parts)
    bucket = max(batch.buckets, key=lambda b: b.real_edges)
    handle = peel_classes_batched(
        bucket.sup, bucket.tris, bucket.indptr, bucket.tids, bucket.alive,
        mesh=mesh, blocking=False)
    phi_ref, _, _ = peel_classes_batched(
        bucket.sup, bucket.tris, bucket.indptr, bucket.tids, bucket.alive)
    phi, st = handle.result()
    assert handle.sharded
    assert (phi == phi_ref).all()
    # result is cached, not re-finalized
    assert handle.result()[0] is phi


def test_local_threshold_peel_sharded_matches(rng, mesh):
    ce, n = _graph(rng, n=24, p=0.4)
    g = glib.build_graph(n, ce)
    tris = list_triangles_np(g)
    sup = support_from_triangle_list(tris, g.m).astype(np.int32)
    removable = rng.random(g.m) < 0.7
    for thresh in (0, 1, 2, 4):
        alive_s, rem_s, _ = local_threshold_peel(
            sup, tris, removable, thresh, mesh=mesh)
        alive_1, rem_1, _ = local_threshold_peel(
            sup, tris, removable, thresh)
        assert (alive_s == alive_1).all(), thresh
        assert (rem_s == rem_1).all(), thresh


def test_shard_incidence_width_follows_the_padded_shape(rng):
    """Two candidates padded to one shape get incidence CSRs of one shape,
    whatever their triangles, so the sharded peel compiled for the first
    serves the second: each shard's width is the most it can hold, 3 T / S.
    """
    from repro.core.distributed import (shard_incidence,
                                        shard_incidence_lanes)

    m, T, S = 40, 16, 4
    dense = rng.integers(0, m, size=(T, 3)).astype(np.int32)
    sparse = np.full((T, 3), m, np.int32)       # drop-slot rows after two
    sparse[:2] = dense[:2]
    for tris in (dense, sparse):
        indptr_s, tids_s = shard_incidence(tris, m, S)
        assert indptr_s.shape == (S, m + 1)
        assert tids_s.shape == (S, 3 * T // S)
        indptr_ls, tids_ls = shard_incidence_lanes(
            np.stack([tris, dense]), m, S)
        assert tids_ls.shape == (2, S, 3 * T // S)
        # the slots past each shard's own incidence stay zero
        counts = indptr_s[:, -1]
        assert all((tids_s[i, c:] == 0).all() for i, c in enumerate(counts))


# ---------------------------------------------------------------------------
# forced 8-device corpus equality (subprocess: device count locks at init)
# ---------------------------------------------------------------------------

def _run(code: str, timeout=560, devices=8):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env, cwd=_ROOT)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    return p.stdout


def test_sharded_rounds_8_devices():
    """phi-equality vs the single-device batched engine on a corpus shaped
    like the test_ooc_property graphs, with 8 real shards: both drivers,
    two partitioners, a non-blocking round and an uneven-lane bucket."""
    out = _run("""
        import jax, numpy as np
        mesh = jax.make_mesh((8,), ("data",))
        from repro.core import graph as glib
        from repro.core.serial import alg2_truss
        from repro.core.bottom_up import bottom_up_decompose
        from repro.core.top_down import top_down_decompose
        from repro.core.partition import (build_partition_batch,
                                          sequential_partition)
        from repro.core.peel import peel_classes_batched
        rng = np.random.default_rng(7)
        for trial, (n, dens) in enumerate([(20, 0.35), (26, 0.25)]):
            iu = np.triu_indices(n, 1)
            keep = rng.random(len(iu[0])) < dens
            ce = glib.canonical_edges(np.stack(iu, 1)[keep], n)
            oracle = alg2_truss(n, ce)
            budget = max(8, len(ce) // 4)
            part = ("sequential", "locality")[trial % 2]
            res_s = bottom_up_decompose(n, ce, budget, partitioner=part,
                                        mesh=mesh)
            res_1 = bottom_up_decompose(n, ce, budget, partitioner=part)
            assert (res_s.phi == oracle).all()
            assert (res_s.phi == res_1.phi).all()
            assert res_s.stats.sharded_rounds > 0
            assert res_s.stats.devices == 8
            # the first sharded bucket's output holds 8 distinct lane slices
            assert res_s.stats.lane_shards == 8
            assert res_1.stats.lane_shards == 0
            td = top_down_decompose(n, ce, budget=budget, mesh=mesh)
            assert (td.phi == oracle).all()
            assert td.stats.sharded_rounds > 0
        # uneven lane count: the dispatcher pads to a multiple of 8 and
        # slices back; a non-blocking handle drives the same path
        g = glib.build_graph(n, ce)
        parts = sequential_partition(g, budget=max(8, len(ce) // 6))
        batch = build_partition_batch(g, parts, pad_lanes_pow2=False)
        uneven = [b for b in batch.buckets if b.n_lanes % 8]
        assert uneven, [b.n_lanes for b in batch.buckets]
        for bucket in uneven:
            h = peel_classes_batched(
                bucket.sup, bucket.tris, bucket.indptr, bucket.tids,
                bucket.alive, mesh=mesh, blocking=False)
            phi_1, _, _ = peel_classes_batched(
                bucket.sup, bucket.tris, bucket.indptr, bucket.tids,
                bucket.alive)
            phi_s, _ = h.result()
            assert h.sharded
            assert h.lane_split[0] == 8
            assert h.lane_split[0] * h.lane_split[1] == -(-bucket.n_lanes
                                                          // 8) * 8
            assert phi_s.shape == phi_1.shape
            assert (phi_s == phi_1).all()
        # a (data, tri) 4x2 mesh splits lanes over "data" only: 4 lane
        # slices, each held by the 2 devices of its "tri" row
        mesh2 = jax.make_mesh((4, 2), ("data", "tri"))
        res_2 = bottom_up_decompose(n, ce, budget, mesh=mesh2,
                                    mesh_axis=("data", "tri"))
        assert (res_2.phi == oracle).all()
        assert res_2.stats.lane_shards == 4
        print("SHARDED-OOC-OK")
    """)
    assert "SHARDED-OOC-OK" in out


def test_mesh_run_counts_its_uploads_and_dead_lanes_4_devices():
    """Over a 4-device mesh every dispatch copies its own (padded) inputs,
    and they count in ``h2d_bytes``: at least the one-device batched run's
    bytes on the same graph.  The lanes split over the mesh and the dead
    ones among them are counted."""
    out = _run("""
        import jax, numpy as np
        from repro.core.peel import truss_decompose
        from repro.data import graphgen
        n, ce = graphgen.rmat(7, 16, seed=0, a=0.57, b=0.19, c=0.19)
        kw = dict(engine="bottom-up", memory_budget=2048, kernel="xla",
                  with_stats=True)
        phi_1, one = truss_decompose(n, ce, **kw)
        mesh = jax.make_mesh((4,), ("data",))
        phi_4, four = truss_decompose(n, ce, mesh=mesh, **kw)
        assert (phi_1 == phi_4).all()
        assert (four.devices, four.lane_shards) == (4, 4)
        assert four.retries == four.degraded == 0
        assert four.h2d_bytes >= one.h2d_bytes > 0
        assert four.mesh_lanes % 4 == 0 and four.mesh_lanes > 0
        assert 0 <= four.mesh_pad_lanes < four.mesh_lanes
        assert one.mesh_lanes == one.mesh_pad_lanes == 0
        print("MESH-H2D-OK", one.h2d_bytes, four.h2d_bytes,
              four.mesh_lanes, four.mesh_pad_lanes)
    """, devices=4)
    assert "MESH-H2D-OK" in out


# ---------------------------------------------------------------------------
# hypothesis sweep (CI): the test_ooc_property corpus, sharded vs single
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as st
    _HAVE_HYPOTHESIS = True
except ImportError:                                   # pragma: no cover
    _HAVE_HYPOTHESIS = False

if _HAVE_HYPOTHESIS:

    @st.composite
    def graphs(draw, max_n=26):
        # same corpus shape as tests/test_ooc_property.py
        n = draw(st.integers(4, max_n))
        density = draw(st.floats(0.1, 0.6))
        seed = draw(st.integers(0, 2**31 - 1))
        g_rng = np.random.default_rng(seed)
        iu = np.triu_indices(n, 1)
        keep = g_rng.random(len(iu[0])) < density
        return n, np.stack(iu, 1)[keep]

    @settings(max_examples=8, deadline=None)
    @given(graphs(), st.sampled_from(["sequential", "locality"]),
           st.sampled_from([0.2, 0.5]))
    def test_sharded_property_corpus(g, partitioner, budget_frac):
        n, edges = g
        ce = glib.canonical_edges(edges, n)
        if len(ce) < 3:
            return
        mesh = jax.make_mesh((len(jax.devices()),), ("data",))
        budget = max(4, int(len(ce) * budget_frac))
        res_s = bottom_up_decompose(n, ce, budget, partitioner=partitioner,
                                    mesh=mesh)
        res_1 = bottom_up_decompose(n, ce, budget, partitioner=partitioner)
        assert (res_s.phi == res_1.phi).all()
        assert (res_s.phi == alg2_truss(n, ce)).all()
        td = top_down_decompose(n, ce, budget=budget,
                                partitioner=partitioner, mesh=mesh)
        assert (td.phi == res_1.phi).all()
