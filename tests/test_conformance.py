"""Cross-engine conformance matrix (DESIGN.md §11).

One parametrized sweep pins every engine × partitioner × mesh
configuration to the ``serial.alg2_truss`` oracle on the shared
``conformance_corpus`` graphs, and asserts the ``OocStats`` invariants
that every out-of-core run must satisfy.  The in-memory engines (dense /
frontier) ignore partitioner and mesh, so each runs once; the out-of-core
engines sweep the full partitioner × mesh cross product.

The mesh configurations build over whatever devices the ambient process
has — 1 locally, 8 in the CI step that sets
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` before jax init —
the shard_map code path is identical either way (DESIGN.md §10).
"""

import warnings

import jax
import pytest

from repro.core.bottom_up import OocStats, bottom_up_decompose
from repro.core.partition import PartitionBudgetWarning
from repro.core.peel import truss_decompose
from repro.core.serial import alg2_truss, verify_truss
from repro.core.top_down import top_down_decompose
from tests.conftest import conformance_corpus

CORPUS = conformance_corpus()
_ORACLE = {name: alg2_truss(n, ce) for name, n, ce in CORPUS}

PARTITIONERS = ("sequential", "random", "locality")
MESHES = ("none", "devices", "devices2d")
# (engine, partitioner, mesh) rows that run: the in-memory engines once,
# the out-of-core engines over every partitioner and mesh
MATRIX = ([(engine, "sequential", "none") for engine in ("dense", "frontier")]
          + [(engine, partitioner, mesh_kind)
             for engine in ("bottom-up", "top-down")
             for partitioner in PARTITIONERS for mesh_kind in MESHES])


def _mesh(kind):
    """(mesh, mesh_axis) for a matrix row.  "devices2d" factors the same
    devices into a (lane, tri) grid (DESIGN.md §13) — (2, 4) under the CI
    step's 8 forced host devices, a degenerate (1, 1) locally."""
    if kind == "none":
        return None, "data"
    d = len(jax.devices())
    if kind == "devices":
        return jax.make_mesh((d,), ("data",)), "data"
    d0 = 1
    while (d0 * 2) ** 2 <= d and d % (d0 * 2) == 0:
        d0 *= 2
    return (jax.make_mesh((d0, d // d0), ("data", "tri")),
            ("data", "tri"))


def _check_ooc_stats(stats: OocStats, mesh, tag):
    """The invariants every out-of-core run's counters must satisfy."""
    assert stats is not None, tag
    assert stats.rounds >= 1, tag
    assert stats.parts >= 1, tag
    assert stats.scans >= stats.parts, tag
    assert 0 <= stats.tri_assigned <= stats.tri_total, tag
    assert 0.0 <= stats.tri_locality <= 1.0, tag
    assert stats.tri_est >= 0, tag
    assert stats.tri_est_error >= 0.0, tag
    assert stats.real_edges <= stats.padded_slots, tag
    assert 0.0 <= stats.padding_waste < 1.0, tag
    assert stats.ns_sweeps <= stats.rounds, tag
    assert stats.tri_routes == stats.ns_sweeps, tag
    assert 0 <= stats.stage2_overlapped <= stats.scans, tag
    assert stats.overlapped <= stats.rounds, tag
    expected_dev = 1 if mesh is None else len(jax.devices())
    assert stats.devices == expected_dev, tag
    if mesh is None:
        assert stats.sharded_rounds == 0, tag
        assert stats.lane_shards == 0, tag
    elif stats.lane_shards:
        # a mesh-split bucket's lanes span exactly the lane ("data") axis
        assert stats.lane_shards == mesh.shape["data"], tag


@pytest.mark.parametrize("engine,partitioner,mesh_kind", MATRIX,
                         ids=["-".join(row[::-1]) for row in MATRIX])
def test_conformance_matrix(engine, partitioner, mesh_kind):
    in_memory = engine in ("dense", "frontier")
    mesh, axes = _mesh(mesh_kind)
    for name, n, ce in CORPUS:
        oracle = _ORACLE[name]
        tag = (engine, partitioner, mesh_kind, name)
        kwargs = dict(engine=engine, with_stats=True)
        if not in_memory:
            kwargs.update(memory_budget=max(48, len(ce)),
                          partitioner=partitioner, mesh=mesh,
                          mesh_axes=axes if mesh_kind == "devices2d"
                          else None)
        with warnings.catch_warnings():
            # the star-hub graph legitimately warns at deep budgets
            warnings.simplefilter("ignore", PartitionBudgetWarning)
            phi, stats = truss_decompose(n, ce, **kwargs)
        assert (phi == oracle).all(), tag
        assert verify_truss(n, ce, phi), tag
        if not in_memory:
            _check_ooc_stats(stats, mesh, tag)
            if mesh is not None and stats.tri_total:
                # triangle-free work short-circuits on host (DESIGN.md
                # §10); anything else must have routed through shard_map
                assert stats.sharded_rounds > 0, tag


@pytest.mark.parametrize("partitioner", PARTITIONERS)
@pytest.mark.parametrize("mesh_kind", MESHES)
def test_conformance_drivers_direct(partitioner, mesh_kind):
    """The driver entry points (not just the unified dispatch) on a deep
    budget: phi equality plus the cross-driver stats contract."""
    mesh, axes = _mesh(mesh_kind)
    for name, n, ce in CORPUS:
        oracle = _ORACLE[name]
        tag = (partitioner, mesh_kind, name)
        budget = max(8, len(ce) // 4)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", PartitionBudgetWarning)
            res = bottom_up_decompose(n, ce, budget,
                                      partitioner=partitioner, mesh=mesh,
                                      mesh_axis=axes)
            td = top_down_decompose(n, ce, budget=budget,
                                    partitioner=partitioner, mesh=mesh,
                                    mesh_axis=axes)
        assert (res.phi == oracle).all(), tag
        _check_ooc_stats(res.stats, mesh, tag)
        assert (td.phi == oracle).all(), tag
        _check_ooc_stats(td.stats, mesh, tag)


@pytest.mark.parametrize("engine", ("bottom-up", "top-down"))
@pytest.mark.parametrize("store_kind", ("memory", "disk"))
@pytest.mark.parametrize("partitioner", ("sequential", "locality"))
@pytest.mark.parametrize("name,n,ce", CORPUS, ids=[c[0] for c in CORPUS])
def test_conformance_store_matrix(tmp_path, name, n, ce, engine, store_kind,
                                  partitioner):
    """``store=`` rows of the matrix (DESIGN.md §15): the same drivers over
    an InMemoryStore (behavioral no-op) and a ChunkedDiskStore (graph
    arrays spilled chunk-wise) must stay phi bit-identical to the oracle,
    and the disk rows must show real chunk I/O in the OocStats counters."""
    from repro.core.store import ChunkedDiskStore, InMemoryStore

    tag = ("store", engine, store_kind, partitioner, name)
    if store_kind == "memory":
        store = InMemoryStore()
    else:
        store = ChunkedDiskStore(str(tmp_path / "store"),
                                 chunk_bytes=1 << 10)
    with store, warnings.catch_warnings():
        warnings.simplefilter("ignore", PartitionBudgetWarning)
        phi, stats = truss_decompose(
            n, ce, engine=engine, memory_budget=max(48, len(ce)),
            partitioner=partitioner, store=store, with_stats=True)
    assert (phi == _ORACLE[name]).all(), tag
    assert verify_truss(n, ce, phi), tag
    _check_ooc_stats(stats, None, tag)
    if store_kind == "disk":
        assert stats.chunk_writes > 0, tag
        assert stats.bytes_spilled > 0, tag
        assert stats.chunk_reads > 0, tag
        total = stats.prefetch_hits + stats.prefetch_misses
        assert total > 0, tag
    else:
        assert stats.chunk_writes == stats.chunk_reads == 0, tag
        assert stats.bytes_spilled == 0, tag


@pytest.mark.parametrize("name,n,ce", CORPUS, ids=[c[0] for c in CORPUS])
def test_conformance_host_memory_budget_knob(name, n, ce):
    """The one-knob spelling: ``host_memory_budget=`` builds a scratch
    ChunkedDiskStore internally and must reproduce the oracle."""
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", PartitionBudgetWarning)
        phi, stats = truss_decompose(
            n, ce, engine="bottom-up", memory_budget=max(48, len(ce)),
            host_memory_budget=1 << 16, with_stats=True)
    assert (phi == _ORACLE[name]).all(), name
    assert stats.chunk_writes > 0, name


@pytest.mark.parametrize("engine", ("bottom-up", "top-down"))
@pytest.mark.parametrize("kernel", ("pallas", "auto"))
def test_conformance_kernel_knob(engine, kernel):
    """``kernel=`` rows of the matrix (DESIGN.md §13): the fused Pallas
    peel (interpret mode off-TPU) and the auto route against the oracle.
    Single-device only — the mesh path always takes the XLA shard_map
    engine, so kernel × mesh is not a meaningful cell."""
    for name, n, ce in CORPUS:
        oracle = _ORACLE[name]
        tag = ("kernel", engine, kernel, name)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", PartitionBudgetWarning)
            phi, stats = truss_decompose(
                n, ce, engine=engine, memory_budget=max(48, len(ce)),
                kernel=kernel, with_stats=True)
        assert (phi == oracle).all(), tag
        assert verify_truss(n, ce, phi), tag
        _check_ooc_stats(stats, None, tag)
