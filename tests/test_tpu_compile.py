"""Compile the main path's Pallas kernels for a described TPU v5e chip.

Interpret-mode parity (``test_frontier_peel_kernel.py``, ``test_kernels.py``)
cannot see what only the chip's compiler refuses: block shapes that break
the Mosaic tiling rule, or more VMEM than a kernel may use.  These tests
lower and compile each kernel at the shapes ``chip_smoke.py`` drives —
tiles from ``resolve_tile`` / ``feasible_tiles`` — for one chip of a
``v5e:2x2`` topology that is described, not attached, and check that the
kernel survived as a ``tpu_custom_call``; the in-memory XLA frontier loop
compiles at the in-memory benchmark graph's shapes.  Nothing runs.

The topology is described inside a fixture (never at import), so every
pytest-xdist worker collects the same tests and only the worker given this
file loads the TPU compiler.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import distributed, peel
from repro.kernels.frontier_peel import kernel as fk
from repro.kernels.frontier_peel import ops
from repro.kernels.triangle_count.kernel import triangle_count_kernel

# smoke-scale lane shapes: the bottom-up engine's Pallas buckets hold
# cap_e = 4096 edge slots and pow2 triangle capacities (chip_smoke.py (c))
CAP_E = 4096
CAP_T = 65536
# frontier capacities peel_classes_batched derives for such a bucket
CAP_F = 512
CAP_INC = 16384
# the in-memory benchmark graph (Graph 500 scale 13) and the frontier
# capacities _default_caps gives it
INMEM_M = 102075
INMEM_T = 1174267
INMEM_CAP_F = 4096
INMEM_CAP_T = 65536


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        # the TPU compiler logs under /tmp unless told otherwise
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip can be written to the persistent
        # cache but never read back without the chip: keep it out
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("lanes", [1, 8])
def test_fused_round_compiles(one_chip, lanes):
    bt = ops.resolve_tile(CAP_E, CAP_T, "auto", False)
    assert bt in fk.feasible_tiles(CAP_E, CAP_T)
    row = _spec(one_chip, (lanes, CAP_E))
    fn = jax.jit(lambda s, a, r, t: fk.fused_round(s, a, r, t, bt=bt))
    compiled = fn.lower(row, row, row,
                        _spec(one_chip, (lanes, CAP_T, 3))).compile()
    _assert_kernel(compiled)


def _widest_auto_lane() -> int:
    """The largest cap_e that ``kernel="auto"`` still routes to the fused
    kernel on a TPU (a triangle-dense lane); only the smallest tile fits."""
    lo, hi = 128, 1 << 20
    assert ops.resolve_kernel("auto", lo, CAP_T, backend="tpu") == "pallas"
    while hi - lo > 1:
        mid = (lo + hi) // 2
        if ops.resolve_kernel("auto", mid, CAP_T, backend="tpu") == "pallas":
            lo = mid
        else:
            hi = mid
    return lo


@pytest.mark.parametrize("lane", ["smoke", "widest_auto"])
def test_fused_round_fits_its_vmem_model(one_chip, monkeypatch, lane):
    """``kernel_vmem_bytes`` is an upper bound of the compiler's scoped
    VMEM need: the kernel compiles with the model's bytes as its limit, at
    the smoke's lanes and at the widest lane "auto" sends to the kernel."""
    from jax.experimental.pallas import tpu as pltpu

    cap_e = CAP_E if lane == "smoke" else _widest_auto_lane()
    bt = ops.resolve_tile(cap_e, CAP_T, "auto", False)
    if lane == "widest_auto":
        assert bt == min(fk.DEFAULT_TILE_CANDIDATES)
    limit = fk.kernel_vmem_bytes(cap_e, bt)
    pallas_call = fk.pl.pallas_call

    def limited(*args, **kwargs):
        kwargs["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=limit)
        return pallas_call(*args, **kwargs)

    monkeypatch.setattr(fk.pl, "pallas_call", limited)
    row = _spec(one_chip, (8, cap_e))
    fn = jax.jit(lambda s, a, r, t: fk.fused_round(s, a, r, t, bt=bt))
    _assert_kernel(fn.lower(row, row, row,
                            _spec(one_chip, (8, CAP_T, 3))).compile())


def test_peel_classes_fused_loop_compiles(one_chip):
    bt = ops.resolve_tile(CAP_E, CAP_T, "auto", False)
    row = _spec(one_chip, (8, CAP_E))
    compiled = ops._peel_classes_fused_impl.lower(
        row, _spec(one_chip, (8, CAP_T, 3)), row,
        bt=bt, interpret=False).compile()
    _assert_kernel(compiled)


def test_peel_threshold_fused_loop_compiles(one_chip):
    bt = ops.resolve_tile(CAP_E, CAP_T, "auto", False)
    row = _spec(one_chip, (1, CAP_E))
    compiled = ops._peel_threshold_fused_impl.lower(
        row, _spec(one_chip, (1, CAP_T, 3)), row, row,
        _spec(one_chip, ()), bt=bt, interpret=False).compile()
    _assert_kernel(compiled)


def test_peel_classes_frontier_loop_compiles(one_chip):
    """The in-memory XLA frontier loop at the benchmark graph's shapes: one
    while loop, with no search loop inside its rounds."""
    m, T = INMEM_M, INMEM_T
    compiled = peel.peel_classes_fixedcap.lower(
        _spec(one_chip, (m,)), _spec(one_chip, (T, 3)),
        _spec(one_chip, (m + 1,)), _spec(one_chip, (3 * T,)),
        _spec(one_chip, (m,), jnp.bool_), _spec(one_chip, (m,)),
        _spec(one_chip, ()), _spec(one_chip, (peel.N_STATS,)),
        cap_f=INMEM_CAP_F, cap_t=INMEM_CAP_T).compile()
    assert compiled.as_text().count(" while(") == 1


def test_triangle_count_kernel_compiles(one_chip):
    fn = jax.jit(lambda a: triangle_count_kernel(a, bm=512, bn=512, bk=512))
    compiled = fn.lower(_spec(one_chip, (4096, 4096), jnp.float32)).compile()
    _assert_kernel(compiled)


# ---------------------------------------------------------------------------
# mesh programs (chip_smoke.py --chips 4) on the described 2x2 chips
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def meshes(topo):
    devs = np.array(topo.devices)
    auto = jax.sharding.AxisType.Auto
    return {"data": Mesh(devs.reshape(4), ("data",), axis_types=(auto,)),
            "data_tri": Mesh(devs.reshape(2, 2), ("data", "tri"),
                             axis_types=(auto,) * 2)}


def _named(mesh, shape, spec, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(mesh, spec))


def test_batched_sharded_compiles_lane_split(meshes):
    """One bucket's lanes split over a 4-chip "data" axis: each chip holds
    its own lane slice of the output."""
    mesh, B = meshes["data"], 8
    fn = distributed._batched_sharded_fn(mesh, "data", CAP_F, CAP_INC)
    lanes = P("data")
    compiled = fn.lower(
        _named(mesh, (B, CAP_E), lanes), _named(mesh, (B, CAP_T, 3), lanes),
        _named(mesh, (B, CAP_E + 1), lanes),
        _named(mesh, (B, 3 * CAP_T), lanes),
        _named(mesh, (B, CAP_E), lanes, jnp.bool_)).compile()
    phi_sharding = compiled.output_shardings[0]
    assert len(phi_sharding.device_set) == 4
    assert phi_sharding.shard_shape((B, CAP_E)) == (B // 4, CAP_E)


def test_batched_sharded2_compiles_with_all_reduce(meshes):
    """Lanes over "data", each lane's triangles over "tri": the per-lane
    support partials are all-reduced across the "tri" pair."""
    mesh, B, n_tri = meshes["data_tri"], 8, 2
    fn = distributed._batched_sharded2_fn(mesh, "data", "tri", CAP_F,
                                          CAP_INC)
    compiled = fn.lower(
        _named(mesh, (B, CAP_E), P("data")),
        _named(mesh, (B, CAP_T, 3), P("data", "tri")),
        _named(mesh, (B, n_tri, CAP_E + 1), P("data", "tri")),
        _named(mesh, (B, n_tri, 3 * CAP_T // n_tri), P("data", "tri")),
        _named(mesh, (B, CAP_E), P("data"), jnp.bool_)).compile()
    assert "all-reduce" in compiled.as_text()
    phi_sharding = compiled.output_shardings[0]
    assert phi_sharding.shard_shape((B, CAP_E)) == (B // 2, CAP_E)


@pytest.mark.parametrize("mesh_name", ["data", "data_tri"])
def test_threshold_sharded_compiles_with_all_reduce(meshes, mesh_name):
    """A candidate peel's triangles sharded over every chip, the edge state
    replicated and kept in step by all-reduces."""
    mesh = meshes[mesh_name]
    axis = tuple(mesh.axis_names) if len(mesh.axis_names) > 1 else "data"
    n = mesh.size
    fn = distributed._threshold_sharded_fn(mesh, axis, CAP_F, CAP_INC)
    compiled = fn.lower(
        _named(mesh, (CAP_E,), P()), _named(mesh, (CAP_T, 3), P(axis)),
        _named(mesh, (n, CAP_E + 1), P(axis)),
        _named(mesh, (n, 3 * CAP_T // n), P(axis)),
        _named(mesh, (CAP_E,), P(), jnp.bool_),
        _named(mesh, (CAP_E,), P(), jnp.bool_),
        _named(mesh, (), P())).compile()
    assert "all-reduce" in compiled.as_text()
