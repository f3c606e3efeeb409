"""Pallas-vs-reference parity for the fused frontier-peel kernel.

The fused kernel (``kernels/frontier_peel``, DESIGN.md §13) computes one
WHOLE removal round per ``pallas_call``; these tests pin it — in interpret
mode, the CPU CI path — to the jnp reference (``ref.fused_round_ref``),
to the host reference peel (``ref.peel_classes_ref``), and to the XLA
frontier engine it replaces (``peel.peel_classes`` /
``peel.peel_threshold``), over a seeded sweep of cap / tile shapes
(the environment has no ``hypothesis``; the sweep is deterministic).

Layout pins: ``ops.N_STATS`` mirrors ``peel.N_STATS`` so the fused path's
stats rows drop into the batched engine's accounting unchanged.
"""

import jax
import numpy as np
import pytest

from repro.core import peel
from repro.core.support import (list_triangles_np, support_from_triangle_list,
                                triangle_density)
from repro.core import graph as glib
from repro.kernels.frontier_peel import kernel as fk
from repro.kernels.frontier_peel import ops, ref
from tests.conftest import random_graph


def _lane(rng, n, p, cap_e):
    """One padded lane: (sup, alive, tris) on ``cap_e`` edge slots from a
    random graph, triangles in local edge ids."""
    edges = glib.canonical_edges(random_graph(rng, n, p), n)
    m = len(edges)
    assert m <= cap_e
    g = glib.build_graph(n, edges)
    tris = np.asarray(list_triangles_np(g), np.int64).reshape(-1, 3)
    sup = np.zeros(cap_e, np.int32)
    sup[:m] = support_from_triangle_list(tris, m)
    alive = np.zeros(cap_e, np.int32)
    alive[:m] = 1
    return sup, alive, np.asarray(tris, np.int32), m


def _pad_to(tris, t_cap, cap_e):
    out = np.full((t_cap, 3), cap_e, np.int32)
    out[: len(tris)] = tris
    return out


# ---------------------------------------------------------------------------
# single fused round: kernel (interpret) == jnp reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cap_e,bt", [(64, 8), (64, 16), (128, 32),
                                      (256, 64), (256, 128)])
def test_fused_round_matches_ref(cap_e, bt):
    rng = np.random.default_rng(cap_e + bt)
    n0 = max(10, int((cap_e / 0.35) ** 0.5))     # ~cap_e/2 expected edges
    for trial in range(3):
        n = n0 + trial
        sup, alive, tris, m = _lane(rng, n, 0.35, cap_e)
        t_cap = max(bt, -(-max(len(tris), 1) // bt) * bt)
        tris_p = _pad_to(tris, t_cap, cap_e)
        # a removal set mixing "support below threshold" and random picks
        rm = ((sup <= 1) & (alive > 0)).astype(np.int32)
        rm[rng.integers(0, m, size=max(1, m // 8))] = 1
        rm &= alive
        sup_k, alive_k = fk.fused_round(sup[None], alive[None], rm[None],
                                        tris_p[None], bt=bt, interpret=True)
        sup_r, alive_r = ref.fused_round_ref(sup[None], alive[None],
                                             rm[None], tris_p[None])
        np.testing.assert_array_equal(np.asarray(alive_k), np.asarray(alive_r))
        np.testing.assert_array_equal(np.asarray(sup_k), np.asarray(sup_r))


def test_fused_round_padding_rows_inert():
    """Rows pointing at the drop slot (id == cap_e) must not change any
    edge slot — the bucket builders' padding convention."""
    rng = np.random.default_rng(5)
    cap_e, bt = 64, 16
    sup, alive, tris, m = _lane(rng, 13, 0.4, cap_e)
    rm = ((sup <= 1) & (alive > 0)).astype(np.int32)
    lean = _pad_to(tris, max(bt, -(-len(tris) // bt) * bt), cap_e)
    fat = _pad_to(tris, lean.shape[0] + 4 * bt, cap_e)
    s1, a1 = fk.fused_round(sup[None], alive[None], rm[None], lean[None],
                            bt=bt, interpret=True)
    s2, a2 = fk.fused_round(sup[None], alive[None], rm[None], fat[None],
                            bt=bt, interpret=True)
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))


# ---------------------------------------------------------------------------
# full class peel: fused == host reference == XLA frontier engine
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cap_e,bt", [(64, "auto"), (128, 32), (256, 128)])
def test_peel_classes_fused_parity(cap_e, bt):
    rng = np.random.default_rng(17 + cap_e)
    n0 = max(9, int((cap_e / 0.45) ** 0.5) - 2)
    lanes = [_lane(rng, n0 + i, 0.45, cap_e) for i in range(3)]
    t_max = max(max(len(t) for _, _, t, _ in lanes), 1)
    sup_b = np.stack([s for s, _, _, _ in lanes])
    alive_b = np.stack([a for _, a, _, _ in lanes])
    tris_b = np.stack([_pad_to(t, t_max, cap_e) for _, _, t, _ in lanes])

    phi_f, st_f = ops.peel_classes_fused(sup_b, tris_b, alive_b,
                                         bt=bt, interpret=True)
    phi_r = ref.peel_classes_ref(sup_b, tris_b, alive_b)
    np.testing.assert_array_equal(np.asarray(phi_f), np.asarray(phi_r))
    # stats rows in peel.N_STATS layout: every alive edge was removed once
    st_f = np.asarray(st_f)
    np.testing.assert_array_equal(st_f[:, ops._S_REMOVED],
                                  alive_b.sum(axis=1))
    assert (st_f[:, ops._S_ROUNDS] >= 1).all()
    assert (st_f[:, ops._S_MAXF] <= st_f[:, ops._S_REMOVED]).all()

    for lane, (sup, alive, tris, m) in enumerate(lanes):
        phi_x, _ = peel.peel_classes(sup[:m].astype(np.int32),
                                     np.asarray(tris, np.int32),
                                     alive[:m] > 0)
        np.testing.assert_array_equal(np.asarray(phi_f)[lane, :m],
                                      np.asarray(phi_x), err_msg=str(lane))


@pytest.mark.parametrize("thresh", [0, 1, 2, 4])
def test_peel_threshold_fused_parity(thresh):
    rng = np.random.default_rng(23 + thresh)
    cap_e = 128
    sup, alive, tris, m = _lane(rng, 18, 0.4, cap_e)
    removable = np.zeros(cap_e, np.int32)
    removable[:m] = rng.integers(0, 2, m)
    tris_p = _pad_to(tris, max(len(tris), 1), cap_e)
    alive_f = ops.peel_threshold_fused(sup, tris_p, removable,
                                       thresh, alive, interpret=True)
    alive_x, _, _ = peel.peel_threshold(
        sup[:m].astype(np.int32), np.asarray(tris, np.int32),
        alive[:m] > 0, removable[:m] > 0, thresh)
    np.testing.assert_array_equal(np.asarray(alive_f)[:m] > 0,
                                  np.asarray(alive_x))


# ---------------------------------------------------------------------------
# layout / routing contracts
# ---------------------------------------------------------------------------

def test_stats_layout_pinned_to_peel():
    assert ops.N_STATS == peel.N_STATS
    assert (ops._S_ROUNDS, ops._S_REMOVED, ops._S_GATHERED, ops._S_MAXF) \
        == (peel._S_ROUNDS, peel._S_REMOVED, peel._S_GATHERED, peel._S_MAXF)


def test_resolve_kernel_routing():
    # explicit knobs pass through regardless of backend
    assert ops.resolve_kernel("xla", 64, 10_000) == "xla"
    assert ops.resolve_kernel("pallas", 1 << 30, 0) == "pallas"
    with pytest.raises(ValueError):
        ops.resolve_kernel("mxu", 64, 64)
    # auto: never Pallas off-TPU (Pallas has no CPU lowering)
    assert ops.resolve_kernel("auto", 64, 10_000, backend="cpu") == "xla"
    # auto on TPU: dense lanes route to the kernel, sparse lanes and
    # VMEM-overflowing caps fall back
    assert ops.resolve_kernel("auto", 1024, 4096, backend="tpu") == "pallas"
    assert ops.resolve_kernel("auto", 1024, 16, backend="tpu") == "xla"
    huge = fk.VMEM_BUDGET_BYTES          # no tile fits this cap_e
    assert ops.resolve_kernel("auto", huge, 10 * huge, backend="tpu") == "xla"
    assert triangle_density(0, 5) == 0.0


def test_resolve_tile_and_feasibility():
    assert ops.resolve_tile(64, 1000, 32, True) == 32      # explicit wins
    bt = ops.resolve_tile(64, 1000, "auto", True)
    assert bt in fk.DEFAULT_TILE_CANDIDATES
    assert fk.kernel_vmem_bytes(64, bt) <= fk.VMEM_BUDGET_BYTES
    tiles = fk.feasible_tiles(256, 1024)
    assert tiles and all(1024 % t == 0 for t in tiles)
    assert tiles == sorted(tiles, reverse=True)
    # vmem model is monotone in both tile and cap
    assert fk.kernel_vmem_bytes(256, 256) > fk.kernel_vmem_bytes(256, 128)
    assert fk.kernel_vmem_bytes(512, 128) > fk.kernel_vmem_bytes(256, 128)


def test_autotune_tiles_returns_feasible():
    bt = fk.autotune_tiles(128, 512, interpret=True)
    assert 512 % bt == 0
    assert fk.kernel_vmem_bytes(128, bt) <= fk.VMEM_BUDGET_BYTES
    # cached: same key returns the same tile without re-timing
    assert fk.autotune_tiles(128, 512, interpret=True) == bt


def test_resolve_tile_raises_when_no_tile_fits():
    # "auto" never routes such a lane to the kernel; forcing it must fail
    # here rather than overflow VMEM on the chip
    huge = fk.VMEM_BUDGET_BYTES
    assert ops.resolve_kernel("auto", huge, 10 * huge, backend="tpu") == "xla"
    with pytest.raises(ValueError, match="VMEM"):
        ops.resolve_tile(huge, 1024, "auto", False)
    assert ops.resolve_tile(huge, 1024, 128, False) == 128   # explicit wins


def test_autotune_tiles_reraises(monkeypatch):
    with pytest.raises(ValueError, match="VMEM"):
        fk.autotune_tiles(fk.VMEM_BUDGET_BYTES, 1024, interpret=True)

    def refused(*args, **kwargs):
        raise RuntimeError("Mosaic refused the tile")

    monkeypatch.setattr(fk, "fused_round", refused)
    with pytest.raises(RuntimeError, match="Mosaic refused"):
        fk.autotune_tiles(96, 256, interpret=True)
