"""Per-kernel shape/dtype sweeps: Pallas (interpret) vs pure-jnp oracle."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import graph as glib
from repro.core.support import edge_support_np
from tests.conftest import random_graph


class TestTriangleCount:
    @pytest.mark.parametrize("n,p,block", [
        (64, 0.3, 32), (96, 0.2, 48), (128, 0.15, 64), (100, 0.25, 64),
    ])
    def test_vs_ref(self, rng, n, p, block):
        from repro.kernels.triangle_count import ref
        from repro.kernels.triangle_count.ops import (adjacency_from_edges,
                                                      dense_support)
        ce = glib.canonical_edges(random_graph(rng, n, p), n)
        A = jnp.asarray(adjacency_from_edges(n, ce))
        S = dense_support(A, block=block, interpret=True)
        np.testing.assert_allclose(np.asarray(S), np.asarray(ref.support_dense(A)))

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_dtypes(self, rng, dtype):
        from repro.kernels.triangle_count.ops import (adjacency_from_edges,
                                                      dense_support)
        ce = glib.canonical_edges(random_graph(rng, 64, 0.3), 64)
        A = jnp.asarray(adjacency_from_edges(64, ce)).astype(dtype)
        S = dense_support(A, block=32, interpret=True)
        g = glib.build_graph(64, ce)
        sup = edge_support_np(g)
        np.testing.assert_allclose(
            np.asarray(S)[ce[:, 0], ce[:, 1]], sup)

    def test_matches_sparse_path(self, rng):
        from repro.kernels.triangle_count.ops import dense_edge_support
        ce = glib.canonical_edges(random_graph(rng, 90, 0.25), 90)
        sup_dense = dense_edge_support(90, ce, block=64, interpret=True)
        sup_sparse = edge_support_np(glib.build_graph(90, ce))
        assert (sup_dense == sup_sparse).all()

    def test_rectangular_tiles(self, rng):
        from repro.kernels.triangle_count.ops import (adjacency_from_edges,
                                                      dense_support)
        ce = glib.canonical_edges(random_graph(rng, 128, 0.2), 128)
        A = jnp.asarray(adjacency_from_edges(128, ce))
        S_ref = dense_support(A, block=128, interpret=True, use_kernel=False)
        for block in [(64, 64, 128), (128, 64, 64), (64, 128, 32)]:
            S = dense_support(A, block=block, interpret=True)
            np.testing.assert_allclose(np.asarray(S), np.asarray(S_ref))

    def test_bf16_tiles_f32_accum(self, rng):
        from repro.kernels.triangle_count.ops import dense_edge_support
        ce = glib.canonical_edges(random_graph(rng, 96, 0.3), 96)
        sup16 = dense_edge_support(96, ce, block=32, interpret=True,
                                   dtype=jnp.bfloat16)
        sup_sparse = edge_support_np(glib.build_graph(96, ce))
        assert (sup16 == sup_sparse).all()

    def test_vmem_budget_and_feasible_tiles(self):
        from repro.kernels.triangle_count.kernel import (VMEM_BUDGET_BYTES,
                                                         feasible_tiles,
                                                         kernel_vmem_bytes)
        # bf16 tiles are half the input footprint of f32
        assert kernel_vmem_bytes(256, 256, 256, jnp.bfloat16) < \
            kernel_vmem_bytes(256, 256, 256, jnp.float32)
        for tiles in feasible_tiles(512, jnp.float32):
            bm, bn, bk = tiles
            assert 512 % bm == 0 and 512 % bn == 0 and 512 % bk == 0
            assert kernel_vmem_bytes(bm, bn, bk) <= VMEM_BUDGET_BYTES

    def test_autotune_reraises_compile_failure(self, monkeypatch):
        from repro.kernels.triangle_count import kernel as tk

        def refused(*args, **kwargs):
            raise RuntimeError("Mosaic refused the tile")

        monkeypatch.setattr(tk, "triangle_count_kernel", refused)
        with pytest.raises(RuntimeError, match="Mosaic refused"):
            tk.autotune_tiles(96, interpret=True, repeats=1)

    def test_autotune_smoke(self, rng):
        from repro.kernels.triangle_count.kernel import autotune_tiles
        from repro.kernels.triangle_count.ops import (adjacency_from_edges,
                                                      dense_support)
        tiles = autotune_tiles(64, interpret=True, repeats=1)
        assert 64 % tiles[0] == 0
        # cached on second call
        assert autotune_tiles(64, interpret=True, repeats=1) == tiles
        ce = glib.canonical_edges(random_graph(rng, 64, 0.3), 64)
        A = jnp.asarray(adjacency_from_edges(64, ce))
        S = dense_support(A, block="auto", interpret=True)
        S_ref = dense_support(A, block=64, interpret=True, use_kernel=False)
        np.testing.assert_allclose(np.asarray(S), np.asarray(S_ref))

