"""Multi-device tests (8 virtual CPU devices via a subprocess, since device
count locks at first jax init)."""

import os
import subprocess
import sys
import textwrap

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, timeout=560):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env, cwd=_ROOT)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    return p.stdout


def test_distributed_truss_core():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        mesh = jax.make_mesh((8,), ("data",))
        from repro.core import graph as glib
        from repro.core.support import edge_support_np, list_triangles_np
        from repro.core.serial import alg2_truss
        from repro.core.distributed import (peel_classes_sharded,
            pad_triangles, ring_support_dense, allgather_support_dense)
        rng = np.random.default_rng(3)
        n = 64
        mask = rng.random((n, n)) < 0.25
        iu = np.triu_indices(n, 1); e = np.stack(iu, 1)[mask[iu]]
        ce = glib.canonical_edges(e, n)
        g = glib.build_graph(n, ce)
        oracle = alg2_truss(n, ce)
        tris = list_triangles_np(g)
        sup = edge_support_np(g).astype(np.int32)
        tp = pad_triangles(tris, g.m, 8)
        phi = np.asarray(peel_classes_sharded(
            mesh, jnp.asarray(sup), jnp.asarray(tp), jnp.ones(g.m, bool)))
        assert (phi == oracle).all()
        A = np.zeros((n, n), np.float32)
        A[ce[:,0], ce[:,1]] = 1; A[ce[:,1], ce[:,0]] = 1
        S_ring = np.asarray(ring_support_dense(mesh, jnp.asarray(A)))
        S_ag = np.asarray(allgather_support_dense(mesh, jnp.asarray(A)))
        assert np.allclose(S_ring, S_ag)
        assert (S_ring[ce[:,0], ce[:,1]] == sup).all()
        print("DIST-CORE-OK")
    """)
    assert "DIST-CORE-OK" in out


def test_distributed_models():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        mesh = jax.make_mesh((4, 2), ("data", "model"))
        from repro.models.gnn import models as G
        from repro.models.gnn.distributed import (bucket_edges_by_owner,
            pad_nodes, eqv2_ring_loss)
        from repro.models.recsys import embedding as emb
        from repro.core import graph as glib
        rng = np.random.default_rng(0)
        n, n_pad = 60, 64
        mask = rng.random((n, n)) < 0.15
        iu = np.triu_indices(n, 1); e = np.stack(iu, 1)[mask[iu]]
        ce = glib.canonical_edges(e, n)
        ei = np.concatenate([ce, ce[:, ::-1]]).astype(np.int32)
        cfg = G.EquiformerV2Config(n_layers=2, d_hidden=16, l_max=2,
                                   m_max=2, n_heads=4, d_in=8)
        params = G.eqv2_init(jax.random.PRNGKey(0), cfg)
        nf = rng.standard_normal((n, 8)).astype(np.float32)
        pos = rng.standard_normal((n, 3)).astype(np.float32)
        tgt = rng.standard_normal(n).astype(np.float32)
        batch = {"node_feat": jnp.asarray(nf), "edge_index": jnp.asarray(ei),
                 "positions": jnp.asarray(pos), "targets": jnp.asarray(tgt),
                 "node_mask": jnp.ones(n, np.float32)}
        loss_plain = G.eqv2_loss(params, batch, cfg)
        g_plain = jax.grad(lambda p: G.eqv2_loss(p, batch, cfg))(params)
        bk = bucket_edges_by_owner(n_pad, ei, pos, 8, pad_factor=4.0)
        rb = {"node_feat": jnp.asarray(pad_nodes(nf, n_pad)),
              "positions": jnp.asarray(pad_nodes(pos, n_pad)),
              "targets": jnp.asarray(pad_nodes(tgt, n_pad)),
              "node_mask": jnp.asarray(pad_nodes(np.ones(n, np.float32), n_pad)),
              **{k: jnp.asarray(v) for k, v in bk.items() if k != "overflow"}}
        with mesh:
            loss_ring = eqv2_ring_loss(params, rb, cfg, mesh)
            g_ring = jax.jit(jax.grad(
                lambda p: eqv2_ring_loss(p, rb, cfg, mesh)))(params)
        np.testing.assert_allclose(float(loss_plain), float(loss_ring), rtol=2e-4)
        for a, b in zip(jax.tree.leaves(g_plain), jax.tree.leaves(g_ring)):
            a, b = np.asarray(a), np.asarray(b)
            assert np.max(np.abs(a - b)) <= 5e-3 * (np.max(np.abs(a)) + 1e-6)
        # sage ring == plain sage on the same graph
        from repro.models.gnn.distributed import sage_ring_loss
        scfg = G.GraphSAGEConfig(n_layers=2, d_hidden=16, d_in=8, n_classes=4)
        sparams = G.sage_init(jax.random.PRNGKey(1), scfg)
        labels = rng.integers(0, 4, n).astype(np.int32)
        lmask = (rng.random(n) < 0.6).astype(np.float32)
        sbatch = {"node_feat": jnp.asarray(nf), "edge_index": jnp.asarray(ei),
                  "labels": jnp.asarray(labels), "label_mask": jnp.asarray(lmask)}
        loss_flat = G.sage_loss(sparams, sbatch, scfg)
        srb = {"node_feat": jnp.asarray(pad_nodes(nf, n_pad)),
               "labels": jnp.asarray(pad_nodes(labels, n_pad)),
               "label_mask": jnp.asarray(pad_nodes(lmask, n_pad)),
               "src_loc": jnp.asarray(bk["src_loc"]),
               "dst_loc": jnp.asarray(bk["dst_loc"]),
               "edge_mask": jnp.asarray(bk["edge_mask"])}
        with mesh:
            loss_sring = sage_ring_loss(sparams, srb, scfg, mesh)
            gs = jax.jit(jax.grad(
                lambda p: sage_ring_loss(p, srb, scfg, mesh)))(sparams)
        np.testing.assert_allclose(float(loss_flat), float(loss_sring),
                                   rtol=2e-4)
        for leaf in jax.tree.leaves(gs):
            assert np.isfinite(np.asarray(leaf)).all()
        # sharded embedding lookup == take
        from jax.sharding import PartitionSpec as P, NamedSharding
        tbl = jnp.asarray(rng.standard_normal((64, 8)).astype(np.float32))
        ids = jnp.asarray(rng.integers(0, 64, (16,)).astype(np.int32))
        with mesh:
            tbl_s = jax.device_put(tbl, NamedSharding(mesh, P("model", None)))
            out = emb.sharded_lookup(tbl_s, ids, mesh=mesh)
        np.testing.assert_allclose(np.asarray(out), np.asarray(tbl)[np.asarray(ids)])
        # compressed psum == mean of grads (within int8 quantization error)
        from repro.optim.compression import compressed_psum
        g8 = rng.standard_normal((8, 128)).astype(np.float32)
        def body(g, e):
            return compressed_psum(g, e, "data")
        fn = jax.shard_map(body, mesh=mesh,
            in_specs=(P("data", None), P("data", None)),
            out_specs=(P("data", None), P("data", None)), check_vma=False)
        gm, _ = fn(jnp.asarray(g8).reshape(8, 128),
                   jnp.zeros((8, 128)))
        # every data-row now holds the mean over its data group (4 shards x 2)
        got = np.asarray(gm)
        grp = g8.reshape(4, 2, 128).mean(0)
        for i in range(4):
            np.testing.assert_allclose(got[2*i:2*i+2], grp, atol=0.05)
        print("DIST-MODELS-OK")
    """)
    assert "DIST-MODELS-OK" in out


def test_dryrun_single_cell_small_mesh():
    """The dry-run machinery itself on an 8-device mesh (fast cell)."""
    out = _run("""
        import jax
        from repro.configs import registry
        from repro.launch.dryrun import run_cell
        mesh = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        cell = registry.get_cell("gat-cora", "full_graph_sm")
        rec = run_cell(cell, mesh, "4x2")
        assert rec["ok"], rec
        assert rec["t_memory"] > 0
        print("DRYRUN-OK")
    """)
    assert "DRYRUN-OK" in out
