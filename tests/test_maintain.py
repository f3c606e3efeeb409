"""Incremental truss maintenance (DESIGN.md §16): ``truss_maintain`` must
produce φ bit-identical to a full recompute on the post-edit edge set, for
every conformance-corpus graph, under insert-only / delete-only / mixed
edit batches — including edits that raise or lower trussness, edits routed
through a spilled :class:`ChunkedDiskStore` graph, and batches interrupted
mid-maintenance (injected error and SIGKILL) then resumed.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import faults
from repro.core import graph as glib
from repro.core.graph import build_graph, edge_id_lookup, undirected_csr
from repro.core.maintain import EditBatch, truss_maintain
from repro.core.peel import truss_decompose
from repro.core.serial import alg2_truss
from tests.conftest import clique_edges, conformance_corpus, rmat_graph

CORPUS = conformance_corpus()
_PHI0 = {name: alg2_truss(n, ce) for name, n, ce in CORPUS}


def _existing(rng, n, ce, k):
    """k distinct (u, v) pairs drawn from the current edge list."""
    k = min(k, len(ce))
    ids = rng.choice(len(ce), size=k, replace=False)
    return [tuple(int(x) for x in ce[i]) for i in ids]


def _absent(rng, n, ce, k):
    """k distinct canonical (u, v) pairs NOT in the current edge list."""
    present = {tuple(e) for e in np.asarray(ce).tolist()}
    out = []
    while len(out) < k:
        u, v = (int(x) for x in rng.integers(0, n, 2))
        if u == v:
            continue
        a, b = min(u, v), max(u, v)
        if (a, b) in present:
            continue
        present.add((a, b))
        out.append((a, b))
    return out


def _check(n, ce, phi0, steps, **kwargs):
    """Maintain, then pin φ AND the maintained edge list to the oracle."""
    res = truss_maintain((n, ce), phi0, steps, **kwargs)
    s = {tuple(e) for e in np.asarray(ce).tolist()}
    for op, u, v in steps:
        a, b = min(int(u), int(v)), max(int(u), int(v))
        if op == "delete":
            s.discard((a, b))
        elif a != b:
            s.add((a, b))
    exp_edges = glib.canonical_edges(
        np.asarray(sorted(s), np.int64).reshape(-1, 2), n)
    assert (res.graph.edges == exp_edges).all()
    assert (res.phi == alg2_truss(n, exp_edges)).all()
    return res


@pytest.mark.parametrize("name,n,ce", CORPUS, ids=[c[0] for c in CORPUS])
def test_differential_insert_only(name, n, ce):
    rng = np.random.default_rng(11)
    steps = [("insert", u, v) for u, v in _absent(rng, n, ce, 4)]
    res = _check(n, ce, _PHI0[name], steps)
    assert res.stats.edits_applied == 4


@pytest.mark.parametrize("name,n,ce", CORPUS, ids=[c[0] for c in CORPUS])
def test_differential_delete_only(name, n, ce):
    if not len(ce):
        pytest.skip("no edges to delete")
    rng = np.random.default_rng(13)
    steps = [("delete", u, v) for u, v in _existing(rng, n, ce, 4)]
    res = _check(n, ce, _PHI0[name], steps)
    assert res.stats.edits_applied == len(steps)


@pytest.mark.parametrize("name,n,ce", CORPUS, ids=[c[0] for c in CORPUS])
def test_differential_mixed(name, n, ce):
    if not len(ce):
        pytest.skip("no edges to delete")
    rng = np.random.default_rng(17)
    dels = [("delete", u, v) for u, v in _existing(rng, n, ce, 3)]
    ins = [("insert", u, v) for u, v in _absent(rng, n, ce, 3)]
    steps = [s for pair in zip(dels, ins) for s in pair]   # interleaved
    _check(n, ce, _PHI0[name], steps)


@pytest.mark.parametrize("name,n,ce", CORPUS, ids=[c[0] for c in CORPUS])
def test_single_edge_edits_stay_local(name, n, ce):
    """One delete and one insert re-peel less than the whole graph: the
    candidate edges summed over every maintenance level stay below m."""
    rng = np.random.default_rng(0)
    steps = [("delete", *_existing(rng, n, ce, 1)[0]),
             ("insert", *_absent(rng, n, ce, 1)[0])]
    res = _check(n, ce, _PHI0[name], steps)
    assert res.stats.edits_applied == 2
    assert res.stats.affected_edges < len(ce)


def test_eight_edit_batch_runs_region_peels():
    n, ce = rmat_graph(scale=6, edge_factor=6, seed=2)
    rng = np.random.default_rng(9)
    steps = ([("delete", u, v) for u, v in _existing(rng, n, ce, 4)]
             + [("insert", u, v) for u, v in _absent(rng, n, ce, 4)])
    res = _check(n, ce, alg2_truss(n, ce), steps)
    assert res.stats.edits_applied == 8
    assert res.stats.maintain_levels > 0


def test_insert_raises_trussness():
    """Completing an almost-clique promotes the surviving edges — the
    k-raising direction must propagate past the inserted edge itself."""
    n, size = 6, 6
    full = glib.canonical_edges(clique_edges(0, size), n)
    hole = full[1:]                          # K6 minus one edge
    phi0 = alg2_truss(n, hole)
    u, v = (int(x) for x in full[0])
    res = _check(n, hole, phi0, [("insert", u, v)])
    assert int(res.phi.max()) > int(phi0.max())
    assert (res.phi == size).all()           # K6: every edge has φ = 6


def test_delete_lowers_trussness():
    """Breaking a clique demotes its edges — the k-lowering direction must
    reach edges far from the deleted one."""
    n, size = 6, 6
    full = glib.canonical_edges(clique_edges(0, size), n)
    phi0 = alg2_truss(n, full)
    u, v = (int(x) for x in full[0])
    res = _check(n, full, phi0, [("delete", u, v)])
    assert int(res.phi.max()) < int(phi0.max())


def test_edit_batch_deletes_first():
    name, n, ce = CORPUS[0]
    rng = np.random.default_rng(19)
    dels = np.asarray(_existing(rng, n, ce, 2), np.int64)
    ins = np.asarray(_absent(rng, n, ce, 2), np.int64)
    batch = EditBatch(inserts=ins, deletes=dels)
    res = truss_maintain((n, ce), _PHI0[name], batch)
    steps = ([("delete", int(u), int(v)) for u, v in dels]
             + [("insert", int(u), int(v)) for u, v in ins])
    ref = _check(n, ce, _PHI0[name], steps)
    assert (res.phi == ref.phi).all()
    assert (res.graph.edges == ref.graph.edges).all()
    assert res.stats.edits_applied == 4


def test_noop_edits_skipped():
    """Deleting an absent edge / inserting a present one is a no-op: φ and
    the graph are untouched and ``edits_applied`` stays 0."""
    name, n, ce = CORPUS[0]
    rng = np.random.default_rng(23)
    (au, av), = _absent(rng, n, ce, 1)
    pu, pv = (int(x) for x in ce[0])
    res = truss_maintain((n, ce), _PHI0[name],
                         [("delete", au, av), ("insert", pu, pv),
                          ("insert", 4, 4)])
    assert res.stats.edits_applied == 0
    assert res.graph.m == len(ce)
    assert (res.phi == _PHI0[name]).all()


def test_bad_edit_op_rejected():
    name, n, ce = CORPUS[0]
    with pytest.raises(ValueError, match="insert.*delete|op"):
        truss_maintain((n, ce), _PHI0[name], [("upsert", 0, 1)])


def test_phi_length_mismatch_rejected():
    name, n, ce = CORPUS[0]
    with pytest.raises(ValueError, match="entries"):
        truss_maintain((n, ce), _PHI0[name][:-1], [("insert", 0, 1)])


def test_spilled_chunk_edits(tmp_path):
    """Edits against a disk-spilled graph: the splice/filter plans must
    rewrite only the touched chunks while the maintained φ stays exact."""
    from repro.core.store import ChunkedDiskStore

    name, n, ce = CORPUS[1]                  # rmat: enough edges to chunk
    rng = np.random.default_rng(29)
    dels = [("delete", u, v) for u, v in _existing(rng, n, ce, 2)]
    ins = [("insert", u, v) for u, v in _absent(rng, n, ce, 2)]
    with ChunkedDiskStore(str(tmp_path / "store"),
                          chunk_bytes=1 << 10) as store:
        res = _check(n, ce, _PHI0[name], dels + ins, store=store)
        assert res.stats.chunk_writes > 0
        assert res.stats.edits_applied == 4


def test_truss_decompose_edits_dispatch():
    """``truss_decompose(..., edits=)`` routes through maintenance; with a
    caller-supplied ``phi0`` the pre-edit decomposition is not recomputed,
    and ``phi0`` without ``edits`` is rejected."""
    name, n, ce = CORPUS[0]
    rng = np.random.default_rng(31)
    steps = ([("delete", u, v) for u, v in _existing(rng, n, ce, 2)]
             + [("insert", u, v) for u, v in _absent(rng, n, ce, 2)])
    ref = _check(n, ce, _PHI0[name], steps)
    phi1 = truss_decompose(n, ce, edits=steps)
    assert (phi1 == ref.phi).all()
    phi2, stats = truss_decompose(n, ce, edits=steps, phi0=_PHI0[name],
                                  with_stats=True)
    assert (phi2 == ref.phi).all()
    assert stats.edits_applied == 4
    with pytest.raises(ValueError, match="phi0"):
        truss_decompose(n, ce, phi0=_PHI0[name])


def test_maintain_interrupt_resume(tmp_path):
    """An injected error between edits leaves a journal the resumed call
    replays from — only the edits after the newest snapshot re-run, and
    the final φ still matches the oracle."""
    name, n, ce = CORPUS[3]
    rng = np.random.default_rng(37)
    steps = ([("delete", u, v) for u, v in _existing(rng, n, ce, 3)]
             + [("insert", u, v) for u, v in _absent(rng, n, ce, 3)])
    d = str(tmp_path / "ckpt")
    plan = faults.FaultPlan([faults.FaultRule(
        site=faults.MAINTAIN, kind="error", nth=4)])
    with faults.active(plan):
        with pytest.raises((faults.InjectedFault, OSError)):
            truss_maintain((n, ce), _PHI0[name], steps, checkpoint_dir=d,
                           checkpoint_every=1)
    res = _check(n, ce, _PHI0[name], steps, checkpoint_dir=d, resume=True)
    assert res.stats.resumed_round >= 0


def test_maintain_rejects_foreign_journal(tmp_path):
    """A maintenance resume must refuse a journal recorded by a
    decomposition run (different stage), not silently continue it."""
    name, n, ce = CORPUS[0]
    d = str(tmp_path / "ckpt")
    import warnings

    from repro.core.bottom_up import bottom_up_decompose
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        bottom_up_decompose(n, ce, budget=64, checkpoint_dir=d,
                            checkpoint_every=1)
    with pytest.raises(ValueError):
        truss_maintain((n, ce), _PHI0[name], [("insert", 0, 1)],
                       checkpoint_dir=d, resume=True)


def test_add_edges_invariants():
    """``Graph.add_edges`` splices in canonical id order with rank reuse.
    CSR orientation legitimately differs from a fresh ``build_graph`` (the
    reused ranks order old vertices by their OLD degrees), so the
    invariants here are orientation-independent: canonical edge list,
    id lookup, undirected adjacency."""
    name, n, ce = CORPUS[0]
    g = build_graph(n, ce)
    rng = np.random.default_rng(41)
    new = np.asarray(_absent(rng, n, ce, 3), np.int64)
    g1 = g.add_edges(new)
    exp = glib.canonical_edges(np.concatenate([ce, new]), n)
    assert g1.m == g.m + 3
    assert (g1.edges == exp).all()
    assert (edge_id_lookup(g1, new[:, 0], new[:, 1]) >= 0).all()
    ip1, nb1 = undirected_csr(g1)
    gf = build_graph(n, exp)
    ipf, nbf = undirected_csr(gf)
    assert (ip1 == ipf).all()
    for r in range(n):
        assert (np.sort(nb1[ip1[r]:ip1[r + 1]])
                == np.sort(nbf[ipf[r]:ipf[r + 1]])).all(), r
    # duplicates and self-loops are no-ops that return the same object
    assert g1.add_edges(new[:1]) is g1
    assert g1.add_edges(np.asarray([[5, 5]], np.int64)) is g1


_MAINT_KILL_DRIVER = r"""
import sys
import numpy as np
from repro.core import faults
from repro.core.maintain import truss_maintain
from repro.core.serial import alg2_truss
from tests.conftest import conformance_corpus

ckpt_dir, nth = sys.argv[1], int(sys.argv[2])
name, n, ce = conformance_corpus()[1]            # rmat
phi0 = alg2_truss(n, ce)
rng = np.random.default_rng(7)
present = {tuple(e) for e in np.asarray(ce).tolist()}
steps = [("delete", int(u), int(v))
         for u, v in (ce[i] for i in rng.choice(len(ce), 4, replace=False))]
while len(steps) < 8:
    u, v = (int(x) for x in rng.integers(0, n, 2))
    a, b = min(u, v), max(u, v)
    if a == b or (a, b) in present:
        continue
    present.add((a, b))
    steps.append(("insert", a, b))
if nth >= 0:
    faults.install(faults.FaultPlan([faults.FaultRule(
        site=faults.MAINTAIN, kind="kill", nth=nth)]))
res = truss_maintain((n, ce), phi0, steps, checkpoint_dir=ckpt_dir,
                     checkpoint_every=1, resume=True)
np.save(ckpt_dir + "/phi.npy", res.phi)
np.save(ckpt_dir + "/edges.npy", res.graph.edges)
"""


def _subprocess_env():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         os.path.join(os.path.dirname(__file__), ".."),
         env.get("PYTHONPATH", "")])
    return env


def test_sigkill_mid_maintenance_and_resume(tmp_path):
    """SIGKILL the worker between committed edits (no atexit, no finally),
    then resume in a fresh process: the replayed tail must land on the
    same φ a full recompute of the final edge set produces."""
    d = str(tmp_path / "ckpt")
    os.makedirs(d)
    env = _subprocess_env()
    kill = subprocess.run([sys.executable, "-c", _MAINT_KILL_DRIVER, d, "5"],
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert kill.returncode == -9, (kill.returncode, kill.stderr[-2000:])
    assert not os.path.exists(d + "/phi.npy")    # it really died mid-batch
    resume = subprocess.run([sys.executable, "-c", _MAINT_KILL_DRIVER,
                             d, "-1"], env=env, capture_output=True,
                            text=True, timeout=600)
    assert resume.returncode == 0, resume.stderr[-2000:]
    phi = np.load(d + "/phi.npy")
    edges = np.load(d + "/edges.npy")
    name, n, ce = CORPUS[1]
    assert (phi == alg2_truss(n, edges)).all()


try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    _HAVE_HYPOTHESIS = True
except ImportError:                              # container has no dev deps
    _HAVE_HYPOTHESIS = False

if _HAVE_HYPOTHESIS:
    _HN = 14

    @settings(max_examples=15, deadline=None)
    @given(st.lists(
        st.tuples(st.booleans(),
                  st.integers(0, _HN - 1), st.integers(0, _HN - 1)),
        min_size=1, max_size=10))
    def test_hypothesis_edit_stream(ops):
        """Arbitrary edit streams (duplicates, self-loops, re-inserting a
        just-deleted edge, deleting a never-present one) always land on
        the full-recompute φ of the final edge set."""
        rng = np.random.default_rng(43)
        ce = glib.canonical_edges(random_edges(rng, _HN), _HN)
        steps = [("insert" if ins else "delete", u, v)
                 for ins, u, v in ops]
        _check(_HN, ce, alg2_truss(_HN, ce), steps)

    def random_edges(rng, n):
        mask = rng.random((n, n)) < 0.3
        iu = np.triu_indices(n, 1)
        return np.stack(iu, 1)[mask[iu]]
