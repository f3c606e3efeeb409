"""Round journaling + resume (DESIGN.md §12): a decomposition interrupted
after an arbitrary completed round and resumed from its checkpoint
directory must produce phi bit-identical to an uninterrupted run.

In-process interruptions inject a non-retryable fault at a chosen site and
re-invoke with ``resume=True``; the subprocess smoke goes further and
SIGKILLs the worker mid-run (no atexit, no finally blocks) before resuming
in this process — the crash case the atomic tmp+rename snapshot contract
exists for.
"""

import contextlib
import os
import subprocess
import sys
import warnings

import numpy as np
import pytest

from repro.core import faults
from repro.core import graph as glib
from repro.core.bottom_up import bottom_up_decompose
from repro.core.partition import PartitionBudgetWarning
from repro.core.peel import truss_decompose
from repro.core.serial import alg2_truss
from repro.core.top_down import top_down_decompose
from tests.conftest import conformance_corpus

CORPUS = conformance_corpus()
_ORACLE = {name: alg2_truss(n, ce) for name, n, ce in CORPUS}
BUDGET = 64


@contextlib.contextmanager
def _quiet():
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", PartitionBudgetWarning)
        yield


def _interrupt(fn, plan, **kwargs):
    """Run ``fn`` under ``plan``; return whether it was actually cut short
    (small corpus graphs may finish before the rule's nth match)."""
    with _quiet(), faults.active(plan):
        try:
            fn(**kwargs)
        except (faults.InjectedFault, OSError):
            return True
    return False


@pytest.mark.parametrize("name,n,ce", CORPUS, ids=[c[0] for c in CORPUS])
@pytest.mark.parametrize("site,where,nth", [
    (faults.PARTITIONER, {"stage": 1}, 3),      # between stage-1 rounds
    (faults.DISPATCH, {"stage": 2}, 1),         # first stage-2 level
    (faults.DISPATCH, {"stage": 2}, 3),         # mid stage-2
], ids=["s1-round3", "s2-first", "s2-mid"])
def test_bottom_up_interrupt_resume(tmp_path, name, n, ce, site, where, nth):
    from repro.checkpoint import manager as ckpt
    d = str(tmp_path / "ckpt")
    plan = faults.FaultPlan([faults.FaultRule(site=site, kind="error",
                                              where=dict(where), nth=nth)])
    _interrupt(bottom_up_decompose, plan, n=n, edges=ce, budget=BUDGET,
               checkpoint_dir=d, checkpoint_every=1)
    had_snap = ckpt.latest_step(d) is not None
    with _quiet():
        res = bottom_up_decompose(n, ce, budget=BUDGET, checkpoint_dir=d,
                                  resume=True)
    assert (res.phi == _ORACLE[name]).all(), name
    if plan.log and had_snap:         # interrupted after a journaled round
        assert res.stats.resumed_round >= 0, name


@pytest.mark.parametrize("name,n,ce", CORPUS, ids=[c[0] for c in CORPUS])
@pytest.mark.parametrize("site,where,nth", [
    (faults.PARTITIONER, {"stage": 1}, 2),      # between support rounds
    (faults.DISPATCH, {"stage": "td"}, 2),      # second class level
], ids=["sup-round2", "td-level2"])
def test_top_down_interrupt_resume(tmp_path, name, n, ce, site, where, nth):
    d = str(tmp_path / "ckpt")
    plan = faults.FaultPlan([faults.FaultRule(site=site, kind="error",
                                              where=dict(where), nth=nth)])
    _interrupt(top_down_decompose, plan, n=n, edges=ce, budget=BUDGET,
               checkpoint_dir=d, checkpoint_every=1)
    with _quiet():
        res = top_down_decompose(n, ce, budget=BUDGET, checkpoint_dir=d,
                                 resume=True)
    assert (res.phi == _ORACLE[name]).all(), name


@pytest.mark.parametrize("name,n,ce", CORPUS, ids=[c[0] for c in CORPUS])
def test_journal_adds_no_device_work(tmp_path, name, n, ce):
    """Journaling every round is host I/O only: a journaled bottom-up run
    writes snapshots, and launches, uploads and rounds exactly what the
    unjournaled run does (the counters ``ooc_batches`` and ``h2d_bytes``
    read)."""
    with _quiet():
        plain = bottom_up_decompose(n, ce, budget=BUDGET)
        journaled = bottom_up_decompose(
            n, ce, budget=BUDGET, checkpoint_dir=str(tmp_path / "ckpt"),
            checkpoint_every=1)
    assert (journaled.phi == _ORACLE[name]).all(), name
    assert journaled.stats.checkpoints > 0, name
    for counter in ("batches", "h2d_bytes", "rounds"):
        assert (getattr(journaled.stats, counter)
                == getattr(plain.stats, counter)), (name, counter)


def test_resume_empty_dir_is_fresh_run(tmp_path):
    name, n, ce = CORPUS[0]
    with _quiet():
        res = bottom_up_decompose(n, ce, budget=BUDGET,
                                  checkpoint_dir=str(tmp_path / "none"),
                                  resume=True)
    assert (res.phi == _ORACLE[name]).all()
    assert res.stats.resumed_round == -1


def test_resume_checkpoints_continue_sequence(tmp_path):
    """A resumed run keeps journaling: the step counter continues past the
    pre-crash snapshots instead of overwriting them."""
    from repro.checkpoint import manager as ckpt
    name, n, ce = CORPUS[0]
    d = str(tmp_path / "ckpt")
    plan = faults.FaultPlan([faults.FaultRule(
        site=faults.DISPATCH, kind="error", where={"stage": 2}, nth=1)])
    _interrupt(bottom_up_decompose, plan, n=n, edges=ce, budget=BUDGET,
               checkpoint_dir=d, checkpoint_every=1)
    before = ckpt.latest_step(d)
    with _quiet():
        bottom_up_decompose(n, ce, budget=BUDGET, checkpoint_dir=d,
                            resume=True)
    assert before is not None and ckpt.latest_step(d) > before


def test_run_key_mismatch_rejected(tmp_path):
    """Resuming a journal recorded for a different graph/config raises —
    silently continuing someone else's snapshot is never acceptable."""
    name, n, ce = CORPUS[0]
    d = str(tmp_path / "ckpt")
    with _quiet():
        bottom_up_decompose(n, ce, budget=BUDGET, checkpoint_dir=d,
                            checkpoint_every=1)
    other = glib.canonical_edges(ce[:-2], n)        # different edge list
    with _quiet(), pytest.raises(ValueError, match="run_key|different run"):
        bottom_up_decompose(n, other, budget=BUDGET, checkpoint_dir=d,
                            resume=True)
    with _quiet(), pytest.raises(ValueError, match="run_key|different run"):
        bottom_up_decompose(n, ce, budget=BUDGET * 2, checkpoint_dir=d,
                            resume=True)


def test_truss_decompose_threads_checkpointing(tmp_path):
    name, n, ce = CORPUS[0]
    d = str(tmp_path / "ckpt")
    with _quiet():
        phi0, _ = truss_decompose(n, ce, engine="bottom-up",
                                  memory_budget=BUDGET, with_stats=True)
        phi1, stats = truss_decompose(n, ce, engine="bottom-up",
                                      memory_budget=BUDGET, with_stats=True,
                                      checkpoint_dir=d, checkpoint_every=1)
        phi2, stats2 = truss_decompose(n, ce, engine="bottom-up",
                                       memory_budget=BUDGET, with_stats=True,
                                       checkpoint_dir=d, resume=True)
    assert (phi0 == phi1).all() and (phi0 == phi2).all()
    assert stats.checkpoints > 0
    assert stats2.resumed_round >= 0


def test_truss_decompose_in_memory_warns_and_ignores(tmp_path):
    name, n, ce = CORPUS[0]
    with pytest.warns(UserWarning, match="in-memory"):
        phi = truss_decompose(n, ce, engine="dense",
                              checkpoint_dir=str(tmp_path))
    assert (phi == _ORACLE[name]).all()


_KILL_DRIVER = r"""
import sys
import numpy as np
from repro.core import faults
from repro.core.bottom_up import bottom_up_decompose
from tests.conftest import conformance_corpus

ckpt_dir, kill_round = sys.argv[1], int(sys.argv[2])
name, n, ce = conformance_corpus()[0]
if kill_round >= 0:
    faults.install(faults.FaultPlan([faults.FaultRule(
        site=faults.PARTITIONER, kind="kill", where={"stage": 1},
        nth=kill_round)]))
import warnings
warnings.simplefilter("ignore")
res = bottom_up_decompose(n, ce, budget=64, checkpoint_dir=ckpt_dir,
                          checkpoint_every=1, resume=True)
np.save(ckpt_dir + "/phi.npy", res.phi)
"""


def _subprocess_env():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         os.path.join(os.path.dirname(__file__), ".."),
         env.get("PYTHONPATH", "")])
    return env


def test_sigkill_crash_and_resume(tmp_path):
    """The real thing: SIGKILL the worker between stage-1 rounds, resume in
    a second process, phi must match the oracle bit-for-bit."""
    d = str(tmp_path / "ckpt")
    os.makedirs(d)
    env = _subprocess_env()
    kill = subprocess.run([sys.executable, "-c", _KILL_DRIVER, d, "2"],
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert kill.returncode == -9, (kill.returncode, kill.stderr[-2000:])
    assert not os.path.exists(d + "/phi.npy")   # it really died mid-run
    resume = subprocess.run([sys.executable, "-c", _KILL_DRIVER, d, "-1"],
                            env=env, capture_output=True, text=True,
                            timeout=600)
    assert resume.returncode == 0, resume.stderr[-2000:]
    phi = np.load(d + "/phi.npy")
    name, n, ce = CORPUS[0]
    assert (phi == _ORACLE[name]).all()


def test_zone_state_helpers_round_trip():
    """The locality partitioner's one float of cross-round feedback
    snapshots and restores; stateless partitioners snapshot as None and
    ignore restores (no attribute is ever attached to them)."""
    from repro.core.bottom_up import (_resolve_partitioner,
                                      _restore_zone_state, _zone_state)
    loc = _resolve_partitioner("locality")
    assert _zone_state(loc) is None          # cold start
    loc.prev_locality = 0.75
    assert _zone_state(loc) == 0.75
    loc2 = _resolve_partitioner("locality")
    _restore_zone_state(loc2, _zone_state(loc))
    assert loc2.prev_locality == 0.75
    seq = _resolve_partitioner("sequential")
    assert _zone_state(seq) is None
    _restore_zone_state(seq, 0.5)            # must not attach state
    assert _zone_state(seq) is None


def test_locality_zone_state_journaled_and_restored(tmp_path):
    """Satellite-1 regression: a stage-1 snapshot of a locality run must
    carry the adaptive partitioner's zone state so the resumed run
    re-plans its remaining rounds from the journaled feedback instead of
    the cold default."""
    from repro.core.bottom_up import (RoundJournal, _mesh_devices,
                                      _resolve_partitioner,
                                      _restore_zone_state, _run_key)
    name, n, ce = CORPUS[3]                  # clustered: locality's regime
    budget = 16
    d = str(tmp_path / "ckpt")
    plan = faults.FaultPlan([faults.FaultRule(
        site=faults.PARTITIONER, kind="error", where={"stage": 1}, nth=3)])
    cut = _interrupt(bottom_up_decompose, plan, n=n, edges=ce, budget=budget,
                     partitioner="locality", checkpoint_dir=d,
                     checkpoint_every=1)
    assert cut
    key = _run_key("bottom_up", n, ce, budget, "locality", 0,
                   devices=_mesh_devices(None, "data"))
    tree, meta = RoundJournal(d, key, every=1).load_latest()
    assert meta["stage"] == "lb"
    zs = meta.get("zone_state")
    assert zs is not None and 0.0 <= float(zs) <= 1.0
    part_fn = _resolve_partitioner("locality")
    _restore_zone_state(part_fn, zs)
    assert part_fn.prev_locality == float(zs)
    with _quiet():
        res = bottom_up_decompose(n, ce, budget=budget,
                                  partitioner="locality", checkpoint_dir=d,
                                  resume=True)
    assert (res.phi == _ORACLE[name]).all()
    assert res.stats.resumed_round >= 0


_SPILL_KILL_DRIVER = r"""
import sys
import numpy as np
from repro.core import faults
from repro.core.bottom_up import bottom_up_decompose
from repro.core.store import ChunkedDiskStore
from tests.conftest import conformance_corpus

ckpt_dir, store_dir, nth = sys.argv[1], sys.argv[2], int(sys.argv[3])
name, n, ce = conformance_corpus()[0]
if nth >= 0:
    faults.install(faults.FaultPlan([faults.FaultRule(
        site=faults.CHUNK_WRITE, kind="kill", nth=nth)]))
import warnings
warnings.simplefilter("ignore")
with ChunkedDiskStore(store_dir, chunk_bytes=1 << 10) as store:
    res = bottom_up_decompose(n, ce, budget=64, checkpoint_dir=ckpt_dir,
                              checkpoint_every=1, resume=True, store=store)
np.save(ckpt_dir + "/phi.npy", res.phi)
"""


def test_sigkill_mid_chunk_spill_and_resume(tmp_path):
    """SIGKILL delivered INSIDE a chunk spill (the chunk-write fault site,
    DESIGN.md §15): the journaled snapshot must survive the torn store
    state, the restarted store must sweep the dead process's spill files,
    and the resumed run must reproduce the oracle bit-for-bit."""
    d = str(tmp_path / "ckpt")
    sd = str(tmp_path / "store")
    os.makedirs(d)
    env = _subprocess_env()
    # write 25 of ~40 chunk spills, then die: mid-run, past several
    # journaled rounds, in the middle of one graph's spill
    kill = subprocess.run([sys.executable, "-c", _SPILL_KILL_DRIVER,
                           d, sd, "25"], env=env, capture_output=True,
                          text=True, timeout=600)
    assert kill.returncode == -9, (kill.returncode, kill.stderr[-2000:])
    assert not os.path.exists(d + "/phi.npy")
    leftovers = [f for f in os.listdir(sd) if f.endswith(".bin")]
    assert leftovers                      # the dead run's torn spill state
    resume = subprocess.run([sys.executable, "-c", _SPILL_KILL_DRIVER,
                             d, sd, "-1"], env=env, capture_output=True,
                            text=True, timeout=600)
    assert resume.returncode == 0, resume.stderr[-2000:]
    phi = np.load(d + "/phi.npy")
    name, n, ce = CORPUS[0]
    assert (phi == _ORACLE[name]).all()
