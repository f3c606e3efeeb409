"""The four-chip mesh cell on four virtual CPU devices, and the readers of
its two metrics on hand-made runs.

The whole run is forced into a subprocess: the device count locks when
JAX first starts, and the test process has one device."""

from __future__ import annotations

import gzip
import os
import subprocess
import sys
import textwrap

import pytest

from perfbench import trace
from perfbench.metrics import mesh_collective_s, mesh_lane_padding_waste

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CELL = "graph500-s11-ooc-mesh4.full-mesh"


def _four_devices(code: str) -> str:
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([ROOT, os.path.join(ROOT, "src")]))
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=300, env=env,
                       cwd=ROOT)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    return p.stdout


def test_mesh_cell_is_correct_and_a_broken_psum_is_not():
    """At scale 9 the cell's whole run reads correct on a 4-device mesh,
    with its counters; with the sharded threshold peel's ``psum`` handing
    back each device's own decrements it reads not correct.  Unmerged
    decrements let the replicas of the edge state drift apart, and with
    them the number of sub-rounds each device runs, so the frontier's
    ``pmin`` hands back the local value too: a ``pmin`` left in place
    would wait for devices that have left the loop."""
    out = _four_devices(f"""
        import time
        import jax
        from perfbench import harness
        from repro.core import distributed, peel

        bench = harness.benchmark()
        cell = harness.cell(bench, "{CELL}")
        cfg = dict(harness.config(bench, cell["config"]), scale=9)
        mix = harness.traffic(cell["traffic"])
        entries = (harness.metrics_for(bench, "{CELL}", False)
                   + harness.metrics_for(bench, "{CELL}", True))
        seen = []
        real = peel.truss_decompose

        def kept(*a, **k):
            phi, stats = real(*a, **k)
            seen.append(stats)
            return phi, stats

        peel.truss_decompose = kept

        def measure():
            return harness.measure(cell, cfg, mix, 2**33 + 5, 0.2, False,
                                   time.perf_counter(), None, entries)

        res = measure()
        assert res["correct"] is True, res
        assert res["failed"] == 0
        for s in seen:
            assert (s.devices, s.lane_shards) == (4, 4)
            assert s.sharded_rounds > 0 and s.retries == s.degraded == 0
            assert s.mesh_lanes % 4 == 0
            assert 0 < s.mesh_pad_lanes < s.mesh_lanes
        stats = seen[0]
        waste = res["metrics"]["mesh_lane_padding_waste"]["value"]
        assert waste == 100 * stats.mesh_pad_lanes / stats.mesh_lanes
        # no chip: nothing for the trace reader
        assert "mesh_collective_s" not in res["metrics"]

        frontier_round = distributed._frontier_round

        def local_psum(*a, **k):
            psum, pmin = jax.lax.psum, jax.lax.pmin
            jax.lax.psum = jax.lax.pmin = lambda x, axis_name, **_: x
            try:
                return frontier_round(*a, **k)
            finally:
                jax.lax.psum, jax.lax.pmin = psum, pmin

        distributed._frontier_round = local_psum
        distributed._threshold_sharded_fn.cache_clear()    # traced anew
        broken = measure()
        assert broken["correct"] is False
        assert broken["checks"]["edges_wrong"]["value"] > 0
        print("MESH-CELL-OK", stats.mesh_lanes, stats.mesh_pad_lanes)
    """)
    assert "MESH-CELL-OK" in out


class _Job:
    def __init__(self, counters):
        self.counters = counters


class _Run:
    def __init__(self, tr, jobs=(), window=(0, 10000)):
        self.trace = tr
        self.trace_window = window if tr is not None else None
        self.completed = list(jobs)


def _op(name, opcode, start, end, shape="s32[4096]{0}"):
    return trace.Event(f"%{name} = {shape} {opcode}(s32[4096]{{0}} %p), "
                       "channel_id=1, replica_groups={{0,1,2,3}}",
                       start, end)


def _chips():
    """Two chips, window [0, 10000) ns.  Chip 0: a sync all-reduce of 1000
    ns, an async all-reduce pair of 200 + 300 ns, a fusion of 4000 ns, and
    an all-gather that starts before the window (only its 500 ns inside
    count).  Chip 1: a tuple-shaped all-reduce-start of 100 ns and a
    collective-permute-done of 400 ns."""
    return trace.Trace(
        ops={"/device:TPU:0": [
                _op("psum.14", "all-reduce", 1000, 2000),
                _op("all-reduce-start.1", "all-reduce-start", 3000, 3200,
                    "(s32[4096]{0}, s32[4096]{0})"),
                _op("all-reduce-done.1", "all-reduce-done", 3500, 3800),
                _op("fusion.3", "fusion", 4000, 8000),
                _op("all-gather.2", "all-gather", -500, 500)],
             "/device:TPU:1": [
                _op("all-reduce-start.2", "all-reduce-start", 100, 200,
                    "(s32[], s32[])"),
                _op("collective-permute-done.4", "collective-permute-done",
                    9000, 9400),
                _op("fusion.9", "fusion", 500, 600)]},
        modules={}, host_spans=[], profile_start_ns=None)


class _Counted:
    def __init__(self, lanes, pad):
        self.mesh_lanes, self.mesh_pad_lanes = lanes, pad


def test_collective_seconds_average_the_chips_and_divide_by_jobs():
    run = _Run(_chips(), jobs=[_Job(None), _Job(None)])
    chip0 = 1000 + 200 + 300 + 500
    chip1 = 100 + 400
    assert mesh_collective_s.read(run) == pytest.approx(
        (chip0 + chip1) / 2 / 2 * 1e-9)


@pytest.mark.parametrize("opcode", sorted(mesh_collective_s.COLLECTIVES))
def test_every_collective_opcode_is_found(opcode):
    tr = trace.Trace(ops={"/device:TPU:0": [_op("c.1", opcode, 0, 1000)]},
                     modules={}, host_spans=[], profile_start_ns=None)
    assert mesh_collective_s.read(_Run(tr, jobs=[_Job(None)])) == \
        pytest.approx(1e-6)


def test_collectives_read_zero_where_none_ran_and_nothing_without_ops():
    ops_only = trace.Trace(
        ops={"/device:TPU:0": [_op("fusion.1", "fusion", 0, 1000)]},
        modules={}, host_spans=[], profile_start_ns=None)
    assert mesh_collective_s.read(_Run(ops_only, jobs=[_Job(None)])) == 0
    no_ops = trace.Trace(ops={}, modules={}, host_spans=[],
                         profile_start_ns=None)
    assert mesh_collective_s.read(_Run(no_ops, jobs=[_Job(None)])) is None
    assert mesh_collective_s.read(_Run(None, jobs=[_Job(None)])) is None


def test_collectives_read_nothing_without_a_completed_job():
    assert mesh_collective_s.read(_Run(_chips(), jobs=[])) is None


def test_lane_padding_waste_averages_the_jobs():
    run = _Run(None, jobs=[_Job(_Counted(8, 2)), _Job(_Counted(8, 4))])
    assert mesh_lane_padding_waste.read(run) == pytest.approx(37.5)
    full = _Run(None, jobs=[_Job(_Counted(4, 0))])
    assert mesh_lane_padding_waste.read(full) == 0


def test_lane_padding_waste_reads_nothing_without_mesh_lanes():
    class Stats:     # a program that does not count mesh lanes
        padded_slots = 8

    assert mesh_lane_padding_waste.read(_Run(None, jobs=[])) is None
    assert mesh_lane_padding_waste.read(
        _Run(None, jobs=[_Job(Stats())])) is None
    assert mesh_lane_padding_waste.read(
        _Run(None, jobs=[_Job(_Counted(0, 0))])) is None


def test_recorded_four_chip_job_reads_its_all_reduces():
    """The chip recording of one warm scale-7 bottom-up job over a 4-chip
    ("data",) mesh of a TPU v5e host: every chip ran the triangle-sharded
    candidate peels' 90 all-reduces (a ``pmin`` and a ``psum`` per
    sub-round, 45 sub-rounds), and the reader averages their time over
    the four chips."""
    with gzip.open(os.path.join(HERE, "testdata", "mesh4_ooc.xplane.pb.gz"),
                   "rb") as f:
        tr = trace.from_bytes(f.read())
    assert sorted(tr.ops) == [f"/device:TPU:{i}" for i in range(4)]
    events = [ev for evs in tr.ops.values() for ev in evs]
    window = (min(ev.start_ns for ev in events),
              max(ev.end_ns for ev in events))
    per_chip = []
    for chip in tr.ops.values():
        opcodes = [(trace.instruction(ev.name)[1], ev) for ev in chip]
        found = [(op, ev) for op, ev in opcodes
                 if op in mesh_collective_s.COLLECTIVES]
        assert [op for op, _ in found] == ["all-reduce"] * 90
        per_chip.append(sum(ev.end_ns - ev.start_ns for _, ev in found))
    got = mesh_collective_s.read(_Run(tr, jobs=[_Job(None)], window=window))
    assert got == pytest.approx(sum(per_chip) / 4 * 1e-9)
    assert 0 < got < 1e-3
