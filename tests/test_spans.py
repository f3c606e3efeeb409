"""The program's ``truss.*`` host spans (``repro.core.spans``), recorded
under a CPU profiler session around five small jobs: in memory, bottom-up
with the XLA lanes, top-down with a budget, top-down reached through
``truss_decompose``, and bottom-up over a mesh of the devices there are.
One file, so that a single test worker holds the one profiler session."""

from __future__ import annotations

import glob
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import graph as glib
from repro.core import spans
from repro.core.peel import estimate_working_set, truss_decompose
from repro.core.support import list_triangles_np
from repro.core.top_down import top_down_decompose
from tests.conftest import rmat_graph

N, EDGES = rmat_graph(scale=6, edge_factor=8, seed=3)
GRAPH = glib.build_graph(N, EDGES)
BUDGET = estimate_working_set(GRAPH) // 4      # working-set entries
PART_BUDGET = max(64, GRAPH.m // 2)            # NS edge cost


def _td(res):
    return res.phi, res.stats


JOBS = {
    "inmem": lambda: truss_decompose(N, EDGES, with_stats=True),
    "bottom_up": lambda: truss_decompose(
        N, EDGES, engine="bottom-up", memory_budget=BUDGET, kernel="xla",
        with_stats=True),
    "top_down": lambda: _td(top_down_decompose(
        N, EDGES, budget=PART_BUDGET, kernel="xla")),
    "routed": lambda: truss_decompose(
        N, EDGES, engine="top-down", memory_budget=BUDGET, kernel="xla",
        with_stats=True),
    "mesh": lambda: truss_decompose(
        N, EDGES, engine="bottom-up", memory_budget=BUDGET, kernel="xla",
        mesh=jax.make_mesh((len(jax.devices()),), ("data",)),
        with_stats=True),
}
# spans each path must open; others may appear (a retry, say)
EXPECTED = {
    "inmem": {"job", "build_graph", "list_triangles", "incidence", "upload",
              "dispatch", "device_wait"},
    "bottom_up": {"job", "build_graph", "list_triangles", "incidence",
                  "upload", "round_build", "candidate_build", "dispatch",
                  "device_wait"},
    "top_down": {"job", "build_graph", "list_triangles", "incidence",
                 "upload", "round_build", "support_credit",
                 "candidate_build", "prune", "dispatch", "device_wait"},
}
EXPECTED["routed"] = EXPECTED["top_down"]
EXPECTED["mesh"] = EXPECTED["bottom_up"]


class _Span:
    def __init__(self, ev):
        self.name = ev.name[len(spans.PREFIX):]
        self.start, self.end = ev.start_ns, ev.end_ns
        self.stats = {key: value for key, value in ev.stats}

    def within(self, other) -> bool:
        return other.start <= self.start and self.end <= other.end


@pytest.fixture(scope="module")
def traced():
    """Each job's answer with the profiler off and on, its counters, and
    the spans of its ``truss.job``."""
    from jax.profiler import ProfileData

    off = {kind: job() for kind, job in JOBS.items()}   # also compiles
    tdir = tempfile.mkdtemp(prefix="truss-spans-")
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tdir, profiler_options=opts)
    try:
        on = {kind: job() for kind, job in JOBS.items()}
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                      recursive=True)
    data = ProfileData.from_file(path)
    # the line is found by what it holds: on the CPU it is named after
    # the process, on a TPU host "python"
    lines = [[_Span(ev) for ev in line.events
              if ev.name.startswith(spans.PREFIX)]
             for plane in data.planes for line in plane.lines]
    lines = [line for line in lines if line]
    assert len(lines) == 1
    found = sorted(lines[0], key=lambda s: (s.start, -s.end))
    jobs = [s for s in found if s.name == "job"]
    assert len(jobs) == len(JOBS)     # the routed call opens one, not two
    per_job = {kind: [s for s in found if s.within(job)]
               for kind, job in zip(JOBS, jobs)}
    return off, on, found, per_job


def test_every_span_is_named_in_the_contract_and_lies_in_one_job(traced):
    _, _, found, _ = traced
    jobs = [s for s in found if s.name == "job"]
    for s in found:
        assert s.name in spans.NAMES
        assert s.end >= s.start
        if s.name != "job":
            assert sum(s.within(job) for job in jobs) == 1


@pytest.mark.parametrize("kind", list(JOBS))
def test_each_path_opens_its_spans(traced, kind):
    _, _, _, per_job = traced
    names = {s.name for s in per_job[kind]}
    assert EXPECTED[kind] <= names
    job, = [s for s in per_job[kind] if s.name == "job"]
    engine = {"inmem": "auto", "bottom_up": "bottom-up",
              "mesh": "bottom-up"}.get(kind, "top-down")
    assert job.stats == {"engine": engine, "n": N, "m": len(EDGES)}


@pytest.mark.parametrize("kind", list(JOBS))
def test_profiler_leaves_the_answer_alone(traced, kind):
    off, on, _, _ = traced
    assert np.array_equal(np.asarray(off[kind][0]), np.asarray(on[kind][0]))


@pytest.mark.parametrize("kind", list(JOBS))
def test_upload_bytes_are_the_counter(traced, kind):
    _, on, _, per_job = traced
    total = sum(s.stats["bytes"] for s in per_job[kind]
                if s.name == "upload")
    assert total > 0
    assert on[kind][1].h2d_bytes == total


def test_in_memory_counts_are_the_arrays(traced):
    _, _, _, per_job = traced
    got = per_job["inmem"]
    T = len(list_triangles_np(GRAPH))
    m = GRAPH.m
    listing, = [s for s in got if s.name == "list_triangles"]
    assert listing.stats == {"triangles": T}
    graph, = [s for s in got if s.name == "build_graph"]
    assert graph.stats == {"m": m}
    incidence, = [s for s in got if s.name == "incidence"]
    assert incidence.stats == {"slots": 3 * T}
    # int32 supports and triangles, then the incidence CSR: indptr (m + 1)
    # and 3T triangle ids; the alive mask is made on the device
    assert sum(s.stats["bytes"] for s in got if s.name == "upload") == \
        4 * m + 12 * T + 4 * (m + 1) + 12 * T
    dispatch, = [s for s in got if s.name == "dispatch"]
    assert dispatch.stats["engine"] == "frontier"
    # compiled off the trace; the trace keeps a bool as 0 or 1
    assert dispatch.stats["new_compile"] == 0
    wait, = [s for s in got if s.name == "device_wait"]
    assert wait.stats == {"resumes": 0}


def test_partition_rounds_count_their_lanes(traced):
    _, on, _, per_job = traced
    rounds = [s for s in per_job["bottom_up"] if s.name == "round_build"]
    stats = on["bottom_up"][1]
    assert [s.stats["round"] for s in rounds] == \
        list(range(1, stats.rounds + 1))
    assert sum(s.stats["padded_slots"] for s in rounds) == stats.padded_slots
    assert sum(s.stats["real_edges"] for s in rounds) == stats.real_edges
    # stage-1 buckets and stage-2 candidates; triangle-free ones stay on
    # the host and dispatch nothing
    dispatches = [s for s in per_job["bottom_up"] if s.name == "dispatch"]
    assert {s.stats["engine"] for s in dispatches} == {"xla"}
    assert sum(s.stats["lanes"] for s in dispatches) == stats.xla_lanes


def test_mesh_dispatches_count_their_devices_and_dead_lanes(traced):
    _, on, _, per_job = traced
    stats = on["mesh"][1]
    devices = len(jax.devices())
    dispatches = [s for s in per_job["mesh"] if s.name == "dispatch"]
    lanes = [s for s in dispatches if s.stats["engine"] == "mesh"]
    threshold = [s for s in dispatches
                 if s.stats["engine"] == "mesh_threshold"]
    assert lanes and threshold
    assert len(lanes) + len(threshold) == len(dispatches)
    assert all(s.stats["devices"] == devices for s in dispatches)
    # bottom-up's rounds pack whole multiples of the lane axis, so the
    # dispatch pads none and its lanes are the counter's
    assert sum(s.stats["lanes"] for s in lanes) == stats.mesh_lanes
    assert sum(s.stats["pad_lanes"] for s in lanes) == stats.mesh_pad_lanes
    assert 0 <= stats.mesh_pad_lanes < stats.mesh_lanes
    assert stats.sharded_rounds == len(lanes) + len(threshold)
    for s in threshold:
        assert s.stats["tri_rows"] > 0 and s.stats["lanes"] == 1
    # the lanes' inputs are copied inside their dispatch
    uploads = [s for s in per_job["mesh"] if s.name == "upload"]
    assert all(any(u.within(d) for d in dispatches) for u in uploads)


def test_support_credit_counts_every_triangle_once(traced):
    _, _, _, per_job = traced
    credits = [s for s in per_job["top_down"] if s.name == "support_credit"]
    assert sum(s.stats["triangles"] for s in credits) == \
        len(list_triangles_np(GRAPH))


def test_span_refuses_device_and_numpy_values():
    with pytest.raises(TypeError):
        spans.span("upload", bytes=jnp.int32(1))
    with pytest.raises(TypeError):
        spans.span("upload", bytes=np.int64(1))
    with spans.span("round_build", round=1) as sp:
        with pytest.raises(TypeError):
            sp.count(lanes=jnp.ones(2))
        sp.count(lanes=2, engine="xla", share=0.5, new_compile=True)
    with pytest.raises(ValueError):
        spans.span("no_such_span")
