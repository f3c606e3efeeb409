"""The readers of the program's ``truss.*`` spans, on a hand-made trace
and on the recorded traces.  Runs on the CPU."""

from __future__ import annotations

import gzip
import os

import pytest

from perfbench import spans, trace
from perfbench.metrics import (device_wait_s, h2d_bytes, host_bound_share,
                               incidence_s, listing_s, round_build_s)

HERE = os.path.dirname(__file__)
SPAN_READERS = (listing_s, incidence_s, round_build_s, device_wait_s,
                host_bound_share)

# One chip, window [0, 10000) ns, ops [1000, 2000) and [6000, 7000): idle
# [0, 1000), [2000, 6000) and [7000, 10000), 8000 ns in all.  The host:
#   truss.job            [500, 9500)
#     list_triangles     [600, 3000)
#     round_build        [3000, 5000)
#       incidence        [3500, 4000)
#     dispatch           [5000, 5200)
#     device_wait        [5200, 7500)
#     edge_support       [8000, 8500)
# and a truss.upload that ends after the window, which no reader counts.
_SPANS = [("perfbench.window", 0, 10000), ("truss.job", 500, 9500),
          ("truss.list_triangles", 600, 3000),
          ("truss.round_build", 3000, 5000), ("truss.incidence", 3500, 4000),
          ("truss.dispatch", 5000, 5200), ("truss.device_wait", 5200, 7500),
          ("truss.edge_support", 8000, 8500),
          ("truss.upload", 9900, 10100)]


def _proto(host_spans) -> str:
    names = sorted({n for n, _, _ in host_spans})
    ids = {n: i + 1 for i, n in enumerate(names)}
    events = "".join(
        f"    events {{ metadata_id: {ids[n]} offset_ps: {s * 1000} "
        f"duration_ps: {(e - s) * 1000} }}\n" for n, s, e in host_spans)
    meta = "".join(
        f'  event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}\n'
        for n, i in ids.items())
    return f'''
planes {{
  id: 1 name: "/device:TPU:0"
  lines {{
    id: 1 name: "XLA Ops" timestamp_ns: 0
    events {{ metadata_id: 1 offset_ps: 1000000 duration_ps: 1000000 }}
    events {{ metadata_id: 1 offset_ps: 6000000 duration_ps: 1000000 }}
  }}
  event_metadata {{ key: 1 value {{ id: 1 name: "%fusion.1 = s32[8]{{0}} fusion(s32[8]{{0}} %p), kind=kLoop" }} }}
}}
planes {{
  id: 2 name: "/host:CPU"
  lines {{
    id: 1 name: "python" timestamp_ns: 0
{events}  }}
{meta}}}
'''


def _trace(host_spans):
    from jax.profiler import ProfileData

    return trace.from_profile(ProfileData.from_text_proto(_proto(host_spans)))


class _Job:
    def __init__(self, counters=None):
        self.counters = counters


class _Run:
    def __init__(self, tr, jobs=2, window=None, counters=None):
        self.trace = tr
        self.trace_window = window or (tr.window() if tr else None)
        self.device_kind = "TPU v5 lite"
        self.completed = [_Job(counters) for _ in range(jobs)]


@pytest.fixture(scope="module")
def hand():
    return _Run(_trace(_SPANS))


def test_spans_inside_the_window_parent_first(hand):
    got = spans.in_window(hand)
    assert [ev.name for ev in got] == [n for n, _, _ in _SPANS[1:-1]]
    assert spans.self_ns(got) == [1600, 2400, 1500, 500, 200, 2300, 500]


@pytest.mark.parametrize("reader, per_job_ns", [
    (listing_s, 2400 + 500),       # list_triangles and edge_support
    (incidence_s, 500),
    (round_build_s, 2000 - 500),   # net of its incidence child
    (device_wait_s, 2300),         # a duration; the wait has no child
])
def test_span_seconds_per_completed_job(hand, reader, per_job_ns):
    assert reader.read(hand) == pytest.approx(per_job_ns * 1e-9 / 2)


def test_host_bound_share_splits_the_idle_time(hand):
    got = host_bound_share.read(hand)
    # idle under list_triangles 400 + 1000, round_build 1500, incidence
    # 500, dispatch 200, edge_support 500: 4100 of 10000 ns
    assert got["value"] == pytest.approx(41.0)
    assert got["by_span"] == {
        "truss.dispatch": pytest.approx(100e-9),
        "truss.edge_support": pytest.approx(250e-9),
        "truss.incidence": pytest.approx(250e-9),
        "truss.list_triangles": pytest.approx(700e-9),
        "truss.round_build": pytest.approx(750e-9)}
    assert got["job_glue_s"] == pytest.approx(1600e-9 / 2)
    assert got["wait_idle_s"] == pytest.approx(1300e-9 / 2)
    assert got["outside_s"] == pytest.approx(1000e-9 / 2)
    assert got["job_covered"] == pytest.approx(100 * 7400 / 9000)
    # the parts add up to the device's idle time
    idle = 1e-9 * 10000 * (1 - hand.trace.busy_s(hand.trace_window) / 1e-5)
    assert 2 * (sum(got["by_span"].values()) + got["job_glue_s"]
                + got["wait_idle_s"] + got["outside_s"]) == \
        pytest.approx(idle)


def test_nothing_to_read_without_spans():
    bare = _Run(_trace([("perfbench.window", 0, 10000)]))
    assert all(r.read(bare) is None for r in SPAN_READERS)
    assert all(r.read(_Run(None)) is None for r in SPAN_READERS)


def test_no_completed_job_reads_nothing():
    empty = _Run(_trace(_SPANS), jobs=0)
    assert all(r.read(empty) is None for r in SPAN_READERS)


def test_a_layer_that_did_not_run_reads_nothing():
    no_incidence = _Run(_trace([s for s in _SPANS
                                if s[0] != "truss.incidence"]))
    assert incidence_s.read(no_incidence) is None
    assert round_build_s.read(no_incidence) == pytest.approx(2000e-9 / 2)


def test_h2d_bytes_reads_the_counter():
    class Stats:
        h2d_bytes = 3_000_000

    tr = _trace(_SPANS)
    assert h2d_bytes.read(_Run(tr, counters=Stats())) == pytest.approx(3.0)
    assert h2d_bytes.read(_Run(tr, counters=object())) is None
    assert h2d_bytes.read(_Run(tr, jobs=0, counters=Stats())) is None
    assert h2d_bytes.read(_Run(None, counters=Stats())) is None


def test_a_run_without_a_chip_reads_nothing():
    """Spans and counters, and no device operation in the trace."""
    class Stats:
        h2d_bytes = 3_000_000

    host_only = _trace(_SPANS)
    host_only.ops.clear()
    run = _Run(host_only, counters=Stats())
    assert all(r.read(run) is None for r in SPAN_READERS + (h2d_bytes,))


def test_a_trace_recorded_before_the_spans_reads_nothing():
    """The readers on a program without spans: the earlier recording of a
    bottom-up job."""
    with gzip.open(os.path.join(HERE, "testdata", "tiny_ooc.xplane.pb.gz"),
                   "rb") as f:
        old = trace.from_bytes(f.read())
    run = _Run(old, window=old.window("job"))
    assert all(r.read(run) is None for r in SPAN_READERS)


@pytest.fixture(scope="module")
def recorded():
    """The chip recording of one warm scale-7 bottom-up job with
    ``kernel="pallas"``, run from a process started as ``python3``: the
    harness's reduction (``trace.from_bytes``) and the same trace with the
    host spans of the line that holds the program's spans."""
    from jax.profiler import ProfileData

    with gzip.open(os.path.join(HERE, "testdata", "spans_ooc.xplane.pb.gz"),
                   "rb") as f:
        raw = f.read()
    harness_view = trace.from_bytes(raw)
    lines = {line.name: list(line.events)
             for plane in ProfileData.from_serialized_xspace(raw).planes
             if plane.name == trace.HOST_PLANE for line in plane.lines}
    held = [name for name, evs in lines.items()
            if any(ev.name.startswith(spans.PREFIX) for ev in evs)]
    assert held == ["python3"]
    events = lines["python3"]
    fixed = trace.Trace(
        ops=harness_view.ops, modules=harness_view.modules,
        host_spans=[trace.Event(ev.name, ev.start_ns, ev.end_ns)
                    for ev in events],
        profile_start_ns=harness_view.profile_start_ns)
    stats = [(ev.name, {k: v for k, v in ev.stats}) for ev in events
             if ev.name.startswith(spans.PREFIX)]
    return harness_view, _Run(fixed, jobs=1), stats


def test_recorded_spans_are_on_the_process_line(recorded):
    """``trace.from_profile`` keeps the host line named ``python``; on a
    TPU host the main thread's line is named after the process, so the
    harness's reduction of this trace holds no span and no window."""
    harness_view, _, _ = recorded
    assert harness_view.host_spans == []
    assert harness_view.window() is None


def test_recorded_spans_of_a_bottom_up_job(recorded):
    _, run, stats = recorded
    names = {name for name, _ in stats}
    assert {"truss.job", "truss.build_graph", "truss.round_build",
            "truss.list_triangles", "truss.incidence", "truss.upload",
            "truss.dispatch", "truss.device_wait",
            "truss.candidate_build"} <= names
    job, = [s for name, s in stats if name == "truss.job"]
    assert job["engine"] == "bottom-up"
    # the job's OocStats.h2d_bytes on the chip
    assert sum(s["bytes"] for name, s in stats if name == "truss.upload") \
        == 1014784
    assert {s["engine"] for name, s in stats if name == "truss.dispatch"} \
        == {"pallas"}


def test_readers_on_the_recorded_job(recorded):
    from perfbench.metrics import device_idle_share

    _, run, _ = recorded
    found = spans.in_window(run)
    own = dict.fromkeys((ev.name for ev in found), 0)
    for ev, ns in zip(found, spans.self_ns(found)):
        own[ev.name] += ns * 1e-9
    assert listing_s.read(run) == pytest.approx(own["truss.list_triangles"])
    assert incidence_s.read(run) == pytest.approx(own["truss.incidence"])
    assert round_build_s.read(run) == pytest.approx(
        own["truss.round_build"] + own["truss.candidate_build"])
    assert device_wait_s.read(run) == pytest.approx(sum(
        ev.end_ns - ev.start_ns for ev in found
        if ev.name == spans.WAIT) * 1e-9)
    got = host_bound_share.read(run)
    idle = device_idle_share.read(run)
    assert 0 < got["value"] <= idle
    window_s = (run.trace_window[1] - run.trace_window[0]) * 1e-9
    assert (sum(got["by_span"].values()) + got["job_glue_s"]
            + got["wait_idle_s"] + got["outside_s"]) == \
        pytest.approx(idle / 100 * window_s)
    assert got["job_covered"] > 90


def test_recorded_fused_round_found_by_name_and_signature(recorded):
    """The kernel's ``name=`` becomes the HLO instruction name that heads
    each ``XLA Ops`` event, and the signature match still finds it."""
    from perfbench.metrics import fused_round_roofline

    _, run, _ = recorded
    calls = [ev for ev in run.trace.op_events(run.trace_window)
             if 'custom_call_target="tpu_custom_call"' in ev.name]
    assert len(calls) == 120
    assert {trace.instruction(ev.name)[0].split(".")[0] for ev in calls} \
        == {"fused_round"}
    assert all(fused_round_roofline.round_shape(ev.name) for ev in calls)
    assert fused_round_roofline.read(run)["calls"] == 120
