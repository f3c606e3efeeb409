"""The trace reduction, on a hand-made trace and on one recorded on a
TPU v5e (a scale-7 bottom-up job with ``kernel="pallas"``, host Python
tracer off).  Runs on the CPU; reading a trace loads no TPU library."""

from __future__ import annotations

import gzip
import os

import pytest

from perfbench import trace
from perfbench.metrics import device_idle_share, fused_round_roofline

FIXTURE = os.path.join(os.path.dirname(__file__), "testdata",
                       "tiny_ooc.xplane.pb.gz")

# Two chips.  Chip 0: ops [1000, 5000) and [3000, 6000) overlap, then
# [8000, 9000); busy 6000 ns.  Chip 1: [2000, 4000); busy 2000 ns.
# Window [0, 10000): mean busy 4000 ns, idle 60%.  Over both chips the
# device is idle in [0, 1000), [6000, 8000) and [9000, 10000).
_HAND = '''
planes {
  id: 1 name: "/device:TPU:0"
  lines {
    id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 4000000 }
    events { metadata_id: 2 offset_ps: 3000000 duration_ps: 3000000 }
    events { metadata_id: 1 offset_ps: 8000000 duration_ps: 1000000 }
  }
  lines {
    id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 500000 duration_ps: 6000000 }
    events { metadata_id: 4 offset_ps: 7500000 duration_ps: 2000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = s32[8]{0} fusion(s32[8]{0} %p), kind=kLoop" } }
  event_metadata { key: 2 value { id: 2 name: "%while.2 = (s32[8]{0}) while((s32[8]{0}) %t), body=%b" } }
  event_metadata { key: 3 value { id: 3 name: "jit_peel_classes_fixedcap(123)" } }
  event_metadata { key: 4 value { id: 4 name: "jit_other(9)" } }
}
planes {
  id: 2 name: "/device:TPU:1"
  lines {
    id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 2000000 duration_ps: 2000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%copy.3 = s32[8]{0} copy(s32[8]{0} %p)" } }
}
planes {
  id: 3 name: "/host:CPU"
  lines {
    id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "perfbench.window" } }
}
'''


@pytest.fixture(scope="module")
def hand():
    from jax.profiler import ProfileData

    return trace.from_profile(ProfileData.from_text_proto(_HAND))


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(FIXTURE, "rb") as f:
        return trace.from_bytes(f.read())


class _Run:
    def __init__(self, tr, window, kind="TPU v5 lite"):
        self.trace, self.trace_window, self.device_kind = tr, window, kind


def test_union_of_overlapping_and_nested_intervals():
    assert trace.union_ns([(0, 10), (2, 3), (5, 15), (20, 25)]) == 20
    assert trace.union_ns([]) == 0
    assert trace.merged([(5, 6), (0, 2), (1, 3)]) == [(0, 3), (5, 6)]


def test_hand_trace_busy_idle_and_modules(hand):
    window = hand.window()
    assert window == (0.0, 10000.0)
    assert len(hand.ops) == 2
    assert hand.busy_s(window) == pytest.approx(4000e-9)
    assert device_idle_share.read(_Run(hand, window)) == pytest.approx(60.0)
    assert hand.module_s("peel_classes_fixedcap", window) == \
        pytest.approx(6000e-9)
    # a window that clips the module and the ops
    assert hand.busy_s((0.0, 4000.0)) == pytest.approx((3000 + 2000) / 2
                                                       * 1e-9)
    assert hand.module_s("peel_classes_fixedcap", (0.0, 4000.0)) == \
        pytest.approx(3500e-9)


def test_hand_trace_ranking_and_gaps(hand):
    window = hand.window()
    top = dict(hand.top_ops(window))
    # the while instruction only contains others and is left out
    assert top == {"jit_peel_classes_fixedcap:fusion.1 (fusion)":
                   pytest.approx(4000e-9),
                   "jit_other:fusion.1 (fusion)": pytest.approx(1000e-9),
                   "?:copy.3 (copy)": pytest.approx(2000e-9)}
    gaps = hand.idle_gaps(window, lambda lo, hi: f"{lo:.0f}-{hi:.0f}")
    assert gaps == [["6000-8000", pytest.approx(2000e-9)],
                    ["0-1000", pytest.approx(1000e-9)],
                    ["9000-10000", pytest.approx(1000e-9)]]


def test_no_device_events_reads_nothing():
    from jax.profiler import ProfileData

    host_only = trace.from_profile(ProfileData.from_text_proto(
        _HAND[_HAND.index("planes {\n  id: 3"):]))
    window = host_only.window()
    assert host_only.busy_s(window) is None
    assert device_idle_share.read(_Run(host_only, window)) is None
    assert fused_round_roofline.read(_Run(host_only, window)) is None


def _sweep_busy_ns(events, lo, hi):
    """Busy time by a coverage count over sorted boundaries, a second
    algorithm beside ``trace.union_ns``."""
    marks = []
    for ev in events:
        s, e = max(ev.start_ns, lo), min(ev.end_ns, hi)
        if e > s:
            marks += [(s, 1), (e, -1)]
    marks.sort()
    busy, depth, last = 0.0, 0, None
    for t, d in marks:
        if depth > 0:
            busy += t - last
        depth += d
        last = t
    return busy


def test_recorded_trace_layout(recorded):
    assert len(recorded.ops) == 1
    window = recorded.window("job")
    assert window == (45654848.0, 156851562.0)
    ops = recorded.ops["/device:TPU:0"]
    assert len(ops) == 1162
    busy = recorded.busy_s(window)
    assert busy == pytest.approx(_sweep_busy_ns(ops, *window) * 1e-9)
    idle = device_idle_share.read(_Run(recorded, window))
    assert idle == pytest.approx(100 * (1 - busy / ((window[1] - window[0])
                                                    * 1e-9)))
    assert 90 < idle < 100
    # the job's two fused peel programs, one classes loop and eleven
    # single-level candidate peels
    assert recorded.module_s("_peel_classes_fused_impl", window) == \
        pytest.approx(2126659e-9)
    assert recorded.module_s("_peel_threshold_fused_impl", window) == \
        pytest.approx(1418707e-9)


def test_recorded_trace_kernel_time_by_shape(recorded):
    window = recorded.window("job")
    calls = [ev for ev in recorded.op_events(window)
             if 'custom_call_target="tpu_custom_call"' in ev.name]
    assert len(calls) == 100
    assert {fused_round_roofline.round_shape(ev.name) for ev in calls} == \
        {(1, 1024, 4096)}
    got = fused_round_roofline.read(_Run(recorded, window))
    kernel_s = sum(ev.end_ns - ev.start_ns for ev in calls) * 1e-9
    assert got["calls"] == 100
    assert got["kernel_s"] == pytest.approx(kernel_s)
    assert got["bytes"] == 100 * (12 * 4096 + 20 * 1024)
    assert got["bound"] == "hbm_bytes"
    assert got["value"] == pytest.approx(
        100 * got["bytes"] / 819e9 / kernel_s)
    with pytest.raises(KeyError):
        fused_round_roofline.read(_Run(recorded, window, "TPU v9 imaginary"))


def test_custom_calls_none_of_them_the_round_is_an_error(recorded):
    """A kernel that still runs under a signature the reader does not know
    must not leave its roofline silent."""
    window = recorded.window("job")
    ops = {plane: [trace.Event(ev.name.replace(",1,1024]", ",1,1000,1]"),
                               ev.start_ns, ev.end_ns) for ev in evs]
           for plane, evs in recorded.ops.items()}
    renamed = trace.Trace(ops=ops, modules=recorded.modules,
                          host_spans=recorded.host_spans,
                          profile_start_ns=recorded.profile_start_ns)
    with pytest.raises(fused_round_roofline.SignatureNotFound):
        fused_round_roofline.read(_Run(renamed, window))
