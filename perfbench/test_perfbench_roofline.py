"""The bytes and operations a fused peel round needs, from its shapes."""

from __future__ import annotations

import pytest

from perfbench.metrics import fused_round_roofline as fr

# one 16-lane call as the TPU v5e trace names it (a bottom-up bucket)
_CALL_16 = (
    "%body.8 = (s32[16,1,4096]{2,1,0:T(1,128)S(1)}, "
    "s32[16,1,4096]{2,1,0:T(1,128)S(1)}) custom-call("
    "s32[16,1,4096]{2,1,0:T(1,128)S(1)} %broadcast_in_dim.69, "
    "s32[16,1,4096]{2,1,0:T(1,128)S(1)} %copy.3, "
    "s32[16,1,4096]{2,1,0:T(1,128)S(1)} %fusion.2, "
    "s32[16,16384,3]{2,1,0:T(8,128)S(1)} %get-tuple-element.9), "
    'custom_call_target="tpu_custom_call", operand_layout_constraints='
    "{s32[16,1,4096]{2,1,0}, s32[16,1,4096]{2,1,0}, s32[16,1,4096]{2,1,0}, "
    "s32[16,16384,3]{2,1,0}}, frontend_attributes={kernel_metadata={}}")


@pytest.mark.parametrize("b, e, t, want", [
    # per lane: 12 bytes a triangle (3 int32 ids, read once) and 20 bytes
    # an edge (support, alive and the frontier, int32, each read once;
    # support and alive written once)
    (1, 1024, 4096, 4096 * 12 + 1024 * 20),            # 69,632
    (16, 4096, 16384, 16 * (16384 * 12 + 4096 * 20)),  # 4,456,448
    (8, 11631, 12800, 8 * (12800 * 12 + 11631 * 20)),  # 3,089,760
])
def test_round_bytes_hand_counted(b, e, t, want):
    assert fr.round_bytes(b, e, t) == want


def test_round_ops_one_decrement_per_corner():
    assert fr.round_ops(16, 4096, 16384) == 16 * 16384 * 3


def test_round_shape_from_trace_name():
    assert fr.round_shape(_CALL_16) == (16, 4096, 16384)


@pytest.mark.parametrize("text", [
    # the same round with narrower element types
    _CALL_16.replace("s32[16,1,4096]", "s8[16,1,4096]"),
    _CALL_16.replace("s32[16,16384,3]", "u16[16,16384,3]"),
    # the same round on (B, E) rows
    _CALL_16.replace("[16,1,4096]", "[16,4096]"),
])
def test_round_shape_survives_type_and_row_layout(text):
    assert fr.round_shape(text) == (16, 4096, 16384)


@pytest.mark.parametrize("text", [
    # another kernel: a (bm, bk) x (bk, bn) tile call
    "%c.1 = f32[512,512]{1,0} custom-call(bf16[512,512]{1,0} %a, "
    'bf16[512,512]{1,0} %b), custom_call_target="tpu_custom_call"',
    # rows of another lane count than the triangle list's
    _CALL_16.replace("s32[16,16384,3]", "s32[8,16384,3]"),
    # a plain fusion
    "%fusion.1 = s32[8]{0} fusion(s32[8]{0} %p), kind=kLoop",
])
def test_round_shape_ignores_other_calls(text):
    assert fr.round_shape(text) is None


def test_memory_bound_at_every_lane_shape():
    # 3 operations against 12 bytes a triangle: far below the v5e's
    # 197e12 / 819e9 = 240 operations a byte, so bytes set the bound
    b, e, t = 16, 4096, 16384
    assert fr.round_ops(b, e, t) / fr.round_bytes(b, e, t) < 240
