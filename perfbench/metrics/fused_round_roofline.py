"""Share, in %, of the fused Pallas peel round's device time that the
least time for the bytes the round needs would take.

A call of ``kernels/frontier_peel.fused_round`` shows in the trace as a
``tpu_custom_call`` whose operands are three ``[B,1,E]`` (or ``[B,E]``)
edge-state rows (support, alive, frontier) and one ``[B,T,3]`` triangle
list, and whose result is two such rows (support, alive).  The trace's HLO
text carries no kernel name, so the call is found by that signature,
whatever the element types.  What the round needs, per lane, whatever
implements it and in whatever types: one read of the triangle list (12
bytes a triangle, three int32 edge ids), one read of support, alive and
the frontier and one write of support and alive (20 bytes an edge, int32
each); and at most one decrement per triangle corner (3 operations a
triangle).  The least time is the larger of bytes over the chip's HBM
bandwidth and operations over its peak rate, from ``peaks.json``; the
result says which bound applies.

Where the window holds ``tpu_custom_call`` events and none has the round's
signature, the reader raises: the kernel may still run under another
layout, and a roofline that went silent would hide it.
"""

from __future__ import annotations

import re

from perfbench import device

_TARGET = 'custom_call_target="tpu_custom_call"'
_ARRAY = re.compile(r"\b[a-z]+\d*\[([\d,]+)\]")


class SignatureNotFound(RuntimeError):
    """Custom calls ran, and none of them looks like the fused round."""


def _dims(text: str) -> list[tuple[int, ...]]:
    return [tuple(int(d) for d in m.split(",")) for m in _ARRAY.findall(text)]


def _row(dims: tuple[int, ...]):
    """(B, E) of an edge-state row shaped [B,1,E] or [B,E], else None."""
    if len(dims) == 3 and dims[1] == 1:
        return dims[0], dims[2]
    if len(dims) == 2:
        return dims
    return None


def round_shape(hlo_text: str):
    """(B, E, T) of a fused round call's HLO text, or None."""
    if _TARGET not in hlo_text or " custom-call(" not in hlo_text:
        return None
    result, rest = hlo_text.split(" custom-call(", 1)
    operands = _dims(rest.split(_TARGET, 1)[0])
    outs = _dims(result.split(" = ", 1)[-1])
    if len(operands) != 4 or len(outs) != 2:
        return None
    rows = [_row(d) for d in operands[:3] + outs]
    tris = operands[3]
    if None in rows or len(set(rows)) != 1:
        return None
    b, e = rows[0]
    if len(tris) != 3 or tris[0] != b or tris[2] != 3:
        return None
    return b, e, tris[1]


def round_bytes(b: int, e: int, t: int) -> int:
    return b * (12 * t + 20 * e)


def round_ops(b: int, e: int, t: int) -> int:
    return b * 3 * t


def read(run):
    if run.trace is None or run.trace_window is None:
        return None
    kernel_ns = 0.0
    nbytes = nops = calls = custom = 0
    for ev in run.trace.op_events(run.trace_window):
        if _TARGET not in ev.name:
            continue
        custom += 1
        shape = round_shape(ev.name)
        if shape is None:
            continue
        calls += 1
        kernel_ns += ev.end_ns - ev.start_ns
        nbytes += round_bytes(*shape)
        nops += round_ops(*shape)
    if custom and not calls:
        raise SignatureNotFound(
            f"{custom} tpu_custom_call events in the traced window and none "
            "with the fused round's signature (three [B,1,E] rows and a "
            "[B,T,3] triangle list in, two rows out); the reader has to "
            "learn the kernel's new signature")
    if not calls or kernel_ns <= 0:
        return None
    peak = device.peaks(run.device_kind)
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    t_ops = nops / peak["bf16_flops_per_s"]
    return {"value": 100.0 * max(t_bytes, t_ops) / (kernel_ns * 1e-9),
            "bound": "hbm_bytes" if t_bytes >= t_ops else "operations",
            "calls": calls, "kernel_s": kernel_ns * 1e-9, "bytes": nbytes}
