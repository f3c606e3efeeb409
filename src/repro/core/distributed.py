"""The batched out-of-core engine over a mesh (shard_map; DESIGN.md §10).

``peel_classes_batched_sharded`` / ``local_threshold_peel_sharded`` are the
pod-spanning form of the batched out-of-core engine: one partition round's
``partition.PartBucket`` lanes are split over a mesh axis (lanes are
independent subproblems, so the per-lane peels need no communication), and
the per-k candidate peel of both drivers runs with its triangle list
sharded — a ``pmin`` agrees on the frontier prefix and a ``psum`` merges
the decrements, so the replicated edge state stays in lockstep.  A
``(lane, tri)`` axis pair additionally shards each lane's triangles over
the second axis (DESIGN.md §13).  ``peel.peel_classes_batched`` /
``peel.local_threshold_peel`` dispatch here when a ``mesh=`` is supplied,
keeping the drivers' double-buffered non-blocking rounds — and the stage-2
candidate pipeline's pre-built supersets with their ``alive0`` dead-edge
masks (DESIGN.md §11) — intact across the mesh: the replicated edge state
simply starts with the masked edges dead, so they never enter any shard's
frontier.
"""

from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.partition import round_up_to_multiple
from repro.core.peel import (N_STATS, _frontier_round, _mesh_size,
                             _peel_classes_vmapped_impl,
                             peel_classes_fixedcap)
from repro.core.spans import upload
from repro.core.support import _pow2_ceil, triangle_incidence_np


def _shard_map(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the replication check off: trip counts are
    data-dependent per shard."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


# ---------------------------------------------------------------------------
# Triangle-sharded incidence
# ---------------------------------------------------------------------------

def _sharded_caps(m: int, indptr_s: np.ndarray,
                  tids_s: np.ndarray) -> tuple[int, int]:
    """Frontier capacities for a triangle-sharded peel: ``cap_t`` covers
    the largest per-shard incidence row, so every shard fits at least one
    edge's row and the pmin-agreed prefix is never empty — progress is
    guaranteed without an overflow/resume path."""
    max_row = int((indptr_s[:, 1:] - indptr_s[:, :-1]).max()) if m else 1
    n_inc = tids_s.shape[1]
    cap_f = _pow2_ceil(min(max(m, 1), max(256, m // 16)))
    cap_t = _pow2_ceil(min(max(n_inc, 1), max(max_row, 512, n_inc // 16)))
    return cap_f, max(cap_t, _pow2_ceil(max_row))


def shard_incidence(tris: np.ndarray, m: int, n_shards: int):
    """Per-shard edge→triangle incidence over contiguous triangle shards.

    ``tris`` (T_pad, 3) with T_pad divisible by ``n_shards``; triangle ids in
    each shard's CSR are LOCAL to the shard (matching the tris rows that
    shard_map hands each device).  Returns (indptr_s (S, m+1), tids_s (S, L))
    with L = 3 * T_pad / S, the most incidence slots a shard can hold: the
    shapes follow the padded triangle list's and not its contents, so
    candidates padded to one shape share one compiled program.
    """
    t_loc = len(tris) // n_shards
    per = [triangle_incidence_np(tris[i * t_loc:(i + 1) * t_loc], m)
           for i in range(n_shards)]
    L = max(3 * t_loc, 1)
    indptr_s = np.zeros((n_shards, m + 1), np.int32)
    tids_s = np.zeros((n_shards, L), np.int32)
    for i, (indptr, tids) in enumerate(per):
        indptr_s[i] = indptr
        tids_s[i, : len(tids)] = tids
    return indptr_s, tids_s


# ---------------------------------------------------------------------------
# Pod-spanning batched OOC rounds (DESIGN.md §10)
# ---------------------------------------------------------------------------

def pad_bucket_lanes(sup_b, tris_b, indptr_b, tids_b, alive_b, n_lanes: int):
    """Padding of a bucket's lane dimension to
    ``n_lanes``: appended lanes are dead (alive False, sup 0, every triangle
    row on the per-lane drop slot cap_e, empty incidence), so they exit the
    peel's while loop immediately and can never contribute support."""
    B, cap_e = sup_b.shape
    if n_lanes == B:
        return sup_b, tris_b, indptr_b, tids_b, alive_b
    pad = n_lanes - B
    return (
        np.concatenate([sup_b, np.zeros((pad, cap_e), np.int32)]),
        np.concatenate(
            [tris_b, np.full((pad,) + tris_b.shape[1:], cap_e, np.int32)]),
        np.concatenate([indptr_b, np.zeros((pad, cap_e + 1), np.int32)]),
        np.concatenate([tids_b, np.zeros((pad, tids_b.shape[1]), np.int32)]),
        np.concatenate([alive_b, np.zeros((pad, cap_e), bool)]),
    )


def _axes_tuple(axis) -> tuple:
    """Normalize an axis knob (one name or a sequence) to a tuple."""
    return (axis,) if isinstance(axis, str) else tuple(axis)


def shard_incidence_lanes(tris_b: np.ndarray, cap_e: int, n_shards: int):
    """Lane-wise :func:`shard_incidence`: per-lane per-shard edge→triangle
    incidence over contiguous triangle shards.

    ``tris_b`` (B, T, 3) with T divisible by ``n_shards``; triangle ids in
    each shard's CSR are LOCAL to the (lane, shard) rows shard_map hands
    each device.  Returns (indptr_ls (B, S, cap_e+1), tids_ls (B, S, L))
    with L = 3 * T / S, the most incidence slots a (lane, shard) can hold:
    one static shape per bucket shape, whatever the triangles.
    """
    B, T = tris_b.shape[0], tris_b.shape[1]
    t_loc = T // n_shards
    per = [[triangle_incidence_np(tris_b[b, i * t_loc:(i + 1) * t_loc],
                                  cap_e)
            for i in range(n_shards)] for b in range(B)]
    L = max(3 * t_loc, 1)
    indptr_ls = np.zeros((B, n_shards, cap_e + 1), np.int32)
    tids_ls = np.zeros((B, n_shards, L), np.int32)
    for b in range(B):
        for i, (indptr, tids) in enumerate(per[b]):
            indptr_ls[b, i] = indptr
            tids_ls[b, i, : len(tids)] = tids
    return indptr_ls, tids_ls


@lru_cache(maxsize=None)
def _batched_sharded2_fn(mesh, lane_axis: str, tri_axis: str, cap_f: int,
                         cap_t: int):
    """jit(shard_map) of the TWO-AXIS batched peel (DESIGN.md §13): lanes
    split over ``lane_axis`` while each lane's triangle list + incidence
    shard over ``tri_axis``.  Edge state is sharded by lane and replicated
    across the triangle axis, so inside each lane's vmapped
    ``peel_classes_fixedcap`` the frontier prefix is agreed by pmin and
    decrements merged by psum over ``tri_axis`` — a bucket with fewer lanes
    than devices still spreads every lane's round across the second axis."""

    def local(sup, tris, indptr, tids, alive):
        def one(s, t, ip, ti, a):
            Em = s.shape[0]
            phi0 = jnp.zeros(Em, jnp.int32)
            st0 = jnp.zeros(N_STATS, jnp.int32)
            _, _, phi, _, st, _ = peel_classes_fixedcap(
                s, t, ip.reshape(-1), ti.reshape(-1), a, phi0,
                jnp.int32(2), st0, cap_f=cap_f, cap_t=cap_t, axis=tri_axis)
            return phi, st

        return jax.vmap(one)(sup, tris, indptr, tids, alive)

    fn = _shard_map(
        local, mesh,
        in_specs=(P(lane_axis), P(lane_axis, tri_axis),
                  P(lane_axis, tri_axis), P(lane_axis, tri_axis),
                  P(lane_axis)),
        out_specs=(P(lane_axis), P(lane_axis)),
    )
    return jax.jit(fn, donate_argnums=(0,))


@lru_cache(maxsize=None)
def _batched_sharded_fn(mesh, axis: str, cap_f: int, cap_t: int):
    """jit(shard_map(·)) of ``peel._peel_classes_vmapped_impl`` — each
    device runs the SAME per-lane vmapped kernel as the single-device path
    on its lane slice; cached per (mesh, caps) so the compile cache stays
    keyed on the pow2/pow4 bucket-shape lattice."""
    fn = _shard_map(
        partial(_peel_classes_vmapped_impl, cap_f=cap_f, cap_t=cap_t),
        mesh,
        in_specs=(P(axis), P(axis), P(axis), P(axis), P(axis)),
        out_specs=(P(axis), P(axis)),
    )
    # sup is donated exactly like the single-device path: rebuilt from
    # scratch by the host every round, layout matching the phi output
    return jax.jit(fn, donate_argnums=(0,))


def peel_classes_batched_sharded(mesh, sup_b, tris_b, indptr_b, tids_b,
                                 alive_b, *, cap_f: int, cap_t: int,
                                 axis: str = "data"):
    """One bucket's NS lanes peeled across the mesh (DESIGN.md §10).

    The lane dimension of the (B, ...) ``partition.PartBucket`` stacks is
    split over ``axis``; lanes are disjoint subproblems, so each device
    peels its slice to its own fixed point with NO communication — the
    pod-wide form of ``peel.peel_classes_batched``'s vmapped kernel.  The
    lane count is first padded to a multiple of the axis size with dead
    lanes (:func:`pad_bucket_lanes`); ``partition.build_partition_batch``'s
    ``lane_multiple`` pre-pads batches so this is normally a no-op, with
    the waste visible in ``OocStats.padding_waste``.

    With ``axis`` a TUPLE (lane_axis, tri_axis) the bucket spans a
    multi-axis mesh (DESIGN.md §13): lanes pad to a multiple of the lane
    axis only, and each lane's triangle rows (padded to a multiple of the
    triangle axis) shard over the second axis with a per-(lane, shard)
    incidence CSR (:func:`shard_incidence_lanes`) — pmin/psum over
    ``tri_axis`` keep the replicated per-lane edge state in lockstep.  The
    caller's ``cap_t`` covers the largest whole-lane incidence row, which
    bounds every shard-local row, so progress stays guaranteed.

    Returns DEVICE arrays ``(phi, stats)`` over the PADDED lane count —
    still futures at return time, so the caller's host work overlaps the
    pod-wide peel; slice back to the original B when materializing — and
    the bytes the inputs' copy to the device took (one ``truss.upload``).
    """
    axes = _axes_tuple(axis)
    n_lane = int(mesh.shape[axes[0]])
    arrs = pad_bucket_lanes(
        sup_b, tris_b, indptr_b, tids_b, alive_b,
        round_up_to_multiple(sup_b.shape[0], n_lane))
    if len(axes) == 1:
        fn = _batched_sharded_fn(mesh, axes[0], int(cap_f), int(cap_t))
        args, h2d = upload(*arrs)
        return (*fn(*args), h2d)
    lane_axis, tri_axis = axes
    n_tri = int(mesh.shape[tri_axis])
    sup_p, tris_p, _, _, alive_p = arrs
    cap_e = int(sup_p.shape[1])
    T = int(tris_p.shape[1])
    T_pad = round_up_to_multiple(T, n_tri)
    if T_pad != T:  # contiguous triangle shards need equal rows per device
        pad = np.full((sup_p.shape[0], T_pad - T, 3), cap_e, np.int32)
        tris_p = np.concatenate([np.asarray(tris_p), pad], axis=1)
    indptr_ls, tids_ls = shard_incidence_lanes(
        np.asarray(tris_p), cap_e, n_tri)
    fn = _batched_sharded2_fn(mesh, lane_axis, tri_axis,
                              int(cap_f), int(cap_t))
    args, h2d = upload(sup_p, np.asarray(tris_p), indptr_ls, tids_ls,
                       alive_p)
    return (*fn(*args), h2d)


@lru_cache(maxsize=None)
def _threshold_sharded_fn(mesh, axis, cap_f: int, cap_t: int):
    """jit(shard_map) of the single-level peel: edge state replicated,
    triangles + incidence sharded, pmin/psum per round.  ``axis`` may be
    one axis name or a tuple of names — ``P(axis)`` then shards the
    triangle rows over the flattened product and pmin/psum reduce over all
    named axes at once (DESIGN.md §13)."""

    def local(sup0, tris_loc, indptr_loc, tids_loc, alive0, removable,
              thresh):
        indptr_loc = indptr_loc.reshape(-1)
        tids_loc = tids_loc.reshape(-1)

        def cond(state):
            alive, sup = state
            return jnp.any(alive & removable & (sup <= thresh))

        def body(state):
            alive, sup = state
            rm = alive & removable & (sup <= thresh)
            alive2, sup2, _, _, _, _, _ = _frontier_round(
                alive, sup, rm, tris_loc, indptr_loc, tids_loc,
                cap_f=cap_f, cap_t=cap_t, axis=axis)
            return alive2, sup2

        alive, _ = jax.lax.while_loop(cond, body, (alive0, sup0))
        return alive

    fn = _shard_map(
        local, mesh,
        in_specs=(P(), P(axis), P(axis), P(axis), P(), P(), P()),
        out_specs=P(),
    )
    return jax.jit(fn)


def local_threshold_peel_sharded(mesh, sup0, tris, alive0, removable, thresh,
                                 *, axis="data"):
    """Single-level candidate peel with the triangle list sharded on ``axis``.

    The mesh form of ``peel.local_threshold_peel``'s kernel (the per-k
    candidate peel of BOTH out-of-core drivers): sup/alive/removable are
    replicated, ``tris`` (T, 3; T a multiple of the axis size, padding rows
    on the drop slot m) is sharded along with its per-shard incidence CSR.
    Every round the devices agree on the removal prefix via ``pmin`` and
    merge support decrements with one ``psum``, so replicated edge state
    stays in lockstep.  ``cap_t`` covers the largest per-shard incidence
    row, so each shard always fits at least one edge's row and the agreed
    prefix is non-empty — no overflow/resume path.

    With ``axis`` a tuple of names the shards span the flattened product of
    those mesh axes (DESIGN.md §13) — one huge candidate peel spreads its
    psum volume across the whole multi-axis mesh.

    Returns ``(alive_device_array, cap_f, cap_t, h2d_bytes)``; the caps
    feed the caller's compile-shape cache key, and ``h2d_bytes`` is what
    the inputs' copy to the device took (one ``truss.upload``).
    """
    axes = _axes_tuple(axis)
    n_shards = _mesh_size(mesh, axes)
    spec_axis = axes[0] if len(axes) == 1 else axes
    m = int(sup0.shape[0])
    tris_np = np.asarray(tris)
    indptr_s, tids_s = shard_incidence(tris_np, m, n_shards)
    cap_f, cap_t = _sharded_caps(m, indptr_s, tids_s)
    fn = _threshold_sharded_fn(mesh, spec_axis, int(cap_f), int(cap_t))
    args, h2d = upload(np.asarray(sup0), tris_np, indptr_s, tids_s,
                       np.asarray(alive0), np.asarray(removable))
    alive = fn(*args, jnp.int32(thresh))
    return alive, cap_f, cap_t, h2d
