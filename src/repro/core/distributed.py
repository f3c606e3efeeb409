"""Distributed truss decomposition (shard_map over the production mesh).

Four device-parallel pieces (DESIGN.md §2, §10):

1. ``distributed_local_truss`` — the LowerBounding stage (Algorithm 3) at pod
   scale: every device owns one (padded) neighborhood subgraph NS(P_i) and
   peels it locally with NO communication — the partition-locality that makes
   the paper's design beat iterate-globally MapReduce.  vmap over the parts
   stacked on each device.

2. ``peel_classes_sharded`` — bulk peeling of ONE big graph whose triangle
   list is sharded across devices: each round every device gathers the
   triangles its shard holds for the (replicated) removal frontier through a
   per-shard edge→triangle incidence CSR and a single psum all-reduce merges
   the decrements (frontier engine, DESIGN.md §3).  Edge-state
   (alive/sup/phi/k) is replicated, so the per-round communication is
   exactly one all-reduce of m int32 plus a scalar pmin agreeing on the
   frontier chunk — the ICI analogue of the paper's "one sequential scan per
   iteration".

3. ``ring_support_dense`` — SUMMA-style dense support counting: adjacency
   row-blocks rotate around the ring (``ppermute``) while each device
   accumulates A_i @ A into its block of (A @ A) ∘ A.  Sequential-neighbor
   traffic instead of all-to-all: the scan(N) discipline applied to ICI.

4. ``peel_classes_batched_sharded`` / ``local_threshold_peel_sharded`` —
   the pod-spanning form of the batched out-of-core engine (DESIGN.md §10):
   one partition round's ``partition.PartBucket`` lanes are split over a
   mesh axis (lanes are independent subproblems, so the per-lane peels need
   no communication), and the per-k candidate peel of both drivers runs
   with its triangle list sharded (pmin on the frontier prefix, psum on the
   decrements — the discipline of piece 2 at a single threshold level).
   ``peel.peel_classes_batched`` / ``peel.local_threshold_peel`` dispatch
   here when a ``mesh=`` is supplied, keeping the drivers' double-buffered
   non-blocking rounds — and the stage-2 candidate pipeline's pre-built
   supersets with their ``alive0`` dead-edge masks (DESIGN.md §11) —
   intact across the mesh: the replicated edge state simply starts with
   the masked edges dead, so they never enter any shard's frontier.
"""

from __future__ import annotations

from functools import lru_cache, partial
from typing import List, Sequence

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

_BIG = jnp.int32(np.iinfo(np.int32).max // 2)


def _shard_map(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the replication check off: trip counts are
    data-dependent per shard."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


# ---------------------------------------------------------------------------
# 1. LowerBounding at pod scale
# ---------------------------------------------------------------------------

def pad_parts(
    parts: Sequence[tuple[np.ndarray, np.ndarray]], n_devices: int
) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Stack per-part (sup, tris) into device-shardable padded arrays.

    Returns (sup_p, tris_p, alive_p, indptr_p, tids_p): shapes (P, Em),
    (P, Tm, 3), (P, Em), (P, Em+1), (P, Lm) with P a multiple of n_devices.
    Padding edges are dead; padding triangles point at the per-part drop
    slot Em.  (indptr_p, tids_p) is each part's edge→triangle incidence CSR
    consumed by the frontier peel engine.
    """
    n_parts = len(parts)
    P_total = max(1, -(-n_parts // n_devices) * n_devices)
    Em = max([len(s) for s, _ in parts] + [1])
    Tm = max([len(t) for _, t in parts] + [1])
    Lm = max(1, 3 * Tm)
    sup_p = np.zeros((P_total, Em), np.int32)
    tris_p = np.full((P_total, Tm, 3), Em, np.int32)
    alive_p = np.zeros((P_total, Em), bool)
    indptr_p = np.zeros((P_total, Em + 1), np.int32)
    tids_p = np.zeros((P_total, Lm), np.int32)
    for i, (sup, tris) in enumerate(parts):
        sup_p[i, : len(sup)] = sup
        alive_p[i, : len(sup)] = True
        if len(tris):
            tris_p[i, : len(tris)] = tris
        indptr, tids = triangle_incidence_np(tris_p[i], Em)
        indptr_p[i] = indptr
        tids_p[i, : len(tids)] = tids
    return sup_p, tris_p, alive_p, indptr_p, tids_p


def distributed_local_truss(mesh, sup_p, tris_p, alive_p, indptr_p, tids_p,
                            axis: str = "data"):
    """Peel every part locally, parts sharded over ``axis``; returns phi_p.

    Runs the frontier-compacted engine per part with capacities pinned to
    the padded part sizes (static under vmap, so the overflow path can never
    trigger)."""
    Em = sup_p.shape[1]
    cap_f = Em
    cap_t = max(1, tids_p.shape[1])

    def one(s, t, ip, ti, a):
        phi0 = jnp.zeros(Em, jnp.int32)
        st0 = jnp.zeros(N_STATS, jnp.int32)
        _, _, phi, _, _, _ = peel_classes_fixedcap(
            s, t, ip, ti, a, phi0, jnp.int32(2), st0,
            cap_f=cap_f, cap_t=cap_t)
        return phi

    def local(sup, tris, indptr, tids, alive):
        return jax.vmap(one)(sup, tris, indptr, tids, alive)

    fn = _shard_map(
        local, mesh,
        in_specs=(P(axis), P(axis), P(axis), P(axis), P(axis)),
        out_specs=P(axis),
    )
    return fn(sup_p, tris_p, indptr_p, tids_p, alive_p)


# ---------------------------------------------------------------------------
# 2. Sharded-triangle bulk peel (one big graph)
# ---------------------------------------------------------------------------

def _peel_sharded_body(sup0, tris_loc, indptr_loc, tids_loc, alive0,
                       axis: str, cap_f: int, cap_t: int):
    """Runs on each device: triangle shard + its incidence local, edge state
    replicated.  Every round removes an agreed (pmin) frontier chunk, gathers
    only the local triangles incident to it, and merges decrements with one
    psum."""
    m = sup0.shape[0]
    indptr_loc = indptr_loc.reshape(-1)
    tids_loc = tids_loc.reshape(-1)

    def cond(state):
        alive, sup, phi, k = state
        return jnp.any(alive)

    def body(state):
        alive, sup, phi, k = state
        rm = alive & (sup <= k - 2)
        has_rm = jnp.any(rm)

        def remove(_):
            alive2, sup2, rm_sub, _, _, _, _ = _frontier_round(
                alive, sup, rm, tris_loc, indptr_loc, tids_loc,
                cap_f=cap_f, cap_t=cap_t, axis=axis)
            phi2 = jnp.where(rm_sub, k, phi)
            return alive2, sup2, phi2, k

        def jump(_):
            min_sup = jnp.min(jnp.where(alive, sup, _BIG))
            return alive, sup, phi, jnp.maximum(k + 1, min_sup + 2)

        return jax.lax.cond(has_rm, remove, jump, operand=None)

    state0 = (alive0, sup0, jnp.zeros(m, jnp.int32), jnp.int32(2))
    alive, sup, phi, k = jax.lax.while_loop(cond, body, state0)
    return phi


def _sharded_caps(m: int, indptr_s: np.ndarray, tids_s: np.ndarray,
                  cap_f=None, cap_t=None) -> tuple[int, int]:
    """Frontier capacities for a triangle-sharded peel: ``cap_t`` is clamped
    to cover the largest per-shard incidence row (even when caller-provided),
    so every shard fits at least one edge's row and the pmin-agreed prefix
    is never empty — progress is guaranteed without an overflow/resume
    path.  Shared by ``peel_classes_sharded`` and
    ``local_threshold_peel_sharded``."""
    max_row = int((indptr_s[:, 1:] - indptr_s[:, :-1]).max()) if m else 1
    n_inc = tids_s.shape[1]
    if cap_f is None:
        cap_f = _pow2_ceil(min(max(m, 1), max(256, m // 16)))
    if cap_t is None:
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


def peel_classes_sharded(mesh, sup0, tris, alive0, axis: str = "data",
                         cap_f=None, cap_t=None):
    """Trussness of one big graph with the triangle list sharded on ``axis``.

    ``tris`` (T, 3) must be padded to a multiple of the axis size (padding
    rows point at edge id m = drop slot).  The per-shard incidence CSR is
    built host-side; capacities default to frontier-sized buffers with
    ``cap_t`` covering the largest incidence row of any shard (progress is
    then guaranteed, so no overflow/resume path is needed here).
    """
    n_shards = mesh.shape[axis]
    m = int(sup0.shape[0])
    tris_np = np.asarray(tris)
    indptr_s, tids_s = shard_incidence(tris_np, m, n_shards)
    cap_f, cap_t = _sharded_caps(m, indptr_s, tids_s, cap_f, cap_t)
    fn = _shard_map(
        partial(_peel_sharded_body, axis=axis, cap_f=cap_f, cap_t=cap_t),
        mesh,
        in_specs=(P(), P(axis), P(axis), P(axis), P()),
        out_specs=P(),
    )
    return fn(sup0, jnp.asarray(tris), jnp.asarray(indptr_s),
              jnp.asarray(tids_s), alive0)


def pad_triangles(tris: np.ndarray, m: int, multiple: int) -> np.ndarray:
    t = len(tris)
    t_pad = max(1, -(-t // multiple)) * multiple
    out = np.full((t_pad, 3), m, np.int32)
    if t:
        out[:t] = tris
    return out


# ---------------------------------------------------------------------------
# 3. Ring (SUMMA) dense support counting
# ---------------------------------------------------------------------------

def ring_support_dense(mesh, A: jnp.ndarray, axis: str = "data"):
    """S = (A @ A) ∘ A with A row-sharded; neighbor-ring collective schedule.

    A: (n, n) 0/1 matrix (float dtype), n divisible by the axis size.
    Returns S with S[u, v] = common-neighbor count for the edge (u, v)
    (zero off-edges) — per-edge support for the dense-core regime.
    """
    p = mesh.shape[axis]
    perm = [(j, (j + 1) % p) for j in range(p)]

    def body(a_loc):                      # (nb, n) block of rows
        nb = a_loc.shape[0]
        idx0 = jax.lax.axis_index(axis)

        def step(i, carry):
            blk, acc = carry              # blk holds rows of device (idx0 - i) % p
            src = (idx0 - i) % p
            cols = jax.lax.dynamic_slice(a_loc, (0, src * nb), (nb, nb))
            acc = acc + cols @ blk        # (nb, nb) @ (nb, n)
            blk = jax.lax.ppermute(blk, axis, perm)
            return blk, acc

        _, acc = jax.lax.fori_loop(0, p, step, (a_loc, jnp.zeros_like(a_loc)))
        return acc * a_loc

    fn = _shard_map(body, mesh, in_specs=P(axis, None), out_specs=P(axis, None))
    return fn(A)


def allgather_support_dense(mesh, A: jnp.ndarray, axis: str = "data"):
    """Baseline: same computation via one big all-gather (no ring overlap).

    Used by EXPERIMENTS.md §Perf to contrast collective schedules.
    """

    def body(a_loc):
        a_full = jax.lax.all_gather(a_loc, axis, tiled=True)   # (n, n)
        return (a_loc @ a_full) * a_loc

    fn = _shard_map(body, mesh, in_specs=P(axis, None), out_specs=P(axis, None))
    return fn(A)


# ---------------------------------------------------------------------------
# 4. Pod-spanning batched OOC rounds (DESIGN.md §10)
# ---------------------------------------------------------------------------

def pad_bucket_lanes(sup_b, tris_b, indptr_b, tids_b, alive_b, n_lanes: int):
    """``pad_parts``-style padding of a bucket's lane dimension to
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
    triangles + incidence sharded, pmin/psum per round (see
    ``_peel_sharded_body`` for the multi-level analogue).  ``axis`` may be
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
