"""Bulk-synchronous truss peeling — frontier-compacted engine (DESIGN.md §3).

The paper's Algorithm 2 removes one minimum-support edge at a time.  On
vector hardware we peel in *rounds*: every round removes alive edges with
``sup <= k-2`` and repairs the supports of surviving edges via triangle
bookkeeping.  Rounds iterate at the same k until a fixed point, then k jumps
directly to ``min_alive_support + 2`` (bucket jump).  This computes exactly
the same k-classes as the serial algorithm.

The seed implementation (kept as ``peel_classes_dense`` / an O(T)-per-round
baseline) rescanned the full (T, 3) triangle list three times per round and
scattered into all m edge slots even when a round removed a handful of
edges.  The frontier engine instead:

  (a) compacts the removed-edge frontier into a fixed-capacity buffer via a
      ``cumsum``-based stream compaction (capacity ``cap_f``);
  (b) gathers ONLY the triangles incident to frontier edges through a
      precomputed edge→triangle incidence CSR (``triangle_incidence_np``),
      each gather slot finding its frontier edge and incidence position
      with a scatter at the segment starts and a prefix sum
      (``_slot_owner``), not a per-slot binary search;
  (c) applies support decrements with scatters sized to the gathered
      frontier (capacity ``cap_t``), not to T or m.

Large rounds are *chunked*: when a round's frontier exceeds the capacities,
only a prefix is removed and the loop re-enters at the same k — peeling is
confluent (removing any subset of sub-threshold edges and iterating reaches
the same fixed point), so the result is unchanged.  Over a whole
decomposition every incidence entry is gathered exactly once, so total
scatter work is Θ(3T) instead of Θ(rounds · 3T).  If a single edge's
incidence row overflows ``cap_t`` the kernel reports overflow and the host
wrapper doubles the capacity and resumes from the returned state (the
default ``cap_t`` already covers the largest row, so this is a safety
valve, not a steady-state path).

State is fixed-shape; each kernel invocation is one ``lax.while_loop`` —
jit-compatible, vmap-compatible (the batched out-of-core lanes) and
shard_map-compatible (the ``distributed`` mesh programs add a ``pmin`` on
the chunk prefix and a ``psum`` on the decrements).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import faults
from repro.core import spans
from repro.core.spans import span, upload
from repro.core.support import (_pow2_ceil, _pow4_ceil, list_triangles_np,
                                support_from_triangle_list,
                                triangle_incidence_np)

_BIG = jnp.int32(np.iinfo(np.int32).max // 2)

# stats vector layout (int32): sub-rounds, edges removed, incidence slots
# gathered, max frontier size seen in a single round
N_STATS = 4
_S_ROUNDS, _S_REMOVED, _S_GATHERED, _S_MAXF = range(N_STATS)


def _tri_alive(alive, tris):
    return alive[tris[:, 0]] & alive[tris[:, 1]] & alive[tris[:, 2]]


@dataclasses.dataclass
class PeelStats:
    """Work counters of one frontier-peel invocation (DESIGN.md §3).

    ``gathered`` is the total number of incidence slots touched by scatter/
    gather work across all rounds — for a full decomposition it equals the
    incidence size (3T): each (edge, triangle) pair is processed exactly once,
    in the round its edge is removed.  The dense engine's equivalent would be
    ``rounds * 3T``.
    """

    rounds: int          # sub-rounds executed (incl. frontier chunks)
    removed: int         # edges removed
    gathered: int        # incidence slots gathered (frontier-sized work)
    max_frontier: int    # largest single-round frontier
    cap_f: int           # frontier buffer capacity used
    cap_t: int           # triangle gather capacity used
    resumes: int         # host capacity-doubling fallbacks taken
    h2d_bytes: int = 0   # graph bytes copied host to device (truss.upload)

    @classmethod
    def from_vec(cls, vec, cap_f, cap_t, resumes, h2d_bytes=0):
        vec = np.asarray(vec)
        return cls(int(vec[_S_ROUNDS]), int(vec[_S_REMOVED]),
                   int(vec[_S_GATHERED]), int(vec[_S_MAXF]),
                   cap_f, cap_t, resumes, h2d_bytes)


# ---------------------------------------------------------------------------
# the frontier round primitive
# ---------------------------------------------------------------------------

def _slot_owner(starts, vals, cap_t: int):
    """``vals[j]`` of the segment that owns each gather slot ``s < cap_t``.

    Segment ``j`` of the ragged-to-flat expansion starts at ``starts[j]``
    (an exclusive prefix sum of the segment lengths, so non-decreasing);
    slot ``s`` belongs to the largest ``j`` with ``starts[j] <= s``, which
    is ``min(searchsorted(inclusive_ends, s, side="right"), len(starts) -
    1)``.  Each segment adds its step ``vals[j] - vals[j - 1]`` at its
    start, and the prefix sum over the slots telescopes to the owner's
    value: segments sharing a start (empty ones before a non-empty one)
    sum to the last one's value, and starts at or past ``cap_t`` own no
    slot and drop.  Exact for any int32 ``vals`` (the sums wrap alike).
    One scatter of ``len(starts)`` values and one prefix sum of ``cap_t``,
    where a binary search would gather ``cap_t`` indices
    ``log2(len(starts))`` times.
    """
    steps = jnp.diff(vals, prepend=jnp.zeros_like(vals[:1]))
    at = jnp.zeros(cap_t, vals.dtype).at[starts].add(steps, mode="drop")
    return _prefix_sum(at)


_TILE = 128


def _prefix_sum(x):
    """Inclusive prefix sum of an int32 vector, as ``jnp.cumsum``.

    Rows of ``_TILE`` are summed by an integer matmul with an upper
    triangle of ones, and each row's carry-in is the prefix sum of the row
    totals, recursively.  Integer matmuls are exact, so this equals
    ``cumsum`` bit for bit.  ``cumsum`` and ``cummax`` lower to a
    reduce-window, and at ``cap_t`` 65536 in a vmapped peel loop the TPU
    compiler (v5e) took 12 and 90 s over such a scan, against 4 s for the
    whole loop with this form.
    """
    n = x.shape[0]
    rows = -(-n // _TILE)
    tri = jnp.triu(jnp.ones((_TILE, _TILE), x.dtype))
    part = jnp.matmul(jnp.pad(x, (0, rows * _TILE - n)).reshape(rows, _TILE),
                      tri, preferred_element_type=x.dtype)
    if rows > 1:
        ends = part[:, -1]
        part = part + (_prefix_sum(ends) - ends)[:, None]
    return part.reshape(-1)[:n]


def _frontier_round(alive, sup, rm, tris, tri_indptr, tri_ids,
                    *, cap_f: int, cap_t: int, axis: Optional[str] = None):
    """One compacted removal step: remove a prefix of ``rm``, repair ``sup``.

    Returns (alive2, sup2, rm_sub, nf, j_take, total_t, overflow) where
    ``rm_sub`` is the subset of ``rm`` actually removed this step (a prefix
    of the frontier in edge-id order; confluence of peeling makes any subset
    valid), ``nf`` the full frontier size, ``j_take`` the number of edges
    taken, ``total_t`` the incidence slots gathered.  ``overflow`` is set
    when the frontier is non-empty but not even one edge's incidence row
    fits in ``cap_t``.

    ``axis``: inside shard_map, the mesh axis holding the triangle shards —
    the taken prefix is agreed via ``pmin`` and decrements merged via
    ``psum`` so replicated edge state stays consistent.
    """
    m = alive.shape[0]
    rm_i = rm.astype(jnp.int32)
    nf = jnp.sum(rm_i)
    idx = jnp.cumsum(rm_i) - 1               # frontier position per edge
    cand = rm & (idx < cap_f)
    tgt = jnp.where(cand, idx, cap_f)        # cap_f = dump slot
    f_ids = jnp.full(cap_f + 1, m, jnp.int32).at[tgt].set(
        jnp.arange(m, dtype=jnp.int32), mode="drop")[:cap_f]
    fc = jnp.minimum(f_ids, m - 1)
    row0 = tri_indptr[fc]                    # incidence row start per edge
    lens = jnp.where(f_ids < m, tri_indptr[fc + 1] - row0, 0)
    offs = jnp.cumsum(lens)                  # inclusive prefix sums
    fits = (offs <= cap_t) & (f_ids < m)     # prefix mask (lens >= 0)
    j_take = jnp.sum(fits.astype(jnp.int32))
    if axis is not None:
        j_take = jax.lax.pmin(j_take, axis)
    overflow = (nf > 0) & (j_take == 0)
    total_t = jnp.where(j_take > 0, offs[jnp.maximum(j_take - 1, 0)], 0)
    rm_sub = rm & (idx < j_take)
    alive2 = alive & ~rm_sub

    # gather the incident triangles of the taken prefix (ragged -> flat)
    s = jnp.arange(cap_t, dtype=jnp.int32)
    starts = offs - lens
    valid = s < total_t
    f = _slot_owner(starts, f_ids, cap_t)    # frontier edge owning this slot
    # the owner's incidence row start plus the slot's offset in its segment
    slot = jnp.minimum(s + _slot_owner(starts, row0 - starts, cap_t),
                       max(tri_ids.shape[0] - 1, 0))
    tid = tri_ids[slot]
    e0 = jnp.minimum(tris[tid, 0], m - 1)
    e1 = jnp.minimum(tris[tid, 1], m - 1)
    e2 = jnp.minimum(tris[tid, 2], m - 1)
    died = alive[e0] & alive[e1] & alive[e2]
    # a triangle incident to several removed edges appears once per such
    # edge; charge it to the minimum removed edge id so it decrements its
    # survivors exactly once
    owner = jnp.minimum(
        jnp.where(rm_sub[e0], e0, _BIG),
        jnp.minimum(jnp.where(rm_sub[e1], e1, _BIG),
                    jnp.where(rm_sub[e2], e2, _BIG)))
    contribute = valid & died & (f == owner)
    dec = jnp.zeros(m + 1, jnp.int32)
    for e_c in (e0, e1, e2):
        tgt_c = jnp.where(contribute & alive2[e_c], e_c, m)
        dec = dec.at[tgt_c].add(jnp.int32(1), mode="drop")
    if axis is not None:
        dec = jax.lax.psum(dec, axis)
    return alive2, sup - dec[:m], rm_sub, nf, j_take, total_t, overflow


def _bump_stats(stats, nf, j_take, total_t):
    stats = stats.at[_S_ROUNDS].add(1)
    stats = stats.at[_S_REMOVED].add(j_take)
    stats = stats.at[_S_GATHERED].add(total_t)
    return stats.at[_S_MAXF].max(nf)


# ---------------------------------------------------------------------------
# fixed-capacity kernels (jit / vmap / shard_map compatible)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("cap_f", "cap_t", "max_k", "axis"))
def peel_classes_fixedcap(sup0, tris, tri_indptr, tri_ids, alive0, phi0, k0,
                          stats0, *, cap_f, cap_t, max_k=None, axis=None):
    """Frontier peel to a fixed point (or overflow) at static capacities.

    Full state in / full state out so the host wrapper can resume after
    doubling a capacity.  Returns (alive, sup, phi, k, stats, overflow).

    ``axis`` names a mesh axis (or tuple of axes) the caller sharded the
    triangle list + incidence over: edge state is then replicated, the
    frontier prefix agreed by pmin and decrements merged by psum
    (``_frontier_round``'s sharded form) — the remove-vs-jump branch and
    the k jump depend only on the replicated edge state, so every shard
    takes the same path.  Used by the multi-axis batched peel
    (``distributed``, DESIGN.md §13), where lanes live on one mesh axis
    and each lane's triangles on another.
    """

    def cond(state):
        alive, sup, phi, k, stats, overflow = state
        ok = jnp.any(alive) & ~overflow
        if max_k is not None:
            ok &= k <= max_k
        return ok

    def body(state):
        alive, sup, phi, k, stats, overflow = state
        rm = alive & (sup <= k - 2)

        def do_remove(_):
            alive2, sup2, rm_sub, nf, j_take, total_t, ovf = _frontier_round(
                alive, sup, rm, tris, tri_indptr, tri_ids,
                cap_f=cap_f, cap_t=cap_t, axis=axis)
            phi2 = jnp.where(rm_sub, k, phi)
            return (alive2, sup2, phi2, k,
                    _bump_stats(stats, nf, j_take, total_t), ovf)

        def do_jump(_):
            min_sup = jnp.min(jnp.where(alive, sup, _BIG))
            new_k = jnp.maximum(k + 1, min_sup + 2)
            return alive, sup, phi, new_k, stats, overflow

        return jax.lax.cond(jnp.any(rm), do_remove, do_jump, operand=None)

    state0 = (alive0, sup0, phi0, k0, stats0, jnp.bool_(False))
    return jax.lax.while_loop(cond, body, state0)


@partial(jax.jit, static_argnames=("cap_f", "cap_t"))
def peel_threshold_fixedcap(sup0, tris, tri_indptr, tri_ids, alive0,
                            removable, thresh, stats0, *, cap_f, cap_t):
    """Single-level frontier peel at static capacities.

    Returns (alive, sup, stats, overflow).
    """

    def cond(state):
        alive, sup, stats, overflow = state
        return jnp.any(alive & removable & (sup <= thresh)) & ~overflow

    def body(state):
        alive, sup, stats, overflow = state
        rm = alive & removable & (sup <= thresh)
        alive2, sup2, _, nf, j_take, total_t, ovf = _frontier_round(
            alive, sup, rm, tris, tri_indptr, tri_ids,
            cap_f=cap_f, cap_t=cap_t)
        return alive2, sup2, _bump_stats(stats, nf, j_take, total_t), ovf

    state0 = (alive0, sup0, stats0, jnp.bool_(False))
    return jax.lax.while_loop(cond, body, state0)


# ---------------------------------------------------------------------------
# host wrappers: incidence construction + capacity doubling fallback
# ---------------------------------------------------------------------------

def _default_caps(m: int, incidence, cap_f, cap_t):
    """Capacity heuristic: large rounds are chunked anyway, so capacities
    trade static per-round gather width against extra sub-rounds.  The
    m//48 and 3T//96 divisors came out of a CPU sweep on power-law graphs
    and have not been set on the chip; the floor on ``cap_t`` must cover
    the largest single incidence row or progress could stall."""
    indptr, tri_ids = incidence
    max_row = int((indptr[1:] - indptr[:-1]).max()) if m else 0
    n_inc = len(tri_ids)
    if cap_f is None:
        cap_f = _pow2_ceil(min(max(m, 1), max(256, m // 48)))
    if cap_t is None:
        # auto-sizing covers the largest row up front; an explicit (too
        # small) cap_t is honored and recovered via the overflow fallback
        cap_t = max(_pow2_ceil(min(max(n_inc, 1), max(1024, n_inc // 96))),
                    _pow2_ceil(max_row))
    return cap_f, cap_t


def _prep_incidence(tris, m, incidence):
    if incidence is None:
        incidence = triangle_incidence_np(np.asarray(tris), m)
    indptr, tri_ids = incidence
    if len(tri_ids) == 0:  # keep gather shapes non-empty
        tri_ids = np.zeros(1, np.int32)
    return np.asarray(indptr), np.asarray(tri_ids)


def _pick_engine(engine: str, tris, m: int, with_stats: bool) -> str:
    """"auto" routes triangle-rich graphs (3T > m) to the frontier engine;
    when the incidence is smaller than the edge list the dense engine's
    O(T)-per-round rescans are already cheaper than any O(m) frontier mask
    work.  Stats only exist for the frontier engine, so ``with_stats``
    forces it."""
    if engine == "auto":
        if with_stats or 3 * int(np.asarray(tris).shape[0]) > m:
            return "frontier"
        return "dense"
    return engine


def peel_classes(sup0, tris, edge_alive0, max_k=None, *, incidence=None,
                 cap_f=None, cap_t=None, with_stats=False, engine="auto"):
    """Compute trussness phi(e) for every edge.

    Args:
      sup0: (m,) int32 initial supports (w.r.t. alive edges).
      tris: (T, 3) int32 triangle edge-id triples (may include triangles of
        dead edges; they are masked out).
      edge_alive0: (m,) bool — initial alive mask (padding / pre-removed edges
        are False).
      max_k: optional static cap: stop after classes <= max_k are emitted
        (used by the bottom-up per-k candidate peel).
      incidence: optional precomputed ``triangle_incidence_np(tris, m)``; pass
        it when peeling the same triangle list repeatedly.
      cap_f, cap_t: frontier / triangle-gather capacities (power-of-two
        recommended to bound recompiles); sized automatically when None.
      with_stats: also return a :class:`PeelStats` ("auto" then picks the
        frontier engine; an explicit engine="dense" returns stats=None —
        the dense baseline has no frontier counters).
      engine: "auto" (default), "frontier", or "dense" (see ``_pick_engine``).

    Returns:
      (phi, alive) — or (phi, alive, stats) with ``with_stats=True``.  phi is
      (m,) int32 trussness, 0 for edges never alive; if ``max_k`` is given,
      edges with trussness > max_k keep phi == 0 and stay alive in the
      returned mask.
    """
    m = int(sup0.shape[0])
    if _pick_engine(engine, tris, m, with_stats) == "dense":
        (sup_j, tris_j, alive_j), _ = upload(sup0, tris, edge_alive0)
        phi, alive = peel_classes_dense(sup_j, tris_j, alive_j, max_k=max_k)
        # the dense baseline has no frontier counters (explicit engine="dense")
        return (phi, alive, None) if with_stats else (phi, alive)
    indptr, tri_ids = _prep_incidence(tris, m, incidence)
    cap_f, cap_t = _default_caps(m, (indptr, tri_ids), cap_f, cap_t)
    (tris_j, indptr_j, tids_j, alive, sup), h2d = upload(
        tris, indptr, tri_ids, edge_alive0, sup0)
    phi = jnp.zeros(m, jnp.int32)
    k = jnp.int32(2)
    stats = jnp.zeros(N_STATS, jnp.int32)
    resumes = 0
    while True:
        with span("dispatch", engine="frontier", lanes=1) as sp:
            compiled = peel_classes_fixedcap._cache_size()
            # trusscheck: allow[TRK104] -- loop-carried arrays keep their (m,)/(T,3) shapes; only cap_t changes, and that retrace IS the deliberate capacity-resume (at most log2 resumes)
            alive, sup, phi, k, stats, overflow = peel_classes_fixedcap(
                sup, tris_j, indptr_j, tids_j, alive, phi, k, stats,
                cap_f=cap_f, cap_t=cap_t, max_k=max_k)
            sp.count(new_compile=peel_classes_fixedcap._cache_size()
                     > compiled)
        # the wait includes the flag's copy back to the host
        with span("device_wait", resumes=resumes):
            # trusscheck: allow[TRK105] -- capacity-resume: the host must read the overflow flag to decide the recompile-at-2x resume (one sync per resume, not per round)
            done = not bool(overflow)
        if done:
            break
        cap_t *= 2          # host fallback: double and resume
        resumes += 1
    if with_stats:
        return phi, alive, PeelStats.from_vec(stats, cap_f, cap_t, resumes,
                                              h2d)
    return phi, alive


def peel_threshold(sup0, tris, alive0, removable, thresh, *, incidence=None,
                   cap_f=None, cap_t=None, with_stats=False, engine="auto"):
    """Single-level peel: repeatedly remove removable alive edges with
    ``sup <= thresh`` (decrementing surviving supports) until fixed point.

    This is Procedure 5 (thresh = k-2, bottom-up: removed edges are the
    k-class) and Procedure 8 (thresh = k-3, top-down: SURVIVING internal
    edges are the k-class) in bulk-synchronous, frontier-compacted form.
    ``removable`` masks the paper's internal edges — external edges are never
    deleted.

    Returns (alive, sup, removed_mask) — plus a PeelStats with
    ``with_stats=True``.
    """
    m = int(sup0.shape[0])
    if _pick_engine(engine, tris, m, with_stats) == "dense":
        args, _ = upload(sup0, tris, alive0, removable)
        alive, sup, removed = peel_threshold_dense(*args, jnp.int32(thresh))
        return (alive, sup, removed, None) if with_stats else \
            (alive, sup, removed)
    indptr, tri_ids = _prep_incidence(tris, m, incidence)
    cap_f, cap_t = _default_caps(m, (indptr, tri_ids), cap_f, cap_t)
    (tris_j, indptr_j, tids_j, alive0, sup, removable), h2d = upload(
        tris, indptr, tri_ids, alive0, sup0, removable)
    alive = alive0
    thresh = jnp.int32(thresh)
    stats = jnp.zeros(N_STATS, jnp.int32)
    resumes = 0
    while True:
        with span("dispatch", engine="frontier", lanes=1) as sp:
            compiled = peel_threshold_fixedcap._cache_size()
            # trusscheck: allow[TRK104] -- loop-carried arrays keep their (m,)/(T,3) shapes; only cap_t changes, and that retrace IS the deliberate capacity-resume (at most log2 resumes)
            alive, sup, stats, overflow = peel_threshold_fixedcap(
                sup, tris_j, indptr_j, tids_j, alive, removable, thresh,
                stats, cap_f=cap_f, cap_t=cap_t)
            sp.count(new_compile=peel_threshold_fixedcap._cache_size()
                     > compiled)
        with span("device_wait", resumes=resumes):
            # trusscheck: allow[TRK105] -- capacity-resume: the host must read the overflow flag to decide the recompile-at-2x resume (one sync per resume, not per round)
            done = not bool(overflow)
        if done:
            break
        cap_t *= 2
        resumes += 1
    if with_stats:
        return alive, sup, alive0 & ~alive, PeelStats.from_vec(
            stats, cap_f, cap_t, resumes, h2d)
    return alive, sup, alive0 & ~alive


# ---------------------------------------------------------------------------
# batched local peels (out-of-core engine, DESIGN.md §8, §9)
# ---------------------------------------------------------------------------

def _peel_classes_vmapped_impl(sup_b, tris_b, indptr_b, tids_b, alive_b,
                               *, cap_f, cap_t):
    """vmap of the fixed-cap frontier peel over the lanes of one bucket."""
    Em = sup_b.shape[1]

    def one(s, t, ip, ti, a):
        phi0 = jnp.zeros(Em, jnp.int32)
        st0 = jnp.zeros(N_STATS, jnp.int32)
        _, _, phi, _, st, _ = peel_classes_fixedcap(
            s, t, ip, ti, a, phi0, jnp.int32(2), st0,
            cap_f=cap_f, cap_t=cap_t)
        return phi, st

    return jax.vmap(one)(sup_b, tris_b, indptr_b, tids_b, alive_b)


# The support buffer is donated: it is rebuilt from scratch by the host
# every round and its (B, cap_e) int32 layout is exactly what the phi
# output needs, so XLA reuses it in place.  (alive is NOT donated — no
# bool output exists to absorb it, so donating it only triggers the
# unused-donation warning.)
_peel_classes_vmapped = jax.jit(
    _peel_classes_vmapped_impl, static_argnames=("cap_f", "cap_t"),
    donate_argnums=(0,))


class PendingPeel:
    """Handle to one asynchronously dispatched device peel (DESIGN.md §9).

    JAX dispatch is asynchronous: the device arrays behind this handle are
    futures, so host work done between dispatch and :meth:`result` overlaps
    the device peel — the consumer half of the drivers' double-buffered
    rounds.  ``result()`` blocks, converts to numpy, applies the host-side
    epilogue and caches the answer.  ``new_compile`` is known at dispatch
    time (shape-cache lookup), so stats never wait on the device;
    ``sharded`` records whether the dispatch spanned a mesh (DESIGN.md §10).

    The finalize handle is consumed (cleared) BEFORE it runs: the dispatch
    donated its support buffers, so a failed finalize must never be
    re-invoked — the kernel would read donated (dead) memory.  A failing
    :meth:`result` raises the original error once and poisons the handle;
    later calls raise a ``RuntimeError`` chained to that error.

    ``fault_ctx`` (optional) names this dispatch at the ``"finalize"``
    fault-injection site (DESIGN.md §12): an injected failure there lands
    inside the consume path exactly like a real asynchronous device error
    surfacing at block time, and poisons the handle the same way.

    ``engine`` names the peel engine the dispatch took ("pallas", "xla", or
    "host" for the triangle-free short-circuit) and ``lanes`` its lane
    width, so drivers can count which lanes went to the fused kernel.
    ``lane_split`` is ``(lane_shards, lanes_per_shard)`` for a bucket whose
    lanes were split over a mesh, read off the output's sharding: the
    number of distinct lane slices the devices hold and the lane rows of
    each; ``None`` for a single-device dispatch.  ``h2d_bytes`` is the
    graph bytes the dispatch copied to the device (its ``truss.upload``
    spans).  ``mesh_lanes`` is the lane count a bucket split over a mesh
    was dispatched at (a multiple of the lane axis), and ``pad_lanes`` how
    many of those lanes hold no live edge; both 0 for any other dispatch.
    """

    def __init__(self, finalize, new_compile: bool, sharded: bool = False,
                 fault_ctx: Optional[dict] = None, engine: str = "xla",
                 lanes: int = 1, lane_split: Optional[tuple] = None,
                 h2d_bytes: int = 0, mesh_lanes: int = 0,
                 pad_lanes: int = 0):
        self._finalize = finalize
        self.new_compile = bool(new_compile)
        self.sharded = bool(sharded)
        self.engine = engine
        self.lanes = int(lanes)
        self.lane_split = lane_split
        self.h2d_bytes = int(h2d_bytes)
        self.mesh_lanes = int(mesh_lanes)
        self.pad_lanes = int(pad_lanes)
        self._fault_ctx = fault_ctx
        self._out = None
        self._error = None

    def result(self):
        if self._error is not None:
            raise RuntimeError(
                "PendingPeel finalize failed previously; the dispatch's "
                "donated buffers are gone, so it cannot be retried"
            ) from self._error
        if self._finalize is not None:
            finalize, self._finalize = self._finalize, None
            try:
                if self._fault_ctx is not None:
                    faults.check(faults.FINALIZE, **self._fault_ctx)
                with span("device_wait"):
                    self._out = finalize()
            except BaseException as e:
                self._error = e
                raise
        return self._out


def _mesh_axes(mesh_axis) -> tuple:
    """Normalize a ``mesh_axis`` knob (one axis name or a sequence of them)
    to a tuple of axis names; axes[0] is always the lane axis."""
    if isinstance(mesh_axis, str):
        return (mesh_axis,)
    return tuple(mesh_axis)


def _mesh_size(mesh, axes: tuple) -> int:
    """Devices the named ``axes`` of ``mesh`` span: their sizes' product."""
    n = 1
    for a in axes:
        n *= int(mesh.shape[a])
    return n


def peel_classes_batched(sup_b, tris_b, indptr_b, tids_b, alive_b,
                         *, shape_cache=None, blocking=True,
                         mesh=None, mesh_axis="data", kernel: str = "auto",
                         fault_ctx: Optional[dict] = None):
    """Local trussness of every NS lane of one bucket in ONE device call.

    Arrays are the (B, cap_e)-padded stacks a ``partition.PartBucket``
    carries; capacities are pinned to the padded lane shape (``cap_f`` =
    cap_e, ``cap_t`` = full incidence width), so the overflow/resume path is
    statically impossible and the kernel is one compile per bucket shape.
    Padded lanes start dead and exit the while loop immediately; padded edge
    slots are dead and every padding triangle points at the drop slot, so
    neither can contribute support.  The support buffer is donated to the
    kernel (the host rebuilds it from scratch every round; its layout is
    reused in place for phi — alive is not donated, no output matches it).

    ``shape_cache``: a caller-owned set of shape keys; returns whether this
    call added a new key (the driver's ``compiles`` counter).  The jit cache
    itself is process-global, so the counter reports at most the true number
    of XLA compiles.

    With ``blocking=False`` the call returns a :class:`PendingPeel`
    immediately after (asynchronous) dispatch; ``handle.result()`` yields
    ``(phi, stats)`` and ``handle.new_compile`` is available at once — the
    producer half of the double-buffered rounds (DESIGN.md §9).

    With a ``mesh``, the bucket's lane dimension is split over ``mesh_axis``
    and the peel spans the pod (``distributed.peel_classes_batched_sharded``,
    DESIGN.md §10): the lane count is padded to a multiple of the axis size
    with dead lanes, the dispatch stays asynchronous, and the handle's
    ``sharded`` flag records the routing.  ``mesh_axis`` may also be a
    TUPLE of axis names (DESIGN.md §13): lanes split over the first axis
    and each lane's triangle list + incidence over the second, so a bucket
    with few big lanes still uses the whole pod.  Triangle-free buckets
    still short-circuit on host (nothing to shard).

    ``kernel`` ("pallas" | "xla" | "auto") picks the per-lane peel engine
    for the single-process dispatch: "pallas" runs the fused
    one-call-per-round kernel (``kernels.frontier_peel``, interpreted
    off-TPU) straight off the (B, T, 3) triangle stacks — the incidence CSR
    inputs are ignored; "auto" routes by backend, VMEM budget and triangle
    density (``frontier_peel.ops.resolve_kernel``).  A ``mesh`` dispatch
    always uses the XLA shard_map engines.

    ``fault_ctx`` names this call at the ``"dispatch"`` fault-injection
    site (and its handle at ``"finalize"``, DESIGN.md §12); ``None`` (the
    default) skips both hooks.

    Returns (phi (B, cap_e) int32 ndarray, stats (B, N_STATS) ndarray,
    newly_compiled bool) when blocking.
    """
    if fault_ctx is not None:
        faults.check(faults.DISPATCH, **fault_ctx)
    cap_e = int(sup_b.shape[1])
    n_inc = int(tids_b.shape[1])
    tris_np = np.asarray(tris_b)
    if (tris_np[:, :, 0] >= cap_e).all():
        # triangle-free bucket: every alive edge has support 0 and peels
        # at k = 2 — no device work needed
        phi = np.where(np.asarray(alive_b), 2, 0).astype(np.int32)
        st = np.zeros(tris_np.shape[:1] + (N_STATS,), np.int32)
        if not blocking:
            return PendingPeel(lambda: (phi, st), False, fault_ctx=fault_ctx,
                               engine="host", lanes=len(phi))
        return phi, st, False
    # frontier capacities: local decompositions peel every lane to EMPTY,
    # so total frontier throughput matters more than per-round width — the
    # divisors are a sweep over the rmat benchmark rounds (wider than the
    # _default_caps tuning for sparse single-graph peels).  cap_t covering
    # the largest incidence row of any lane makes overflow statically
    # impossible (no resume path under vmap).
    max_row = int(np.max(indptr_b[:, 1:] - indptr_b[:, :-1])) if cap_e else 0
    cap_f = _pow2_ceil(min(cap_e, max(512, cap_e // 8)))
    cap_t = max(_pow2_ceil(min(max(n_inc, 1), max(2048, n_inc // 16))),
                _pow2_ceil(max(max_row, 1)))
    if mesh is not None:
        from repro.core.distributed import peel_classes_batched_sharded
        from repro.core.partition import round_up_to_multiple

        axes = _mesh_axes(mesh_axis)
        n_lane = int(mesh.shape[axes[0]])
        B = int(sup_b.shape[0])
        # key on the PADDED lane count — that is the shape jit compiles
        # (the counter must stay <= the true number of XLA compiles)
        B_pad = round_up_to_multiple(B, n_lane)
        # dead lanes of the split: those the packer added to reach the
        # device multiple and those pad_bucket_lanes appends
        pad_lanes = B_pad - int(np.asarray(alive_b).any(axis=1).sum())
        key = ((B_pad,) + tuple(sup_b.shape[1:]),
               (B_pad,) + tuple(tris_b.shape[1:]),
               cap_f, cap_t,
               ("mesh",) + tuple(int(mesh.shape[a]) for a in axes))
        new = shape_cache is not None and key not in shape_cache
        if shape_cache is not None:
            shape_cache.add(key)
        # the sharded dispatch copies its padded inputs itself
        with span("dispatch", engine="mesh", lanes=B, new_compile=new,
                  devices=_mesh_size(mesh, axes), pad_lanes=pad_lanes):
            phi_d, st_d, h2d = peel_classes_batched_sharded(
                mesh, np.asarray(sup_b), tris_np, np.asarray(indptr_b),
                np.asarray(tids_b), np.asarray(alive_b),
                cap_f=cap_f, cap_t=cap_t, axis=mesh_axis)

        def _finish():
            # drop the lanes pad_bucket_lanes appended for the mesh split
            return np.asarray(phi_d)[:B], np.asarray(st_d)[:B]

        if not blocking:
            shape = tuple(phi_d.shape)
            slices = phi_d.sharding.devices_indices_map(shape).values()
            lane_split = (len({s[0].indices(shape[0]) for s in slices}),
                          int(phi_d.sharding.shard_shape(shape)[0]))
            return PendingPeel(_finish, new, sharded=True,
                               fault_ctx=fault_ctx, lanes=B,
                               lane_split=lane_split, h2d_bytes=h2d,
                               mesh_lanes=B_pad, pad_lanes=pad_lanes)
        with span("device_wait"):
            phi, st = _finish()
        return phi, st, new
    from repro.kernels.frontier_peel import ops as frontier_ops

    lanes = int(sup_b.shape[0])
    if frontier_ops.resolve_kernel(kernel, cap_e,
                                   int(tris_np.shape[1])) == "pallas":
        interpret = jax.default_backend() != "tpu"
        bt = frontier_ops.resolve_tile(cap_e, int(tris_np.shape[1]),
                                       "auto", interpret)
        key = (sup_b.shape, tris_b.shape, ("pallas", bt))
        new = shape_cache is not None and key not in shape_cache
        if shape_cache is not None:
            shape_cache.add(key)
        # the fused kernel takes int32 rows: converted on the host, as
        # jnp.asarray(x, jnp.int32) would
        (sup_d, tris_d, alive_d), h2d = upload(
            np.asarray(sup_b, np.int32), np.asarray(tris_np, np.int32),
            np.asarray(alive_b, np.int32))
        with span("dispatch", engine="pallas", lanes=lanes,
                  new_compile=new):
            phi_d, st_d = frontier_ops.peel_classes_fused(
                sup_d, tris_d, alive_d, bt=bt, interpret=interpret)
        if not blocking:
            return PendingPeel(
                lambda: (np.asarray(phi_d), np.asarray(st_d)), new,
                fault_ctx=fault_ctx, engine="pallas", lanes=lanes,
                h2d_bytes=h2d)
        with span("device_wait"):
            return np.asarray(phi_d), np.asarray(st_d), new
    key = (sup_b.shape, tris_b.shape, cap_f, cap_t)
    new = shape_cache is not None and key not in shape_cache
    if shape_cache is not None:
        shape_cache.add(key)
    args, h2d = upload(sup_b, tris_b, indptr_b, tids_b, alive_b)
    with span("dispatch", engine="xla", lanes=lanes, new_compile=new):
        phi, st = _peel_classes_vmapped(*args, cap_f=cap_f, cap_t=cap_t)
    if not blocking:
        return PendingPeel(lambda: (np.asarray(phi), np.asarray(st)), new,
                           fault_ctx=fault_ctx, lanes=lanes, h2d_bytes=h2d)
    with span("device_wait"):
        return np.asarray(phi), np.asarray(st), new


def local_threshold_peel(sup0, tris, removable, thresh, *, alive0=None,
                         shape_cache=None, blocking=True, mesh=None,
                         mesh_axis="data", kernel: str = "auto",
                         fault_ctx: Optional[dict] = None):
    """Single-level peel of a COMPACTED candidate subgraph on padded shapes.

    The out-of-core k-class extraction (bottom-up Procedure 5, top-down
    Procedure 8) peels one candidate subgraph per k.  Peeling it at its
    natural (dynamic) shape would recompile every k; this pads edges and
    triangles to pow4 capacities (at most 4x pad, far fewer shapes) so
    consecutive k values reuse the same compiled kernel (``thresh`` is
    traced, not static).  All ``m`` real edges start alive unless
    ``alive0`` masks some out — the stage-2 candidate pipeline
    (DESIGN.md §11) pre-builds level k+1's candidate while level k still
    peels, then kills the edges that peel removed via this mask instead of
    re-extracting: dead edges never enter the frontier, never report as
    removed, and their triangles never repair supports (the caller must
    compute ``sup0`` from fully-alive triangles only).  ``removable``
    marks the internal/tentative edges (intersected with ``alive0``).

    With ``blocking=False`` returns a :class:`PendingPeel` right after
    dispatch (``handle.result()`` -> (alive_mask, removed_mask)), so the
    caller's host work overlaps the device peel (DESIGN.md §9).

    With a ``mesh``, the padded triangle list (rows rounded up to a multiple
    of the axis size) and its per-shard incidence are sharded over
    ``mesh_axis`` and the peel runs pod-wide with replicated edge state
    (``distributed.local_threshold_peel_sharded``, DESIGN.md §10); the
    handle's ``sharded`` flag records the routing.  A TUPLE ``mesh_axis``
    shards the triangles over the flattened product of the named axes
    (pmin/psum take tuples of axis names), so one huge candidate peel
    spreads its psum volume across the whole multi-axis mesh.

    ``kernel`` ("pallas" | "xla" | "auto") picks the single-process peel
    engine: "pallas" runs the fused one-call-per-round kernel on the padded
    triangle list directly — no incidence CSR is built at all; "auto"
    routes by backend/VMEM/density (``frontier_peel.ops.resolve_kernel``).
    A ``mesh`` dispatch always uses the XLA shard_map engine.

    ``fault_ctx`` names this call at the ``"dispatch"`` fault-injection
    site (and its handle at ``"finalize"``, DESIGN.md §12); ``None`` (the
    default) skips both hooks.

    Returns (alive_mask (m,), removed_mask (m,), newly_compiled bool)
    when blocking.
    """
    if fault_ctx is not None:
        faults.check(faults.DISPATCH, **fault_ctx)
    m = int(len(sup0))
    T = int(len(tris))
    alive0 = (np.ones(m, bool) if alive0 is None
              else np.asarray(alive0, dtype=bool))
    removable = np.asarray(removable, bool) & alive0
    if T == 0:
        # no triangles: removals cascade nothing, one sweep is the fixpoint
        removed = removable & (np.asarray(sup0) <= thresh)
        alive_out = alive0 & ~removed
        if not blocking:
            return PendingPeel(lambda: (alive_out, removed), False,
                               fault_ctx=fault_ctx, engine="host")
        return alive_out, removed, False
    # pow4 capacities: consecutive k levels shrink the candidate slowly, so
    # the coarser grid makes most of a run's peels share one compiled shape
    cap_e = _pow4_ceil(max(m, 1))
    cap_tri = _pow4_ceil(max(T, 1))
    if mesh is not None:
        from repro.core.distributed import local_threshold_peel_sharded
        from repro.core.partition import round_up_to_multiple

        axes = _mesh_axes(mesh_axis)
        n_dev = _mesh_size(mesh, axes)
        # shape ladder (DESIGN.md §13): if an already-compiled sharded
        # shape (read back off the caller's shape_cache keys — stage-2
        # mesh keys are the int-headed 5-tuples) can hold this candidate,
        # adopt the tightest one so the dispatch is a cache hit instead of
        # a pod-wide recompile stall; the extra rows are dead padding
        # whose per-shard cost is 1/n_dev, and a candidate no entry holds
        # peels at its natural pow4 shape (adding it to the cache)
        if shape_cache is not None:
            best = None
            for k in shape_cache:
                if (len(k) == 5 and isinstance(k[0], int)
                        and k[4] == ("mesh", n_dev)
                        and k[0] >= cap_e and k[1] >= cap_tri):
                    if best is None or k[0] * k[1] < best[0] * best[1]:
                        best = k
            if best is not None:
                cap_e, cap_tri = best[0], best[1]
        # contiguous triangle shards need equal row counts per device
        cap_tri = round_up_to_multiple(cap_tri, n_dev)
    tris_p = np.full((cap_tri, 3), cap_e, np.int32)
    if T:
        tris_p[:T] = tris
    sup_p = np.zeros(cap_e, np.int32)
    sup_p[:m] = sup0
    alive_p = np.zeros(cap_e, bool)
    alive_p[:m] = alive0
    rem_p = np.zeros(cap_e, bool)
    rem_p[:m] = removable
    if mesh is not None:
        # the sharded dispatch copies its inputs itself
        with span("dispatch", engine="mesh_threshold", lanes=1,
                  devices=n_dev, tri_rows=cap_tri // n_dev) as sp:
            alive_dev, cap_f, cap_t, h2d = local_threshold_peel_sharded(
                mesh, sup_p, tris_p, alive_p, rem_p, thresh, axis=mesh_axis)
            key = (cap_e, cap_tri, cap_f, cap_t, ("mesh", n_dev))
            new = shape_cache is not None and key not in shape_cache
            sp.count(new_compile=new)
        if shape_cache is not None:
            shape_cache.add(key)

        def _finish_sharded():
            alive = np.asarray(alive_dev)[:m]
            return alive, alive0 & ~alive

        if not blocking:
            return PendingPeel(_finish_sharded, new, sharded=True,
                               fault_ctx=fault_ctx, h2d_bytes=h2d)
        with span("device_wait"):
            alive, removed = _finish_sharded()
        return alive, removed, new
    from repro.kernels.frontier_peel import ops as frontier_ops

    if frontier_ops.resolve_kernel(kernel, cap_e, cap_tri) == "pallas":
        interpret = jax.default_backend() != "tpu"
        bt = frontier_ops.resolve_tile(cap_e, cap_tri, "auto", interpret)
        key = (cap_e, cap_tri, ("pallas", bt))
        new = shape_cache is not None and key not in shape_cache
        if shape_cache is not None:
            shape_cache.add(key)
        # int32 rows, converted on the host as jnp.asarray(x, jnp.int32)
        # would
        (sup_d, tris_d, rem_d, alive_d), h2d = upload(
            sup_p, tris_p, rem_p.astype(np.int32), alive_p.astype(np.int32))
        with span("dispatch", engine="pallas", lanes=1, new_compile=new):
            alive_dev = frontier_ops.peel_threshold_fused(
                sup_d, tris_d, rem_d, thresh, alive_d,
                bt=bt, interpret=interpret)

        def _finish_fused():
            alive = np.asarray(alive_dev)[:m] > 0
            return alive, alive0 & ~alive

        if not blocking:
            return PendingPeel(_finish_fused, new, fault_ctx=fault_ctx,
                               engine="pallas", h2d_bytes=h2d)
        with span("device_wait"):
            alive, removed = _finish_fused()
        return alive, removed, new
    indptr, tids = triangle_incidence_np(tris_p, cap_e)
    tids_p = np.zeros(3 * cap_tri, np.int32)
    tids_p[: len(tids)] = tids
    cap_f, cap_t = _default_caps(cap_e, (indptr, tids_p), None, None)
    key = (cap_e, cap_tri, cap_f, cap_t)
    new = shape_cache is not None and key not in shape_cache
    if shape_cache is not None:
        shape_cache.add(key)
    st0 = jnp.zeros(N_STATS, jnp.int32)
    args, h2d = upload(sup_p, tris_p, indptr, tids_p, alive_p, rem_p)
    # _default_caps covers the largest incidence row, so overflow is
    # impossible and no resume loop is needed
    with span("dispatch", engine="xla", lanes=1, new_compile=new):
        alive_dev, _, _, _ = peel_threshold_fixedcap(
            *args, jnp.int32(thresh), st0, cap_f=cap_f, cap_t=cap_t)

    def _finish():
        alive = np.asarray(alive_dev)[:m]
        return alive, alive0 & ~alive

    if not blocking:
        return PendingPeel(_finish, new, fault_ctx=fault_ctx, h2d_bytes=h2d)
    with span("device_wait"):
        alive, removed = _finish()
    return alive, removed, new


# ---------------------------------------------------------------------------
# dense (seed) engine — O(T) scatter work per round
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("max_k",))
def peel_classes_dense(sup0, tris, edge_alive0, max_k=None):
    """Seed bulk peel: every round rescans the full triangle list.

    ``engine="auto"`` takes it for triangle-poor graphs (``_pick_engine``),
    and it is a second independent implementation for cross-checks.
    """
    m = sup0.shape[0]
    phi0 = jnp.zeros(m, jnp.int32)
    k0 = jnp.int32(2)

    def cond(state):
        alive, sup, phi, k = state
        any_alive = jnp.any(alive)
        if max_k is None:
            return any_alive
        return any_alive & (k <= max_k)

    def body(state):
        alive, sup, phi, k = state
        rm = alive & (sup <= k - 2)
        has_rm = jnp.any(rm)

        def do_remove(_):
            alive2 = alive & ~rm
            phi2 = jnp.where(rm, k, phi)
            died = _tri_alive(alive, tris) & ~_tri_alive(alive2, tris)
            dec = jnp.zeros(m + 1, jnp.int32)
            for c in range(3):
                e = tris[:, c]
                contrib = (died & alive2[e]).astype(jnp.int32)
                dec = dec.at[e].add(contrib, mode="drop")
            return alive2, sup - dec[:m], phi2, k

        def do_jump(_):
            min_sup = jnp.min(jnp.where(alive, sup, _BIG))
            new_k = jnp.maximum(k + 1, min_sup + 2)
            return alive, sup, phi, new_k

        return jax.lax.cond(has_rm, do_remove, do_jump, operand=None)

    alive, sup, phi, k = jax.lax.while_loop(cond, body, (edge_alive0, sup0, phi0, k0))
    return phi, alive


@jax.jit
def peel_threshold_dense(sup0, tris, alive0, removable, thresh):
    """Seed single-level peel (full-triangle-list rescans); baseline."""
    m = sup0.shape[0]

    def cond(state):
        alive, sup = state
        return jnp.any(alive & removable & (sup <= thresh))

    def body(state):
        alive, sup = state
        rm = alive & removable & (sup <= thresh)
        alive2 = alive & ~rm
        died = _tri_alive(alive, tris) & ~_tri_alive(alive2, tris)
        dec = jnp.zeros(m + 1, jnp.int32)
        for c in range(3):
            e = tris[:, c]
            contrib = (died & alive2[e]).astype(jnp.int32)
            dec = dec.at[e].add(contrib, mode="drop")
        return alive2, sup - dec[:m]

    alive, sup = jax.lax.while_loop(cond, body, (alive0, sup0))
    return alive, sup, alive0 & ~alive


def estimate_working_set(g) -> int:
    """In-memory peel working set, in int32 entries (dispatch heuristic).

    Edge state (alive/sup/phi/frontier ≈ 4m) plus triangle list + incidence
    (6T), with T bounded by the oriented wedge count Σ_a deg⁺(a)² — the
    quantity the enumeration actually materializes.  An upper bound: real
    triangle counts are usually far lower, so ``memory_budget`` should be
    read as "route to out-of-core once even the wedge bound doesn't fit".
    """
    out_deg = (g.indptr[1:] - g.indptr[:-1]).astype(np.int64)
    return 4 * g.m + 6 * int((out_deg * out_deg).sum())


@spans.job("auto")
def truss_decompose(n: int, edges: np.ndarray, *, engine: str = "auto",
                    memory_budget=None, partitioner: str = "sequential",
                    partitioner_seed: int = 0, mesh=None,
                    mesh_axis="data", mesh_axes=None,
                    kernel: str = "auto", with_stats: bool = False,
                    checkpoint_dir=None, checkpoint_every=1,
                    resume: bool = False, max_retries: int = 2,
                    store=None, host_memory_budget=None,
                    edits=None, phi0=None):
    """End-to-end decomposition — the unified host entry point.

    ``engine``:
      * "auto" (default) — in-memory frontier/dense dispatch; when
        ``memory_budget`` is given and ``estimate_working_set`` exceeds it,
        routes to the batched out-of-core bottom-up engine instead.
      * "frontier" / "dense" — force the in-memory engines (DESIGN.md §3).
      * "bottom-up" / "top-down" — force the batched out-of-core engines
        (DESIGN.md §8); the per-part NS budget is ``memory_budget`` edge
        entries (default m // 8).  ``partitioner`` picks the round splitter
        ("sequential", "random", or the locality-aware "locality" —
        DESIGN.md §9) and ``partitioner_seed`` offsets the randomized
        partitioner's per-round reseed.  A non-positive ``memory_budget``
        raises.

    ``mesh``: span each out-of-core partition round across the mesh
    (DESIGN.md §10) — bucket lanes split over ``mesh_axis``, per-k candidate
    peels triangle-sharded.  The in-memory engines are single-program and
    ignore it.
    ``mesh_axes`` (a sequence of axis names) overrides ``mesh_axis`` with a
    MULTI-AXIS layout (DESIGN.md §13): bucket lanes split over the first
    axis while each lane's triangles shard over the second, and candidate
    peels spread their psum volume over the flattened product — so late
    rounds with few lanes still use the whole pod.

    ``kernel`` ("pallas" | "xla" | "auto") picks the out-of-core engines'
    per-lane peel engine (the fused Pallas round kernel vs the XLA frontier
    chain — ``peel.peel_classes_batched``); the in-memory engines have
    their own ``engine=`` dispatch and ignore it.

    ``checkpoint_dir`` enables the out-of-core engines' round journal
    (DESIGN.md §12): every ``checkpoint_every``-th completed partition
    round / class level snapshots the host-side state through
    ``checkpoint.manager.save``'s atomic tmp+rename path, and
    ``resume=True`` restores the latest intact snapshot and continues,
    producing φ bit-identical to an uninterrupted run.  ``max_retries``
    bounds the lane-split retries a device OOM gets before the engine
    degrades (mesh drop, then smaller rounds).  The in-memory engines run
    in one device call and have nothing to journal — a ``checkpoint_dir``
    that ends up routed to them warns and is ignored.
    ``checkpoint_every`` also accepts a duration string (``"30s"``) to
    gate snapshots by wall clock.

    ``store`` / ``host_memory_budget`` make the out-of-core engines'
    working graph itself non-resident (DESIGN.md §15): pass a
    :class:`~repro.core.store.GraphStore`, or just a byte budget —
    ``host_memory_budget=`` alone builds a ``ChunkedDiskStore`` in a fresh
    temp directory capping retained graph chunks at that many bytes.  φ is
    bit-identical to the in-memory run; ``OocStats`` gains the chunk I/O
    and prefetch counters.  Like ``checkpoint_dir``, both warn and are
    ignored when the run routes to an in-memory engine.  A non-positive
    ``host_memory_budget`` raises.

    ``edits`` routes the call through incremental maintenance
    (DESIGN.md §16) instead of a fresh decomposition: the pre-edit graph
    ``(n, edges)`` is decomposed (or its known trussness accepted via
    ``phi0``, indexed by the canonical pre-edit edge list) and the edit
    batch — a :class:`~repro.core.maintain.EditBatch` or ``(op, u, v)``
    sequence — is applied by :func:`~repro.core.maintain.truss_maintain`.
    The returned φ indexes the canonical POST-edit edge list, and
    ``checkpoint_dir`` / ``resume`` journal the maintenance itself (one
    snapshot per committed edit).  ``phi0`` without ``edits`` raises.

    With ``with_stats`` the second return value is a :class:`PeelStats`
    (in-memory frontier), ``None`` (dense), or an ``OocStats`` (out-of-core
    and maintenance runs).
    """
    import warnings

    from repro.core.graph import build_graph

    if memory_budget is not None and memory_budget <= 0:
        # a falsy budget must be rejected, not silently replaced by the
        # m // 8 default (a budget of 0 entries can never be honored)
        raise ValueError(
            f"memory_budget must be a positive number of working-set "
            f"entries, got {memory_budget!r}")
    if host_memory_budget is not None and host_memory_budget <= 0:
        raise ValueError(
            f"host_memory_budget must be a positive byte count, got "
            f"{host_memory_budget!r}")
    if mesh_axes is not None:
        axes = _mesh_axes(mesh_axes)
        mesh_axis = axes[0] if len(axes) == 1 else axes
    if phi0 is not None and edits is None:
        raise ValueError("phi0= is only meaningful together with edits=")
    if edits is not None:
        from repro.core.maintain import truss_maintain

        if phi0 is None:
            # inside this call's truss.job span
            phi0 = truss_decompose.__wrapped__(
                n, edges, engine=engine, memory_budget=memory_budget,
                partitioner=partitioner, partitioner_seed=partitioner_seed,
                mesh=mesh, mesh_axis=mesh_axis, kernel=kernel,
                max_retries=max_retries)
        res = truss_maintain(
            (n, np.asarray(edges)), phi0, edits, kernel=kernel, mesh=mesh,
            mesh_axis=mesh_axis, store=store,
            checkpoint_dir=checkpoint_dir,
            checkpoint_every=checkpoint_every, resume=resume)
        phi = np.asarray(res.phi, dtype=np.int64)
        return (phi, res.stats) if with_stats else phi
    g = build_graph(n, edges)
    if g.m == 0:
        phi = np.zeros(0, np.int64)
        return (phi, None) if with_stats else phi
    est = estimate_working_set(g)
    if engine == "auto" and memory_budget is not None and est > memory_budget:
        engine = "bottom-up"
    if engine in ("bottom-up", "top-down"):
        if store is None and host_memory_budget is not None:
            import tempfile

            from repro.core.store import ChunkedDiskStore

            store = ChunkedDiskStore(
                tempfile.mkdtemp(prefix="truss-store-"),
                host_memory_budget=host_memory_budget)
        if memory_budget is not None:
            # memory_budget is in working-set ENTRIES; the partitioners'
            # budget is in NS edge cost (sum of incident degrees, 2m
            # total).  Scale by the graph's entries-per-edge density so a
            # part's estimated working set fits the budget — without this
            # any budget above 2m would yield one whole-graph "partition".
            part_budget = max(64, (2 * g.m * memory_budget) // max(est, 1))
        else:
            part_budget = max(64, g.m // 8)
        if engine == "bottom-up":
            from repro.core.bottom_up import bottom_up_decompose

            res = bottom_up_decompose(n, edges, part_budget,
                                      partitioner=partitioner,
                                      partitioner_seed=partitioner_seed,
                                      mesh=mesh, mesh_axis=mesh_axis,
                                      kernel=kernel,
                                      checkpoint_dir=checkpoint_dir,
                                      checkpoint_every=checkpoint_every,
                                      resume=resume, max_retries=max_retries,
                                      store=store)
        else:
            from repro.core.top_down import top_down_decompose

            # inside this call's truss.job span
            res = top_down_decompose.__wrapped__(
                n, edges, budget=part_budget, partitioner=partitioner,
                partitioner_seed=partitioner_seed, mesh=mesh,
                mesh_axis=mesh_axis, kernel=kernel,
                checkpoint_dir=checkpoint_dir,
                checkpoint_every=checkpoint_every, resume=resume,
                max_retries=max_retries, store=store)
        phi = np.asarray(res.phi).astype(np.int64)
        return (phi, res.stats) if with_stats else phi
    if checkpoint_dir is not None:
        warnings.warn(
            "checkpoint_dir is ignored by the in-memory engines (one device "
            "call, nothing to journal); pass a memory_budget that routes to "
            "an out-of-core engine, or engine='bottom-up'/'top-down'",
            stacklevel=2)
    if store is not None or host_memory_budget is not None:
        warnings.warn(
            "store=/host_memory_budget= are ignored by the in-memory "
            "engines (the whole graph is resident by construction); pass a "
            "memory_budget that routes to an out-of-core engine, or "
            "engine='bottom-up'/'top-down'",
            stacklevel=2)
    tris = list_triangles_np(g)
    sup = support_from_triangle_list(tris, g.m).astype(np.int32)
    if len(tris) == 0:
        tris = np.full((1, 3), g.m, np.int32)  # points at the drop slot
    (sup_j, tris_j), h2d = upload(sup, tris)
    args = (sup_j, tris_j, jnp.ones(g.m, bool))
    if with_stats:
        phi, _, stats = peel_classes(*args, engine=engine, with_stats=True)
        if stats is not None:
            stats.h2d_bytes += h2d
    else:
        phi, _ = peel_classes(*args, engine=engine)
        stats = None
    phi = np.asarray(phi).astype(np.int64)
    return (phi, stats) if with_stats else phi


def kmax_truss(n: int, edges: np.ndarray) -> tuple[int, np.ndarray]:
    """The k_max-truss (paper Section 7.4): returns (k_max, its edge list)."""
    phi = truss_decompose(n, edges)
    if len(phi) == 0:
        return 2, np.zeros((0, 2), np.int32)
    from repro.core.graph import canonical_edges

    edges = canonical_edges(edges, n)
    kmax = int(phi.max())
    return kmax, edges[phi == kmax]
