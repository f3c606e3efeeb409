"""Edge-support (per-edge triangle count) computation.

This is the paper's computational hot spot (Alg 2 Step 2 / Alg 3 Step 6).
The vectorized form keeps the paper's O(m^1.5) bound (Theorem 1):

  * every edge is oriented low-rank -> high-rank (rank = (deg, id) order), so
    out-degrees are O(sqrt(m));
  * for each oriented edge (a->b), every out-neighbor w of a is tested for
    membership in N+(b) — a lookup in the sorted CSR (the TPU-idiomatic
    replacement for the paper's hashtable);
  * a hit identifies triangle {a,b,w} exactly once (forward algorithm) and
    credits support to all three edge ids.

The host path expands each edge's wedges exactly, cut into chunks of a
fixed wedge count, and finds every member with one ``searchsorted`` of the
chunk's composite keys ``b * n + w`` into the graph's sorted CSR keys
``row * n + nbr``.  The device path keeps static shapes: edges are processed
in fixed-size chunks of C edges, each expanded to (C, D) wedge candidates
searched by a per-row binary search.  A single global D = max oriented
out-degree would let one hub vertex in a power-law graph inflate every chunk
by orders of magnitude, so the device path is *skew-aware*: oriented edges
are bucketed by the power-of-two out-degree of their source row and each
bucket runs the wedge enumeration with its own D (DESIGN.md §4).  Total work
stays O(m^1.5); memory per bucket is O(C_b * D_b) with C_b sized to a fixed
element budget.

Entry points:
  * ``list_triangles_np`` / ``list_triangles`` — host triangle lists, in
    edge-id order / in the device path's bucket order;
  * ``edge_support_np``   — numpy, host-side (oracle + preprocessing);
  * ``edge_support_jax``  — jit'd lax.scan over bucketed chunks (device path);
  * ``edge_support_auto`` — dispatch: dense-core partitions go to the
    dense-tile kernel (kernels/triangle_count), sparse ones to the bucketed
    wedge path; see DESIGN.md §2.

``triangle_incidence_np`` builds the edge→triangle incidence CSR consumed by
the frontier-compacted peeling engine (core/peel.py, DESIGN.md §3).
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.graph import Graph, csr_keys
from repro.core.spans import span, upload


# ---------------------------------------------------------------------------
# numpy path
# ---------------------------------------------------------------------------

# Wedges a host chunk expands at once: each per-wedge int64 array is 16 MiB,
# below a 32 MiB malloc mmap threshold, so chunks reuse heap pages instead
# of faulting in fresh ones.
WEDGE_BUDGET = 1 << 21


def _wedge_hits(g: Graph, eids: np.ndarray, budget: int):
    """Yield the triangles the wedges of ``eids`` close, chunk by chunk.

    Edge e = (a->b) opens outdeg(a) wedges (b, w), w over row a in order.
    The wedges are expanded exactly, with no padding, and cut into chunks
    of at most ``budget``, so a boundary may fall inside a row.  A wedge
    closes a triangle iff the key b * n + w is a CSR entry's key; one
    ``searchsorted`` of a chunk's keys into the graph's sorted keys finds
    every member.  Yields (e_ab, e_aw, e_bw), the edge ids of the hits,
    ordered by the position of e_ab in ``eids``, then by w.
    """
    eids = np.asarray(eids, dtype=np.int64)
    if len(eids) == 0:
        return
    keys = csr_keys(g.indptr, g.nbrs, g.n)
    nbrs, nbr_eid = g.nbrs, g.nbr_eid
    indptr = g.indptr.astype(np.int64)
    a = g.src[eids]
    row_lo = indptr[a]
    n_wedges = indptr[a + 1] - row_lo
    ends = np.cumsum(n_wedges)
    starts = ends - n_wedges
    # global wedge k of edge i sits at CSR position k + shift[i] of row a
    shift = row_lo - starts
    row_b = g.dst[eids].astype(np.int64) * np.int64(g.n)
    last = len(keys) - 1
    total = int(ends[-1])
    for k_lo in range(0, total, budget):
        k_hi = min(k_lo + budget, total)
        # edges [i_lo, i_hi) own the chunk's wedges, the outer two in part
        i_lo = int(np.searchsorted(ends, k_lo, side="right"))
        i_hi = int(np.searchsorted(starts, k_hi, side="left"))
        take = (np.minimum(ends[i_lo:i_hi], k_hi)
                - np.maximum(starts[i_lo:i_hi], k_lo))
        pos_aw = np.repeat(shift[i_lo:i_hi], take)
        pos_aw += np.arange(k_lo, k_hi, dtype=np.int64)
        q = np.repeat(row_b[i_lo:i_hi], take)
        q += nbrs[pos_aw]
        p = np.searchsorted(keys, q)
        np.minimum(p, last, out=p)
        hit = np.flatnonzero(keys[p] == q)
        yield (np.repeat(eids[i_lo:i_hi], take)[hit], nbr_eid[pos_aw[hit]],
               nbr_eid[p[hit]])


def _triangle_rows(g: Graph, eids: np.ndarray, budget: int) -> np.ndarray:
    """(T, 3) int32 rows (e_ab, e_aw, e_bw) of every triangle ``eids`` close."""
    out = [np.stack(hits, axis=1) for hits in _wedge_hits(g, eids, budget)]
    return (np.concatenate(out, axis=0).astype(np.int32) if out
            else np.zeros((0, 3), np.int32))


def edge_support_np(g: Graph, budget: int = WEDGE_BUDGET) -> np.ndarray:
    """Support of every canonical edge (numpy, chunked by wedge count)."""
    sup = np.zeros(g.m, dtype=np.int64)
    with span("edge_support", m=g.m):
        for hits in _wedge_hits(g, np.arange(g.m), budget):
            sup += np.bincount(np.concatenate(hits), minlength=g.m)
    return sup


def list_triangles_np(g: Graph, budget: int = WEDGE_BUDGET) -> np.ndarray:
    """Static triangle list: (T, 3) int32 edge-id triples, each triangle
    once, enumerated in edge-id order."""
    with span("list_triangles") as sp:
        tris = _triangle_rows(g, np.arange(g.m), budget)
        sp.count(triangles=len(tris))
    return tris


def list_triangles(g: Graph, budget: int = WEDGE_BUDGET) -> np.ndarray:
    """Triangle listing in the device path's bucket order (DESIGN.md §4).

    The same triangles as ``list_triangles_np``, each exactly once, with
    the edges enumerated as ``wedge_bucket_plan`` groups them: by the pow2
    out-degree of their source row, ids ascending within a bucket.
    """
    with span("list_triangles") as sp:
        plan = wedge_bucket_plan(g)
        eids = (np.concatenate([b.eids[: b.n_real] for b in plan]) if plan
                else np.zeros(0, np.int64))
        tris = _triangle_rows(g, eids, budget)
        sp.count(triangles=len(tris))
    return tris


def support_from_triangle_list(tris: np.ndarray, m: int) -> np.ndarray:
    """sup(e) from a static triangle list (all edges alive).

    Peeling needs the triangle list anyway, so deriving the initial supports
    from it saves a second full wedge enumeration.
    """
    sup = np.zeros(m, dtype=np.int64)
    if len(tris):
        flat = np.asarray(tris).reshape(-1)
        counts = np.bincount(flat[flat < m], minlength=m)
        sup[: len(counts)] += counts[:m]
    return sup


# ---------------------------------------------------------------------------
# edge -> triangle incidence CSR (frontier peel preprocessing, DESIGN.md §3)
# ---------------------------------------------------------------------------

def triangle_incidence_np(tris: np.ndarray, m: int) -> tuple[np.ndarray, np.ndarray]:
    """CSR index from edge id to the ids of triangles containing it.

    Args:
      tris: (T, 3) edge-id triples; rows may reference the drop slot (id >= m,
        used for padding) — those entries are excluded.
      m: number of real edges.

    Returns:
      (tri_indptr, tri_ids): ``tri_ids[tri_indptr[e]:tri_indptr[e+1]]`` are
      the triangle row indices containing edge ``e``.  len(tri_ids) == 3T for
      an unpadded list (each triangle appears in exactly 3 rows).
    """
    tris = np.asarray(tris)
    if len(tris) == 0 or m == 0:
        return np.zeros(m + 1, np.int32), np.zeros(0, np.int32)
    with span("incidence") as sp:
        flat_e = tris.reshape(-1).astype(np.int64)
        flat_t = np.repeat(np.arange(len(tris), dtype=np.int64), 3)
        keep = flat_e < m
        flat_e, flat_t = flat_e[keep], flat_t[keep]
        order = np.argsort(flat_e, kind="stable")
        tri_ids = flat_t[order].astype(np.int32)
        indptr = np.zeros(m + 1, dtype=np.int64)
        np.add.at(indptr, flat_e + 1, 1)
        indptr = np.cumsum(indptr).astype(np.int32)
        sp.count(slots=len(tri_ids))
    return indptr, tri_ids


def triangle_density(m: int, n_tris: int) -> float:
    """Incidence entries per edge slot, 3T / E — the routing statistic the
    fused frontier-peel kernel shares with the dense-core dispatch
    (DESIGN.md §13).  Each fused removal round streams the FULL triangle
    list, so the dense sweep amortizes its one-hot matmuls only when the
    lane is triangle-dense; below ~1 entry per edge the sparse
    gather/scatter chain wins."""
    if m <= 0:
        return 0.0
    return 3.0 * n_tris / m


# ---------------------------------------------------------------------------
# JAX path
# ---------------------------------------------------------------------------

def _search_iters(max_row: int) -> int:
    return max(1, math.ceil(math.log2(max_row + 1))) if max_row > 0 else 1


def _row_lower_bound_jax(nbrs, lo, hi, target, iters):
    n_entries = nbrs.shape[0]

    def body(_, carry):
        lo, hi = carry
        mid = (lo + hi) >> 1
        midc = jnp.minimum(mid, max(n_entries - 1, 0))
        less = jnp.where(lo < hi, nbrs[midc] < target, False)
        new_lo = jnp.where(less, mid + 1, lo)
        new_hi = jnp.where(less, hi, jnp.where(lo < hi, mid, hi))
        return new_lo, new_hi

    lo, hi = jax.lax.fori_loop(0, iters, body, (lo, hi))
    return lo


@partial(jax.jit, static_argnames=("D", "iters", "chunk"))
def _support_scan(eids_pad, src, dst, indptr, nbrs, nbr_eid, *, D, iters, chunk):
    """Partial sup(e) from the wedges of the given oriented edges.

    Args:
      eids_pad: (E_pad,) edge ids to enumerate, padded with ``m`` sentinels to
        a multiple of ``chunk``.
      src, dst: (m + 1,) oriented endpoints with a zero pad slot at index m.
      D: static wedge-slot bound — max out-degree of the *source rows of this
        bucket*, not of the whole graph (the skew-aware part, DESIGN.md §4).

    Returns sup over (m + 1) slots; the last slot absorbs masked scatters.
    """
    m = src.shape[0] - 1
    n_chunks = eids_pad.shape[0] // chunk
    sup0 = jnp.zeros(m + 1, jnp.int32)

    def one_chunk(sup, c):
        eids = jax.lax.dynamic_slice(eids_pad, (c * chunk,), (chunk,))
        live = eids < m
        a = src[eids]
        b = dst[eids]
        slot = jnp.arange(D, dtype=jnp.int32)[None, :]
        row_start = indptr[a][:, None]
        row_len = (indptr[a + 1] - indptr[a])[:, None]
        valid = (slot < row_len) & live[:, None]
        pos_aw = jnp.minimum(row_start + slot, max(nbrs.shape[0] - 1, 0))
        w = nbrs[pos_aw]
        lo = jnp.broadcast_to(indptr[b][:, None], (chunk, D))
        hi = jnp.broadcast_to(indptr[b + 1][:, None], (chunk, D))
        p = _row_lower_bound_jax(nbrs, lo.reshape(-1), hi.reshape(-1), w.reshape(-1), iters)
        p = p.reshape(chunk, D)
        in_row = p < indptr[b + 1][:, None]
        pc = jnp.minimum(p, max(nbrs.shape[0] - 1, 0))
        hit = valid & in_row & (nbrs[pc] == w)
        sink = jnp.int32(m)
        e_ab = jnp.where(hit, eids[:, None], sink)
        e_aw = jnp.where(hit, nbr_eid[pos_aw], sink)
        e_bw = jnp.where(hit, nbr_eid[pc], sink)
        ones = jnp.ones_like(e_ab, dtype=jnp.int32)
        sup = sup.at[e_ab].add(ones, mode="drop")
        sup = sup.at[e_aw].add(ones, mode="drop")
        sup = sup.at[e_bw].add(ones, mode="drop")
        return sup, None

    sup, _ = jax.lax.scan(one_chunk, sup0, jnp.arange(n_chunks, dtype=jnp.int32))
    return sup


def _pow2_ceil(x: int) -> int:
    return 1 << max(0, math.ceil(math.log2(max(1, x))))


def _pow4_ceil(x: int) -> int:
    """Next power of four — the coarse padding grid of the OOC batch engine
    (DESIGN.md §8): fewer distinct static shapes than pow2, at most 4x pad."""
    return 1 << (2 * max(0, math.ceil(math.log2(max(1, x)) / 2)))


@dataclasses.dataclass(frozen=True)
class WedgeBucket:
    """One power-of-two out-degree class of oriented edges."""

    eids: np.ndarray      # (E_pad,) edge ids, padded with m sentinels
    n_real: int           # real (unpadded) edge count
    D: int                # wedge-slot bound for this bucket (pow2)
    chunk: int            # scan chunk size

    @property
    def capacity(self) -> int:
        """Wedge-tensor elements this bucket materializes in total."""
        return len(self.eids) * self.D


def wedge_bucket_plan(
    g: Graph, chunk: int = 1 << 14, budget: int = 1 << 18
) -> list[WedgeBucket]:
    """Group oriented edges by the pow2 out-degree of their source row.

    Every bucket runs the wedge enumeration with its own D = 2^b covering
    source rows of length in (2^(b-1), 2^b], so a single hub vertex no longer
    inflates the wedge tensor of every chunk (the dense blow-up of the
    global-D path on power-law graphs).  ``budget`` bounds chunk*D elements
    per scan step, keeping peak memory flat across buckets.
    """
    if g.m == 0:
        return []
    row_len = (g.indptr[g.src + 1] - g.indptr[g.src]).astype(np.int64)
    # bucket index: ceil(log2(row_len)), row_len >= 1 always (dst is in src's row)
    b_idx = np.zeros(g.m, dtype=np.int64)
    nz = row_len > 1
    b_idx[nz] = np.ceil(np.log2(row_len[nz])).astype(np.int64)
    plan: list[WedgeBucket] = []
    for b in np.unique(b_idx):
        ids = np.nonzero(b_idx == b)[0].astype(np.int32)
        D = 1 << int(b)
        # chunk never exceeds the bucket itself — padding a 2-edge bucket to
        # a 16k chunk would reintroduce the blow-up bucketing removes
        c = max(1, min(chunk, budget // D, _pow2_ceil(len(ids))))
        e_pad = -(-len(ids) // c) * c
        ids_pad = np.full(e_pad, g.m, np.int32)
        ids_pad[: len(ids)] = ids
        plan.append(WedgeBucket(eids=ids_pad, n_real=len(ids), D=D, chunk=c))
    return plan


def edge_support_jax(
    g: Graph, chunk: int = 1 << 14, *, bucketed: bool = True,
    budget: int = 1 << 18,
) -> jnp.ndarray:
    """Device-path support computation (jit'd, static shapes).

    ``bucketed=True`` (default) runs the skew-aware per-bucket wedge scans;
    ``bucketed=False`` restores the single global-D scan (the seed behavior,
    kept for benchmarks and as a fallback).
    """
    if g.m == 0:
        return jnp.zeros(0, jnp.int32)
    (src, dst, indptr, nbrs, nbr_eid), _ = upload(
        np.concatenate([g.src, np.zeros(1, np.int32)]),
        np.concatenate([g.dst, np.zeros(1, np.int32)]),
        g.indptr, g.nbrs, g.nbr_eid)
    iters = _search_iters(g.max_out_deg)
    if bucketed:
        plan = wedge_bucket_plan(g, chunk, budget)
    else:
        c = max(8, min(chunk, _pow2_ceil(g.m)))
        e_pad = -(-g.m // c) * c
        ids_pad = np.full(e_pad, g.m, np.int32)
        ids_pad[: g.m] = np.arange(g.m, dtype=np.int32)
        plan = [WedgeBucket(ids_pad, g.m, max(g.max_out_deg, 1), c)]
    sup = jnp.zeros(g.m + 1, jnp.int32)
    for bucket in plan:
        # trusscheck: allow[TRK104] -- bucket eid lengths and D/chunk sit on the pow2 grid wedge_bucket_plan pads to, so distinct shapes (hence compiles) are O(log) per run by design
        sup = sup + _support_scan(
            jnp.asarray(bucket.eids), src, dst, indptr, nbrs, nbr_eid,
            D=bucket.D, iters=iters, chunk=bucket.chunk,
        )
    return sup[: g.m]


# ---------------------------------------------------------------------------
# dense/sparse dispatch (DESIGN.md §2)
# ---------------------------------------------------------------------------

def dense_core_stats(g: Graph) -> tuple[np.ndarray, float]:
    """(sorted active vertices, edge density over active vertices)."""
    if g.m == 0:
        return np.zeros(0, np.int64), 0.0
    verts = np.unique(g.edges.reshape(-1)).astype(np.int64)
    n_act = len(verts)
    density = 2.0 * g.m / (n_act * (n_act - 1)) if n_act > 1 else 0.0
    return verts, density


def edge_support_auto(
    g: Graph,
    *,
    dense_threshold: float = 0.125,
    dense_max_n: int = 4096,
) -> np.ndarray:
    """Support with sparse/dense routing (DESIGN.md §2).

    Dense-core partitions (active-vertex density above ``dense_threshold``
    and small enough for an adjacency tile set) go to the blocked dense
    matmul path — the Pallas MXU kernel on TPU, its jnp reference elsewhere.
    Sparse graphs take the bucketed wedge enumeration.
    """
    if g.m == 0:
        return np.zeros(0, np.int64)
    with span("edge_support", m=g.m):
        verts, density = dense_core_stats(g)
        n_act = len(verts)
        if n_act <= dense_max_n and density >= dense_threshold:
            from repro.kernels.triangle_count.ops import dense_edge_support

            relabel = np.zeros(int(verts.max()) + 1, np.int64)
            relabel[verts] = np.arange(n_act)
            compact = relabel[g.edges.astype(np.int64)].astype(np.int32)
            use_kernel = jax.default_backend() == "tpu"
            return dense_edge_support(
                n_act, compact, use_kernel=use_kernel,
                interpret=not use_kernel)
        return np.asarray(edge_support_jax(g)).astype(np.int64)


# ---------------------------------------------------------------------------
# Graph-store triangle spilling (DESIGN.md §15): the incremental per-round
# triangle list is the largest single array the out-of-core round loop holds
# across a yield, so it rides the same chunked store as the graph arrays.
# ---------------------------------------------------------------------------

def spill_triangles(store, key: str, tris: np.ndarray) -> None:
    """Spill a round's triangle list (local edge-id triples) to ``store``
    under ``key``; an existing list under the key is replaced."""
    store.put(key, np.ascontiguousarray(tris, dtype=np.int64).reshape(-1, 3))


def load_triangles(store, key: str) -> np.ndarray:
    """Reload a triangle list spilled by :func:`spill_triangles`."""
    return np.asarray(store.get(key), dtype=np.int64).reshape(-1, 3)


def iter_triangle_chunks(store, key: str):
    """Stream a spilled triangle list chunk-wise: yields (rows, 3) int64
    blocks sized by the store's chunk granularity, so a consumer's peak
    working set is one chunk instead of the whole 3·T list (the OOC-store
    fix of DESIGN.md §16)."""
    for part in store.get_chunks(key):
        yield np.asarray(part, dtype=np.int64).reshape(-1, 3)


def stream_spill_triangles(store, key: str):
    """An appendable (rows, 3) triangle writer — the streaming counterpart
    of :func:`spill_triangles`.  The key is registered at ``close()``; on a
    chunked store, chunk files flush incrementally so the producer never
    holds the full list either."""
    return store.stream_put(key, np.int64, (3,))
