"""Plain trussness reference, independent of the code under test.

Triangle listing by degree orientation and hashed edge lookup, then level
peeling: at each level k every alive edge with support <= k - 2 is removed
and gets φ = k, each alive triangle it closes dies once, and the surviving
corners lose one support per dead triangle, until no such edge is left;
then k rises.  This is the definition of trussness (Wang and Cheng,
"Truss Decomposition in Massive Networks", Algorithm 2) applied to whole
frontiers, which peeling's confluence makes equal to one edge at a time.

Imports numpy only.  ``once_per_level=True`` is the benchmark's control:
each level removes its first frontier and moves on without iterating to a
fixed point, a round cap that breaks the exact-trussness guarantee.
"""

from __future__ import annotations

import numpy as np

_PAIR_CHUNK = 1 << 22


def list_triangles(n: int, edges: np.ndarray) -> np.ndarray:
    """(T, 3) int64 edge-id triples of every triangle of a canonical
    edge list, each triangle once."""
    m = len(edges)
    if m == 0:
        return np.zeros((0, 3), np.int64)
    u = edges[:, 0].astype(np.int64)
    v = edges[:, 1].astype(np.int64)
    deg = np.bincount(np.concatenate([u, v]), minlength=n)
    rank = np.empty(n, np.int64)
    rank[np.lexsort((np.arange(n), deg))] = np.arange(n)
    lo = np.where(rank[u] < rank[v], u, v)
    hi = np.where(rank[u] < rank[v], v, u)
    # out-lists of the orientation lo -> hi, each sorted by rank
    order = np.lexsort((rank[hi], lo))
    src, dst, eid = lo[order], hi[order], order
    indptr = np.concatenate([[0], np.cumsum(np.bincount(src, minlength=n))])
    key = src * n + dst
    key_order = np.argsort(key)
    key_sorted, key_eid = key[key_order], eid[key_order]
    # each wedge (src; dst[p], dst[q]) with p < q in one out-list is
    # closed by the oriented edge dst[p] -> dst[q] if it exists
    later = indptr[src + 1] - np.arange(m) - 1
    csum = np.cumsum(later)
    cuts = np.searchsorted(
        csum, np.arange(1, csum[-1] // _PAIR_CHUNK + 1) * _PAIR_CHUNK,
        side="right")
    out = []
    for start, stop in zip([0, *cuts], [*cuts, m]):
        cnt = later[start:stop]
        first = np.repeat(np.arange(start, stop), cnt)
        base = np.repeat(np.cumsum(cnt) - cnt, cnt)
        second = first + 1 + (np.arange(len(first)) - base)
        want = dst[first] * n + dst[second]
        at = np.minimum(np.searchsorted(key_sorted, want), m - 1)
        hit = key_sorted[at] == want
        out.append(np.stack([eid[first[hit]], eid[second[hit]],
                             key_eid[at[hit]]], 1))
    return np.concatenate(out) if out else np.zeros((0, 3), np.int64)


def trussness(m: int, tris: np.ndarray, *,
              once_per_level: bool = False) -> np.ndarray:
    """φ of every edge from its triangle list (edges in no triangle: 2)."""
    tris = np.asarray(tris, np.int64).reshape(-1, 3)
    flat = tris.ravel()
    sup = np.bincount(flat, minlength=m).astype(np.int64)
    inc_order = np.argsort(flat, kind="stable")
    inc_tri = inc_order // 3
    inc_ptr = np.concatenate([[0], np.cumsum(np.bincount(flat, minlength=m))])
    phi = np.zeros(m, np.int64)
    alive = np.ones(m, bool)
    tri_alive = np.ones(len(tris), bool)
    left = m
    k = 2
    while left:
        front = np.flatnonzero(alive & (sup <= k - 2))
        if len(front) == 0:
            k = max(k + 1, int(sup[alive].min()) + 2)
            continue
        phi[front] = k
        alive[front] = False
        left -= len(front)
        lens = inc_ptr[front + 1] - inc_ptr[front]
        slots = (np.repeat(inc_ptr[front] - np.cumsum(lens) + lens, lens)
                 + np.arange(int(lens.sum())))
        dead = np.unique(inc_tri[slots])
        dead = dead[tri_alive[dead]]
        tri_alive[dead] = False
        corners = tris[dead].ravel()
        corners = corners[alive[corners]]
        sup -= np.bincount(corners, minlength=m)
        if once_per_level:
            k += 1
    return phi


def phi(n: int, edges: np.ndarray) -> np.ndarray:
    """Trussness of every edge of a canonical edge list."""
    return trussness(len(edges), list_triangles(n, edges))


def control_phi(n: int, edges: np.ndarray) -> np.ndarray:
    """The control: trussness with one removal sweep per level."""
    return trussness(len(edges), list_triangles(n, edges),
                     once_per_level=True)
