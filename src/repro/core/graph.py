"""Graph representation for truss decomposition.

Host-side (numpy) preprocessing produces static-shape arrays consumed by the
JAX algorithms:

* canonical edge list ``edges`` — (m, 2) int32, ``u < v``, lexicographically
  sorted, deduplicated, self-loop free.  The row index of an edge is its
  *edge id*, stable across the whole decomposition.
* degree-ordered orientation (the paper's Theorem-1 trick): rank vertices by
  ``(deg, id)``; orient every edge from its lower-rank endpoint ``a`` to the
  higher-rank endpoint ``b``.  Out-degrees are then bounded by ``O(sqrt(m))``
  for any graph, which is what gives wedge enumeration its ``O(m^1.5)`` total
  work bound — the vectorized analogue of "iterate over the lower-degree
  endpoint's neighbors".
* CSR of the oriented out-neighborhoods with rows sorted by neighbor id, so
  membership tests are vectorized binary searches instead of hash lookups
  (sorted arrays are the TPU-idiomatic replacement for the paper's hashtable).
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import numpy as np

from repro.core.spans import span

Int = np.int32


def canonical_edges(edges: np.ndarray, n: Optional[int] = None) -> np.ndarray:
    """Canonicalize an edge list: undirected, simple, u < v, lex-sorted.

    Vertex ids are validated: negatives always raise, and with an explicit
    ``n`` any id >= n raises — the ``u * n + v`` dedup key below is
    injective only for ids in [0, n), so an out-of-range id would silently
    fold distinct edges together (and decode to garbage) instead of
    failing loudly.
    """
    edges = np.asarray(edges, dtype=np.int64).reshape(-1, 2)
    if edges.size == 0:
        return np.zeros((0, 2), dtype=Int)
    if int(edges.min()) < 0:
        raise ValueError(
            f"edge list contains negative vertex id {int(edges.min())}")
    u = np.minimum(edges[:, 0], edges[:, 1])
    v = np.maximum(edges[:, 0], edges[:, 1])
    keep = u != v  # drop self loops
    u, v = u[keep], v[keep]
    if n is None:
        n = int(v.max()) + 1 if v.size else 0
    elif v.size and int(v.max()) >= n:
        raise ValueError(
            f"edge list references vertex id {int(v.max())} but n={n}; "
            f"vertex ids must lie in [0, n)")
    key = u * np.int64(n) + v
    key = np.unique(key)
    out = np.stack([key // n, key % n], axis=1)
    return out.astype(Int)


def degrees(n: int, edges: np.ndarray) -> np.ndarray:
    deg = np.zeros(n, dtype=Int)
    if len(edges):
        np.add.at(deg, edges[:, 0], 1)
        np.add.at(deg, edges[:, 1], 1)
    return deg


def csr_keys(indptr: np.ndarray, nbrs: np.ndarray, n: int) -> np.ndarray:
    """Composite key row * n + nbr of every CSR entry, int64.  Rows come in
    order and each row is sorted, so the keys ascend: a ``searchsorted``
    into them is a membership test for any (row, nbr) pair."""
    rows = np.repeat(np.arange(n, dtype=np.int64), np.diff(indptr))
    return rows * np.int64(n) + nbrs


class Graph:
    """Static-shape packed graph (all arrays numpy; moved to device lazily).

    Attributes:
      n: number of vertices.
      edges: (m, 2) canonical edge list (edge id == row index).
      deg: (n,) degrees in the undirected graph.
      rank: (n,) degree-order rank of each vertex (position in (deg, id) order).
      src, dst: (m,) oriented endpoints per edge id: rank[src] < rank[dst].
      indptr: (n+1,) CSR row pointers of oriented out-adjacency.
      nbrs: (m,) concatenated out-neighbor lists, each row sorted by vertex id.
      nbr_eid: (m,) edge id of each (row_vertex, nbrs[i]) entry.
      max_out_deg: max oriented out-degree (static bound for wedge enumeration).

    With a :class:`~repro.core.store.GraphStore` attached (``store=``), the
    array attributes become *views through the store*: :meth:`spill` moves
    them out (to disk, for ``ChunkedDiskStore``) and each attribute access
    re-materializes lazily via ``store.get`` — the out-of-core round loop
    spills the working graph between rounds so the host never holds it
    whole (DESIGN.md §15).  ``store=None`` keeps today's behavior exactly:
    arrays are plain resident ndarrays and every store method is a no-op.
    """

    # the spillable payload, in spill order (scalars n/max_out_deg stay)
    _ARRAYS = ("edges", "deg", "rank", "src", "dst", "indptr", "nbrs",
               "nbr_eid")

    def __init__(self, *, n: int, edges: np.ndarray, deg: np.ndarray,
                 rank: np.ndarray, src: np.ndarray, dst: np.ndarray,
                 indptr: np.ndarray, nbrs: np.ndarray, nbr_eid: np.ndarray,
                 max_out_deg: int, store=None,
                 spill_plan: Optional[Dict[str, Tuple]] = None):
        self.n = int(n)
        self.max_out_deg = int(max_out_deg)
        self._m = len(edges)
        self._store = store
        self._key: Optional[str] = None
        self._spill_plan = spill_plan
        self._spilled: set = set()
        self._arrays: Dict[str, np.ndarray] = {
            "edges": edges, "deg": deg, "rank": rank, "src": src,
            "dst": dst, "indptr": indptr, "nbrs": nbrs, "nbr_eid": nbr_eid,
        }

    # -- store-routed array access ------------------------------------------
    def _fetch(self, name: str) -> np.ndarray:
        arr = self._arrays.get(name)
        if arr is None:
            if self._store is None or self._key is None:
                raise RuntimeError(
                    f"graph array {name!r} was dropped without a store to "
                    f"reload it from")
            arr = self._store.get(f"{self._key}/{name}")
            self._arrays[name] = arr
        return arr

    @property
    def edges(self) -> np.ndarray:
        return self._fetch("edges")

    @property
    def deg(self) -> np.ndarray:
        return self._fetch("deg")

    @property
    def rank(self) -> np.ndarray:
        return self._fetch("rank")

    @property
    def src(self) -> np.ndarray:
        return self._fetch("src")

    @property
    def dst(self) -> np.ndarray:
        return self._fetch("dst")

    @property
    def indptr(self) -> np.ndarray:
        return self._fetch("indptr")

    @property
    def nbrs(self) -> np.ndarray:
        return self._fetch("nbrs")

    @property
    def nbr_eid(self) -> np.ndarray:
        return self._fetch("nbr_eid")

    @property
    def m(self) -> int:
        return self._m

    @property
    def store(self):
        return self._store

    # -- spill lifecycle (all no-ops without a store) ------------------------
    def spill(self) -> None:
        """Move the packed arrays into the store and drop the host refs.

        A graph produced by :meth:`remove_edges` carries a *spill plan*:
        filtered arrays go through ``store.put_filtered`` (chunk-wise —
        source chunks whose rows are all kept are aliased, not rewritten)
        and the reused ``rank`` through ``store.alias`` (zero write I/O,
        the PR-2 rank-reuse discipline made visible on disk).  Arrays
        already spilled once are never rewritten — re-materialized copies
        are just dropped.
        """
        if self._store is None:
            return
        if self._key is None:
            self._key = self._store.graph_key()
        plan = self._spill_plan or {}
        for name in self._ARRAYS:
            if name in self._spilled:
                continue
            arr = self._arrays.get(name)
            if arr is None:
                continue
            dst_key = f"{self._key}/{name}"
            step = plan.get(name)
            if step is None:
                self._store.put(dst_key, arr)
            elif step[0] == "alias":
                self._store.alias(dst_key, step[1], arr)
            elif step[0] == "insert":  # ("insert", src_key, is_new_mask)
                self._store.put_inserted(dst_key, step[1], step[2], arr)
            else:  # ("filter", src_key, keep_mask)
                self._store.put_filtered(dst_key, step[1], step[2], arr)
            self._spilled.add(name)
        self._spill_plan = None
        self._arrays = {}

    def prefetch(self, names: Optional[Sequence[str]] = None) -> None:
        """Hint the store to warm this graph's arrays for the next round."""
        if self._store is None or self._key is None:
            return
        self._store.prefetch([f"{self._key}/{nm}"
                              for nm in (names or self._ARRAYS)
                              if nm in self._spilled])

    def unload(self) -> None:
        """Drop re-materialized host copies of already-spilled arrays."""
        if self._store is None:
            return
        for name in list(self._arrays):
            if name in self._spilled:
                del self._arrays[name]

    def release(self) -> None:
        """Drop this graph's chunks from the store (refcounted: chunk files
        aliased into a successor graph survive)."""
        if self._store is not None and self._key is not None:
            self._store.release(self._key)
        self._arrays = {}
        self._spilled = set()
        self._key = None

    # -- structural ops ------------------------------------------------------
    def subgraph(self, edge_mask: np.ndarray) -> "Graph":
        """Graph induced by the kept edges (vertex ids preserved)."""
        return build_graph(self.n, self.edges[edge_mask])

    def remove_edges(self, remove_mask: np.ndarray, *,
                     detach: bool = False) -> "Graph":
        """Incremental maintenance: drop the masked edges without a rebuild.

        ``build_graph`` pays a full lexsort (ranks) plus a lexsort of the
        oriented edge list (CSR) every call; the out-of-core drivers remove
        a batch of internal edges per round, so this filters instead:

        * ``rank`` is REUSED — it stays a total order, so the orientation of
          every surviving edge is unchanged and wedge enumeration remains
          correct (the forward algorithm only needs *some* fixed acyclic
          orientation).  The O(sqrt(m)) out-degree bound degrades gracefully
          as ranks go stale w.r.t. the shrunk degrees; correctness does not.
        * CSR rows are filtered in place — each row stays sorted by neighbor
          id, so membership binary searches keep working.

        Total cost O(n + m) with no sort.  Edge ids are renumbered densely;
        old id ``i`` maps to ``cumsum(keep)[i] - 1`` (order preserved, so the
        canonical lex order of ``edges`` is intact).

        Store-backed graphs hand the successor a *spill plan* (which mask
        filters which array, plus the ``rank`` alias) so its :meth:`spill`
        rewrites only the chunks the filter actually touched.
        ``detach=True`` produces a plain in-memory graph instead — for
        short-lived scoped graphs (the partition batch builder) that must
        never allocate store namespaces.
        """
        remove_mask = np.asarray(remove_mask, dtype=bool)
        if remove_mask.shape != (self.m,):
            raise ValueError(f"mask shape {remove_mask.shape} != ({self.m},)")
        keep = ~remove_mask
        new_edges = self.edges[keep]
        # old edge id -> new edge id (valid only where keep)
        new_id = np.cumsum(keep, dtype=np.int64) - 1
        deg = self.deg.copy()
        gone = self.edges[remove_mask]
        if len(gone):
            np.subtract.at(deg, gone[:, 0], 1)
            np.subtract.at(deg, gone[:, 1], 1)
        # filter CSR entries (row ownership from the old indptr)
        out_deg_old = (self.indptr[1:] - self.indptr[:-1]).astype(np.int64)
        rows = np.repeat(np.arange(self.n, dtype=np.int64), out_deg_old)
        keep_entry = keep[self.nbr_eid]
        counts = np.zeros(self.n + 1, dtype=np.int64)
        if keep_entry.any():
            np.add.at(counts, rows[keep_entry] + 1, 1)
        indptr = np.cumsum(counts).astype(Int)
        out_deg = indptr[1:] - indptr[:-1]
        store = None if detach else self._store
        plan = None
        if store is not None and self._key is not None:
            plan = {
                "edges": ("filter", f"{self._key}/edges", keep),
                "src": ("filter", f"{self._key}/src", keep),
                "dst": ("filter", f"{self._key}/dst", keep),
                "nbrs": ("filter", f"{self._key}/nbrs", keep_entry),
                "rank": ("alias", f"{self._key}/rank"),
                # deg / indptr / nbr_eid are recomputed, not filtered: they
                # take plain puts (no plan entry)
            }
        return Graph(
            n=self.n, edges=new_edges, deg=deg, rank=self.rank,
            src=self.src[keep], dst=self.dst[keep], indptr=indptr,
            nbrs=self.nbrs[keep_entry],
            nbr_eid=new_id[self.nbr_eid[keep_entry]].astype(Int),
            max_out_deg=int(out_deg.max()) if self.n and len(new_edges) else 0,
            store=store, spill_plan=plan,
        )

    def add_edges(self, new_edges: np.ndarray, *,
                  detach: bool = False) -> "Graph":
        """Incremental maintenance: splice new edges in without a rebuild.

        The mirror image of :meth:`remove_edges`, under the same
        rank-reuse / no-lexsort discipline (DESIGN.md §16):

        * ``rank`` is REUSED — it stays a total order over the fixed
          vertex set, so every existing edge keeps its orientation and the
          inserted edges are oriented by the same ranks (the forward
          algorithm only needs *some* fixed acyclic orientation).  Ranks
          go stale w.r.t. the grown degrees; the O(sqrt(m)) out-degree
          bound degrades gracefully, correctness does not.
        * the canonical lex order of ``edges`` is preserved by a
          searchsorted SPLICE: old edge id ``i`` maps to ``i + (#inserted
          keys < key_i)`` and the inserted edges take the gap ids — the m
          existing edges are never re-sorted.  Each CSR row absorbs its
          new entries the same way (a merge of two sorted runs keyed by
          ``row * n + nbr``); only the k inserted entries are ever sorted.

        Inserted pairs are canonicalized against ``self.n`` (self loops,
        duplicates and edges already present are dropped); when nothing
        remains, ``self`` is returned unchanged.  Total cost O(n + m +
        k log k) with no sort of existing data.

        Store-backed graphs hand the successor an *insertion-preserving*
        spill plan (``store.put_inserted``): source chunks with no
        interior splice point are aliased, so a small edit batch costs
        write I/O proportional to the chunks it touches, not the graph
        (the insertion side of the chunk-wise ``remove_edges`` filter).
        ``detach=True`` produces a plain in-memory graph instead.
        """
        ins = canonical_edges(new_edges, self.n)
        if len(ins):
            ins = ins[edge_id_lookup(self, ins[:, 0], ins[:, 1]) < 0]
        if len(ins) == 0:
            return self
        n, m, k = self.n, self.m, len(ins)
        old_keys = (self.edges[:, 0].astype(np.int64) * np.int64(n)
                    + self.edges[:, 1])
        ins_keys = ins[:, 0].astype(np.int64) * np.int64(n) + ins[:, 1]
        # splice position of each inserted edge within the OLD edge list;
        # old id i shifts by the number of inserted keys before it and
        # inserted edge j lands at pos[j] + j (keys are unique, pos sorted)
        pos = np.searchsorted(old_keys, ins_keys)
        shift = np.searchsorted(ins_keys, old_keys)
        new_id_old = np.arange(m, dtype=np.int64) + shift
        new_id_ins = pos.astype(np.int64) + np.arange(k, dtype=np.int64)
        edges = np.insert(self.edges, pos, ins, axis=0)
        is_new = np.zeros(m + k, dtype=bool)
        is_new[new_id_ins] = True
        deg = self.deg.copy()
        np.add.at(deg, ins[:, 0], 1)
        np.add.at(deg, ins[:, 1], 1)
        rank = self.rank
        u_first = rank[ins[:, 0]] < rank[ins[:, 1]]
        ins_src = np.where(u_first, ins[:, 0], ins[:, 1]).astype(Int)
        ins_dst = np.where(u_first, ins[:, 1], ins[:, 0]).astype(Int)
        src = np.insert(self.src, pos, ins_src)
        dst = np.insert(self.dst, pos, ins_dst)
        # CSR merge: the existing entries are already sorted by the
        # composite key row * n + nbr (rows ascending, each row sorted by
        # neighbor id); sort just the k new entries and splice them at
        # their searchsorted positions
        out_deg_old = (self.indptr[1:] - self.indptr[:-1]).astype(np.int64)
        key_old = csr_keys(self.indptr, self.nbrs, n)
        order = np.lexsort((ins_dst, ins_src))
        e_src, e_dst = ins_src[order], ins_dst[order]
        e_eid = new_id_ins[order]
        key_new = e_src.astype(np.int64) * np.int64(n) + e_dst
        cpos = np.searchsorted(key_old, key_new)
        nbrs = np.insert(self.nbrs, cpos, e_dst)
        nbr_eid = np.insert(new_id_old[self.nbr_eid], cpos,
                            e_eid).astype(Int)
        is_new_entry = np.zeros(len(nbrs), dtype=bool)
        is_new_entry[cpos + np.arange(k, dtype=np.int64)] = True
        counts = np.zeros(n + 1, dtype=np.int64)
        counts[1:] = out_deg_old + np.bincount(
            e_src.astype(np.int64), minlength=n)
        indptr = np.cumsum(counts).astype(Int)
        out_deg = indptr[1:] - indptr[:-1]
        store = None if detach else self._store
        plan = None
        if store is not None and self._key is not None:
            plan = {
                "edges": ("insert", f"{self._key}/edges", is_new),
                "src": ("insert", f"{self._key}/src", is_new),
                "dst": ("insert", f"{self._key}/dst", is_new),
                "nbrs": ("insert", f"{self._key}/nbrs", is_new_entry),
                "rank": ("alias", f"{self._key}/rank"),
                # deg / indptr / nbr_eid are recomputed, not spliced: they
                # take plain puts (no plan entry)
            }
        return Graph(
            n=n, edges=edges.astype(Int), deg=deg, rank=rank, src=src,
            dst=dst, indptr=indptr, nbrs=nbrs, nbr_eid=nbr_eid,
            max_out_deg=int(out_deg.max()) if n and len(edges) else 0,
            store=store, spill_plan=plan,
        )


def build_graph(n: int, edges: np.ndarray, store=None) -> Graph:
    """Build the oriented CSR package from a canonical edge list.

    ``store`` attaches a :class:`~repro.core.store.GraphStore`; the graph
    stays fully resident until its first :meth:`Graph.spill`.
    """
    with span("build_graph") as sp:
        g = _build_graph(n, edges, store)
        sp.count(m=g.m)
    return g


def _build_graph(n: int, edges: np.ndarray, store) -> Graph:
    edges = canonical_edges(edges, n)
    m = len(edges)
    deg = degrees(n, edges)
    # rank by (deg, id): stable and total.
    order = np.lexsort((np.arange(n), deg))  # vertices sorted by (deg, id)
    rank = np.empty(n, dtype=Int)
    rank[order] = np.arange(n, dtype=Int)
    if m == 0:
        return Graph(
            n=n, edges=edges, deg=deg, rank=rank,
            src=np.zeros(0, Int), dst=np.zeros(0, Int),
            indptr=np.zeros(n + 1, Int), nbrs=np.zeros(0, Int),
            nbr_eid=np.zeros(0, Int), max_out_deg=0, store=store,
        )
    u, v = edges[:, 0], edges[:, 1]
    u_first = rank[u] < rank[v]
    src = np.where(u_first, u, v).astype(Int)
    dst = np.where(u_first, v, u).astype(Int)
    # CSR over (src -> dst), rows sorted by dst id for binary search.
    order = np.lexsort((dst, src))
    rows = src[order]
    nbrs = dst[order]
    nbr_eid = np.arange(m, dtype=Int)[order]
    indptr = np.zeros(n + 1, dtype=Int)
    np.add.at(indptr, rows + 1, 1)
    indptr = np.cumsum(indptr, dtype=np.int64).astype(Int)
    out_deg = indptr[1:] - indptr[:-1]
    return Graph(
        n=n, edges=edges, deg=deg, rank=rank, src=src, dst=dst,
        indptr=indptr, nbrs=nbrs, nbr_eid=nbr_eid,
        max_out_deg=int(out_deg.max()) if n else 0, store=store,
    )


def edge_id_lookup(graph: Graph, a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Edge ids for vertex pairs (a, b); -1 if absent.  Host-side helper."""
    u = np.minimum(a, b).astype(np.int64)
    v = np.maximum(a, b).astype(np.int64)
    key = u * np.int64(graph.n) + v
    ekey = graph.edges[:, 0].astype(np.int64) * np.int64(graph.n) + graph.edges[:, 1]
    pos = np.searchsorted(ekey, key)
    pos = np.clip(pos, 0, len(ekey) - 1) if len(ekey) else np.zeros_like(pos)
    ok = len(ekey) > 0
    hit = ok & (ekey[pos] == key) if ok else np.zeros_like(key, dtype=bool)
    return np.where(hit, pos, -1).astype(Int)


def neighborhood_subgraph(
    graph: Graph, part_vertices: np.ndarray
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Extract NS(P): all edges with >= 1 endpoint in P (paper Definition 4).

    Returns (edge_ids, edges, internal_mask) where ``internal_mask`` marks
    edges with *both* endpoints in P (the paper's internal edges).
    """
    in_part = np.zeros(graph.n, dtype=bool)
    in_part[part_vertices] = True
    u_in = in_part[graph.edges[:, 0]]
    v_in = in_part[graph.edges[:, 1]]
    keep = u_in | v_in
    edge_ids = np.nonzero(keep)[0].astype(Int)
    internal = (u_in & v_in)[edge_ids]
    return edge_ids, graph.edges[edge_ids], internal


def undirected_csr(graph: Graph) -> tuple[np.ndarray, np.ndarray]:
    """CSR of the full (undirected) adjacency: (indptr, nbrs).

    The packed :class:`Graph` stores only the oriented out-adjacency; BFS
    growth (the locality-aware partitioner) needs both directions.  Each
    edge contributes two entries.  Built once per partition round, so the
    grouping uses a single stable argsort on the row key — neighbor order
    within a row is unspecified (no caller relies on it).
    """
    n, m = graph.n, graph.m
    if m == 0:
        return np.zeros(n + 1, Int), np.zeros(0, Int)
    e = graph.edges
    rows = np.concatenate([e[:, 0], e[:, 1]])
    cols = np.concatenate([e[:, 1], e[:, 0]])
    cols = cols[np.argsort(rows, kind="stable")]
    indptr = np.zeros(n + 1, dtype=np.int64)
    indptr[1:] = np.cumsum(np.bincount(rows, minlength=n))
    return indptr, cols.astype(Int)


def wedge_weight(deg_a: np.ndarray, deg_b: np.ndarray) -> np.ndarray:
    """Per-pair closed-wedge weight ``max(min(deg_a, deg_b) - 1, 0)`` —
    the wedges through an (a, b) edge that could close into a triangle.
    The single formula behind the DESIGN.md §11 cost model: shared by
    :func:`closed_wedge_estimate` and the locality partitioner's
    admission gain so the accuracy counter (``OocStats.tri_est_error``)
    always validates the formula that actually steers part growth."""
    return np.maximum(np.minimum(deg_a, deg_b) - 1, 0)


def closed_wedge_estimate(graph: Graph) -> np.ndarray:
    """Per-vertex triangle-volume estimate from wedge counts, O(m).

    ``t(v) = (1/2) * Σ_{u ∈ N(v)} max(min(deg(u), deg(v)) - 1, 0)`` — each
    neighbor u contributes the wedges (v, u, ·) that *could* close into a
    triangle, capped by v's own degree (a triangle at v needs its third
    vertex adjacent to v too).  Exact on cliques (``t(v) = C(deg(v), 2)``,
    the incident triangle count) and an upper-bound-flavored estimate on
    sparse graphs; ``Σ_v t(v) / 3`` estimates the graph's triangle count.

    This is the cost model of the triangle-aware locality partitioner
    (DESIGN.md §11): the per-edge weight depends only on endpoint degrees,
    so two scatters over the edge list suffice — no CSR, no sort — which
    is what lets every partition round afford it.  Additive over vertex
    sets, so per-part triangle budgets compose; its per-run accuracy is
    measured against the actual enumeration (``OocStats.tri_est_error``).
    """
    if graph.m == 0:
        return np.zeros(graph.n, np.int64)
    deg = graph.deg.astype(np.int64)
    e = graph.edges.astype(np.int64)
    w = wedge_weight(deg[e[:, 0]], deg[e[:, 1]]).astype(np.float64)
    est = np.bincount(e[:, 0], weights=w, minlength=graph.n) \
        + np.bincount(e[:, 1], weights=w, minlength=graph.n)
    return est.astype(np.int64) // 2


def compact_index(sorted_ids: np.ndarray, values: np.ndarray) -> np.ndarray:
    """Map global ids to part-local slots: position of ``values`` in the
    ascending ``sorted_ids``.

    Shared by the partition-batch triangle routing and the top-down
    candidate compaction — every value must be present in ``sorted_ids``
    (NS(P) contains every edge of a triangle assigned to P; a candidate
    contains every edge of a kept triangle).
    """
    return np.searchsorted(sorted_ids, values).astype(Int)


def compact_edge_list(edges: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Relabel an edge list's vertices to dense local ids.

    Returns ``(local_edges, verts)`` with ``verts[local_id]`` the original
    vertex id.  The relabeling is monotone, so a canonical (u < v,
    lex-sorted) input stays canonical and the row index of every edge is
    preserved — the property the partition-batch engine relies on to map
    local edge ids back to parent edge ids.
    """
    if len(edges) == 0:
        return np.zeros((0, 2), Int), np.zeros(0, Int)
    verts = np.unique(edges.reshape(-1))
    local = np.searchsorted(verts, edges)
    return local.astype(Int), verts.astype(Int)


def incident_vertices(edges: np.ndarray) -> np.ndarray:
    """Sorted unique vertices touched by an edge list."""
    if len(edges) == 0:
        return np.zeros(0, dtype=Int)
    return np.unique(edges.reshape(-1)).astype(Int)


# ---------------------------------------------------------------------------
# Graph statistics for the paper's Table 6 comparison
# (examples/truss_analysis.py).
# ---------------------------------------------------------------------------

def clustering_coefficient(n: int, edges: np.ndarray) -> float:
    """Global clustering coefficient: 3 * #triangles / #wedges."""
    g = build_graph(n, edges)
    if g.m == 0:
        return 0.0
    from repro.core import support as _support  # lazy to avoid jax import here

    sup = np.asarray(_support.edge_support_np(g))
    tri3 = sup.sum()  # counts each triangle 3x
    d = g.deg.astype(np.int64)
    wedges = (d * (d - 1) // 2).sum()
    return float(tri3) / float(wedges) if wedges else 0.0
