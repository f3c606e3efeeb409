"""Bottom-up I/O-efficient truss decomposition (paper Section 5, Alg 3-5).

Two stages, adapted to the TPU memory hierarchy (DESIGN.md §2, §8):

Stage 1 — ``lower_bounding`` (Algorithm 3): partition the current graph's
vertices into parts whose neighborhood subgraphs fit the working-set budget;
decompose each NS(P) *locally*; Lemma 1 makes the local trussness a global
lower bound φ(e).  Internal edges are removed after each round and emitted
to ``G_new``; the loop repeats on the shrinking remainder until no edges are
left.

Stage 2 — ``bottom_up_decompose`` (Algorithm 4 + Procedure 5): for ascending
k: extract the candidate subgraph H = NS(U_k), U_k = endpoints of edges with
φ(e) <= k; peel H at threshold (k-2) — the removed internal edges are exactly
Φ_k (Theorem 2); delete them from G_new and continue.  Empty classes are
skipped by jumping k straight to ``min lb`` over the remaining edges.

Engines (DESIGN.md §8):

* ``engine="batched"`` (default) — one :class:`partition.PartitionBatch` per
  round: every NS(P) compacted to local ids, parts grouped into pow4 size
  classes, lane-packed and padded to static shapes, every bucket decomposed
  in ONE device call (``peel.peel_classes_batched``, one compile per bucket
  shape); the
  working graph shrinks via ``Graph.remove_edges`` incremental maintenance
  instead of a per-round rebuild.  Rounds are **double-buffered**
  (DESIGN.md §9): a round's internal-edge removal is known at batch-build
  time, so the ``_partition_rounds`` producer advances the working graph
  and builds round r + 1 on the host while the device still peels round r
  (non-blocking dispatch, results consumed one round late).  Stage-2
  candidates are compacted and peeled on pow4-padded shapes
  (``peel.local_threshold_peel``), so consecutive k values share one
  compiled kernel — and are **pipelined** the same way the stage-1 rounds
  are (DESIGN.md §11): level k+1's candidate is pre-built on the host from
  the pre-result masks (a superset U′ ⊇ U_{k+1}, provably sound) while the
  device peels level k; the edges level k removes are killed at use time
  via the peel's ``alive0`` mask (``OocStats.stage2_overlapped``).
* ``engine="perpart"`` — the seed path (full ``build_graph`` per round, one
  host triangle enumeration and one freshly-shaped device peel per part);
  kept as the before/after benchmark baseline (BENCH_ooc.json).

Deviation from the paper (documented in DESIGN.md §7): Algorithm 3 Step 8
flags internal zero-support edges as Φ_2 in *every* round, but from round 2
onward local supports are measured against the already-shrunk working graph,
which can under-count (a crossing edge whose triangle partner was emitted to
G_new in an earlier round shows support 0 yet can have trussness 3).  We flag
Φ_2 exactly in round 1 only (supports there are exact w.r.t. G), and start
stage 2 at k = 2 so any remaining 2-class edges are recovered exactly —
stage-2 candidate supports are always exact w.r.t. G_new.
"""

from __future__ import annotations

import dataclasses
import hashlib
import inspect
import re
import time
import warnings
from typing import Callable, Dict, Iterator, List, Optional, Tuple, Union

import jax.numpy as jnp
import numpy as np

from repro.checkpoint import manager as ckpt
from repro.core import faults
from repro.core import graph as glib
from repro.core import partition as plib
from repro.core.store import GraphStore
from repro.core.peel import (_mesh_axes, _mesh_size, local_threshold_peel,
                             peel_classes, peel_classes_batched,
                             peel_threshold)
from repro.core.spans import span
from repro.core import support as sup_lib
from repro.core.support import (list_triangles, list_triangles_np,
                                support_from_triangle_list)

# The degradation ladder's floor for the per-round working-set budget:
# halving below this cannot meaningfully shrink a dispatch (a single lane
# is already ~this size), so at the floor the failure propagates.
_MIN_ROUND_BUDGET = 64


class _RestartRounds(Exception):
    """Internal control flow of the stage-1 degradation ladder: unwind the
    round generator and restart it from the journaled host state with a
    smaller working-set budget (smaller parts => smaller dispatches).  All
    completed rounds' folds are idempotent scatters, so the restart loses
    at most the failed round's device work."""

    def __init__(self, budget: int):
        super().__init__(f"restart partition rounds at budget={budget}")
        self.budget = budget


@dataclasses.dataclass
class _Engine:
    """Mutable dispatch configuration shared by a run's device launches.

    The degradation ladder rewrites it in place (``mesh = None`` drops the
    run to single-device), so every later dispatch — including stage 2 —
    inherits the degraded routing without re-threading arguments."""

    mesh: object = None
    mesh_axis: object = "data"   # one axis name or a (lane, tri) tuple (§13)
    kernel: str = "auto"         # per-lane peel engine (pallas | xla | auto)

    @property
    def lane_axis(self) -> str:
        ax = self.mesh_axis
        return ax if isinstance(ax, str) else ax[0]

    @property
    def n_dev(self) -> int:
        """Lane-axis size — the multiple the bucket packers pad lanes to."""
        if self.mesh is None:
            return 1
        return int(self.mesh.shape[self.lane_axis])

    @property
    def devices(self) -> int:
        """Total devices spanned: the product over every named mesh axis."""
        return _mesh_devices(self.mesh, self.mesh_axis)


def _mesh_devices(mesh, mesh_axis) -> int:
    """Total devices a (mesh, mesh_axis) pair spans: the product over the
    named axes.  1 without a mesh; for a single axis name this equals the
    axis size, keeping single-axis checkpoint run keys unchanged."""
    if mesh is None:
        return 1
    return _mesh_size(mesh, _mesh_axes(mesh_axis))


def _accepts_round(fn) -> bool:
    """Whether a user partitioner asks for (graph, budget, round_idx).

    Only a third *required* positional parameter (or ``*args``) opts in:
    a defaulted third parameter (``def p(g, b, strict=True)``) keeps the
    legacy 2-arg call so pre-existing config kwargs are never hijacked by
    the round index.
    """
    try:
        params = list(inspect.signature(fn).parameters.values())
    except (TypeError, ValueError):      # no introspectable signature
        return False
    required = sum(
        p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD)
        and p.default is p.empty
        for p in params)
    return (required >= 3
            or any(p.kind == p.VAR_POSITIONAL for p in params))


class _AdaptiveLocality:
    """Stateful wrapper feeding observed triangle locality back into the
    zoned partitioner (DESIGN.md §11): ``_partition_rounds`` calls
    :meth:`observe` with each built batch, and the next round's zone cap
    scales with the capture fraction the previous round actually achieved
    (``partition._zone_mult``) instead of the fixed 4x constant."""

    def __init__(self, fn):
        self._fn = fn
        self.prev_locality: float | None = None

    def __call__(self, g, budget, round_idx):
        return self._fn(g, budget, prev_locality=self.prev_locality)

    def observe(self, batch: "plib.PartitionBatch") -> None:
        if batch.tri_total:
            self.prev_locality = batch.tri_locality


def _zone_state(part_fn):
    """Journal payload of the partitioner's adaptive zone state.

    The locality partitioner (:class:`_AdaptiveLocality`) carries one
    float of cross-round feedback — the previous round's observed triangle
    locality, which sizes the next round's zone cap.  A journal snapshot
    that omits it makes a resumed run re-plan its rounds from the cold
    default instead of reproducing the original sequence (φ stays exact
    either way, but perf and the round/locality counters diverge —
    DESIGN.md §16).  Stateless partitioners snapshot as None.
    """
    state = getattr(part_fn, "prev_locality", None)
    return None if state is None else float(state)


def _restore_zone_state(part_fn, state) -> None:
    """Reinstall a journaled :func:`_zone_state` into the partitioner."""
    if state is not None and hasattr(part_fn, "prev_locality"):
        part_fn.prev_locality = float(state)


def _resolve_partitioner(partitioner, seed: int = 0):
    """Normalize to fn(graph, budget, round_idx) -> parts.

    The randomized partitioner is re-seeded every round (Chu–Cheng's
    guarantee that crossing edges eventually co-locate holds w.h.p. only
    under re-randomization); ``seed`` offsets the per-round reseed so the
    drivers' ``partitioner_seed=`` reaches ``random_partition`` (with the
    default 0 the schedule is the historical ``seed=round_idx`` one).
    Deterministic partitioners ignore both.  User callables with a third
    required positional parameter (or ``*args``) receive the round index
    too, so custom partitioners can vary per round the way the built-in
    "random" reseed does; 2-arg callables — including ones with defaulted
    config parameters — keep the legacy (graph, budget) call.

    The built-in "locality" partitioner resolves to a stateful
    :class:`_AdaptiveLocality` whose ``observe`` hook the round generator
    drives; resolving fresh per run keeps the feedback run-local.
    """
    if callable(partitioner):
        if _accepts_round(partitioner):
            return lambda g, b, r: partitioner(g, b, r)
        return lambda g, b, r: partitioner(g, b)
    fn = plib.PARTITIONERS[partitioner]
    if partitioner == "random":
        return lambda g, b, r: fn(g, b, seed=seed + r)
    if partitioner == "locality":
        return _AdaptiveLocality(fn)
    return lambda g, b, r: fn(g, b)


@dataclasses.dataclass
class OocStats:
    """Work counters of one out-of-core run (mirrors ``PeelStats``).

    ``compiles`` counts distinct padded shapes this run traced — the cost
    the bucket padding exists to bound (the seed per-part path compiled once
    per part shape).  The jit cache is process-global, so the counter is an
    upper bound on actual XLA work.  ``padding_waste`` is the fraction of
    materialized lane slots that held no real edge.
    """

    rounds: int = 0           # partition rounds (the paper's O(m/M) scans)
    scans: int = 0            # NS/candidate extractions (I/O-scan analogue)
    batches: int = 0          # device launches (one per bucket per round)
    compiles: int = 0         # distinct padded shapes traced this run
    parts: int = 0            # NS parts processed
    max_part_edges: int = 0   # largest NS working set seen (budget check)
    real_edges: int = 0       # Σ real edge slots across all batches
    padded_slots: int = 0     # Σ materialized lane slots across all batches
    tri_total: int = 0        # triangles enumerated across partition rounds
    tri_assigned: int = 0     # of those, captured inside some part
    ns_sweeps: int = 0        # whole-graph NS edge-list sweeps (1 per batch)
    overlapped: int = 0       # rounds whose device peel overlapped the
    #                           host build of the NEXT round (pipeline depth)
    stage2_overlapped: int = 0  # stage-2 levels whose candidate extraction
    #                           + compaction was pre-built on the host while
    #                           the previous level's peel still ran on the
    #                           device (DESIGN.md §11)
    tri_est: int = 0          # wedge-based triangle estimates summed over
    #                           partition rounds (the cost model's
    #                           prediction; compare tri_total)
    tri_rescans_avoided: int = 0  # rounds whose triangle list was filtered
    #                           from the previous round's instead of
    #                           re-enumerated (the O(m^1.5) scan replaced
    #                           by an O(T) filter; at most rounds - 1)
    devices: int = 1          # mesh devices the sharded dispatch spans
    sharded_rounds: int = 0   # device dispatches (stage-1 partition rounds
    #                           + per-k candidate peels) routed through
    #                           shard_map across the mesh (DESIGN.md §10)
    retries: int = 0          # failed dispatches re-driven by the retry
    #                           ladder (lane splits + degraded re-runs)
    degraded: int = 0         # engine degradations taken: mesh drops +
    #                           working-set budget halvings (DESIGN.md §12)
    checkpoints: int = 0      # journal snapshots written this run
    resumed_round: int = -1   # round/level index of the snapshot this run
    #                           resumed from (-1: started fresh)
    chunk_reads: int = 0      # graph-store chunks read back (DESIGN.md §15)
    chunk_writes: int = 0     # graph-store chunks written (spilled)
    bytes_spilled: int = 0    # bytes written to the chunked store; chunks
    #                           aliased by the chunk-wise remove_edges cost 0
    prefetch_hits: int = 0    # chunk requests served by the background
    #                           prefetch thread (scheduled before requested)
    prefetch_misses: int = 0  # chunk requests that fell back to a
    #                           synchronous disk read at request time
    tri_spill_rows: int = 0   # largest triangle list (rows) spilled to the
    #                           store across partition rounds
    tri_reload_peak_rows: int = 0  # peak triangle rows resident at once
    #                           while CONSUMING a spilled list (chunk-
    #                           streamed: must stay far below
    #                           tri_spill_rows, DESIGN.md §16)
    edits_applied: int = 0    # maintenance edits applied (maintain.py)
    maintain_levels: int = 0  # per-level region peels run by maintenance
    affected_edges: int = 0   # Σ candidate edges over maintenance levels
    pallas_lanes: int = 0     # lanes dispatched to the fused Pallas kernel
    #                           (a retried dispatch counts again)
    xla_lanes: int = 0        # lanes dispatched to the XLA frontier engines
    pallas_max_lanes: int = 0  # widest lane batch one fused-kernel call took
    lane_shards: int = 0      # distinct lane slices the devices held in the
    #                           first bucket split over a mesh (its output
    #                           sharding; 1 = every device held every lane)
    lanes_per_shard: int = 0  # lane rows each device held in that bucket
    h2d_bytes: int = 0        # graph bytes the dispatches copied host to
    #                           device (their truss.upload spans)
    mesh_lanes: int = 0       # lanes of the buckets split over a mesh, at
    #                           the device multiple they were dispatched at
    mesh_pad_lanes: int = 0   # of those, lanes holding no live edge: the
    #                           dead lanes the split added (DESIGN.md §10)

    @property
    def prefetch_hit_rate(self) -> float:
        """Fraction of chunk requests the prefetcher hid the latency of —
        the overlap quality metric the ooc-disk smoke gates on (≥ 0.5)."""
        total = self.prefetch_hits + self.prefetch_misses
        return self.prefetch_hits / total if total else 1.0

    @property
    def tri_routes(self) -> int:
        """Whole-graph triangle enumerations routed to parts — an alias:
        ``build_partition_batch`` does exactly one triangle routing per NS
        sweep, so the two whole-graph scan counters move in lockstep."""
        return self.ns_sweeps

    @property
    def padding_waste(self) -> float:
        if not self.padded_slots:
            return 0.0
        return 1.0 - self.real_edges / self.padded_slots

    @property
    def tri_locality(self) -> float:
        """Fraction of enumerated triangles captured inside a part — the
        objective the locality-aware partitioner maximizes (DESIGN.md §9)."""
        return self.tri_assigned / self.tri_total if self.tri_total else 1.0

    @property
    def tri_est_error(self) -> float:
        """Relative error of the partitioner's wedge-based triangle-volume
        estimate vs the actual per-round enumerations (DESIGN.md §11).
        The cost model only steers locality — a wildly wrong estimate can
        cost rounds, never correctness — but the error is surfaced so the
        estimator's drift on new graph shapes is visible in benchmarks.
        The denominator floors at 1 so triangle-free runs still expose an
        over-predicting estimator instead of reporting it as exact."""
        return abs(self.tri_est - self.tri_total) / max(self.tri_total, 1)

    def absorb_batch(self, batch: "plib.PartitionBatch") -> None:
        self.parts += batch.n_parts
        self.scans += batch.n_parts
        self.batches += len(batch.buckets)
        self.real_edges += batch.real_edges
        self.padded_slots += batch.padded_slots
        self.max_part_edges = max(self.max_part_edges, batch.max_part_edges)
        self.tri_total += batch.tri_total
        self.tri_assigned += batch.tri_assigned
        self.tri_est += batch.tri_est
        self.ns_sweeps += 1        # build_partition_batch does exactly one
        #                            whole-graph NS sweep + triangle routing

    def count_lanes(self, handle) -> None:
        """Credit one dispatch's lanes to the engine it took
        (``PendingPeel.engine``); host short-circuits count as neither.
        The first mesh-split bucket also records its lane split, and
        every one its lanes and dead lanes.  The dispatch's uploaded bytes
        go to ``h2d_bytes``."""
        self.h2d_bytes += handle.h2d_bytes
        self.mesh_lanes += handle.mesh_lanes
        self.mesh_pad_lanes += handle.pad_lanes
        if handle.engine == "pallas":
            self.pallas_lanes += handle.lanes
            self.pallas_max_lanes = max(self.pallas_max_lanes, handle.lanes)
        elif handle.engine == "xla":
            self.xla_lanes += handle.lanes
        if handle.lane_split is not None and not self.lane_shards:
            self.lane_shards, self.lanes_per_shard = handle.lane_split

    def as_dict(self) -> Dict[str, int]:
        """JSON-safe counter snapshot (the journal's metadata form)."""
        return {f.name: int(getattr(self, f.name))
                for f in dataclasses.fields(self)}

    @classmethod
    def from_dict(cls, d: Dict[str, int]) -> "OocStats":
        """Rebuild from :meth:`as_dict` output; unknown keys (snapshots
        written by a newer layout) are ignored."""
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: int(v) for k, v in d.items() if k in known})


@dataclasses.dataclass
class LowerBoundResult:
    edges: np.ndarray        # canonical edge list of the original graph
    phi: np.ndarray          # trussness; filled with 2 for the exact Phi_2
    lb: np.ndarray           # lower bound phi(e) for G_new edges (>=2)
    in_gnew: np.ndarray      # bool mask: edge still undecided (in G_new)
    rounds: int              # partition rounds (the paper's O(m/M) iterations)
    scans: int               # NS extractions (I/O-scan analogue)
    max_part_edges: int      # largest NS working set seen (budget check)
    stats: Optional[OocStats] = None


def _run_key(driver: str, n: int, edges: np.ndarray, budget,
             partitioner, partitioner_seed: int, **extras) -> str:
    """Digest binding a journal to one run configuration (DESIGN.md §12).

    Covers the driver, the canonical edge bytes and every parameter that
    changes the decomposition's trajectory, so ``resume=True`` can never
    silently continue a snapshot from a different graph or configuration.
    Callable partitioners hash by name — the best identity available short
    of bytecode hashing.
    """
    pname = (partitioner if isinstance(partitioner, str)
             else getattr(partitioner, "__name__", "custom"))
    h = hashlib.sha256()
    desc = "|".join(
        [driver, f"n={n}", f"budget={budget}", f"part={pname}",
         f"seed={partitioner_seed}"]
        + [f"{k}={v}" for k, v in sorted(extras.items())])
    h.update(desc.encode())
    h.update(np.ascontiguousarray(edges, dtype=np.int64).tobytes())
    return h.hexdigest()[:16]


def _parse_every(every: Union[int, str]) -> Tuple[str, float]:
    """Normalize a ``checkpoint_every`` knob to ``(mode, value)``.

    Integers are the historical event-count gate (``("events", k)``, floored
    at 1).  Strings are wall-clock budgets — ``"30s"``, ``"500ms"``,
    ``"5m"``, ``"1h"`` — yielding ``("time", seconds)``: long decompositions
    bound *time at risk* rather than rounds, since round durations vary by
    orders of magnitude across the shrink (DESIGN.md §12).
    """
    if isinstance(every, str):
        match = re.fullmatch(r"\s*(\d+(?:\.\d+)?)\s*(ms|s|m|h)\s*", every)
        if match is None:
            raise ValueError(
                f"checkpoint_every={every!r}: expected an event count or a "
                f"duration like '30s', '500ms', '5m', '1h'")
        secs = float(match.group(1)) * {"ms": 1e-3, "s": 1.0, "m": 60.0,
                                        "h": 3600.0}[match.group(2)]
        if secs <= 0:
            raise ValueError(
                f"checkpoint_every={every!r}: duration must be positive")
        return "time", secs
    return "events", float(max(1, int(every)))


class RoundJournal:
    """Round-granular snapshot journal over ``checkpoint.manager`` (§12).

    One journal serves one decomposition run.  Each snapshot is a flat
    ``{name: array}`` tree of host-side round state plus metadata
    ``{stage, index, run_key, stats, **extra}``; writes go through
    :func:`checkpoint.manager.save`'s atomic tmp+rename path, so a crash
    mid-write can never corrupt the newest intact snapshot.  Steps form a
    monotone sequence continued across resumes (the constructor seeds the
    counter from the directory), and ``run_key`` is verified at load so a
    ``checkpoint_dir`` can never silently resume a different run.

    ``every`` gates writes by event count (int) or wall clock (a duration
    string, :func:`_parse_every`); ``clock`` injects the monotonic time
    source so time-gated tests stay deterministic.  ``store`` ties the
    journal to the run's graph store: each snapshot first absorbs the
    store's I/O counters into ``stats`` (so a resumed run's counters
    include pre-crash I/O), and the snapshot payload is reserved against
    the store's :class:`~repro.core.store.IoAccount` while it serializes —
    checkpoint I/O and chunk I/O share one budget (DESIGN.md §15).
    """

    def __init__(self, ckpt_dir: str, run_key: str, *,
                 every: Union[int, str] = 1, keep: int = 3,
                 clock: Callable[[], float] = time.monotonic,
                 store: Optional[GraphStore] = None):
        self.ckpt_dir = ckpt_dir
        self.run_key = run_key
        self.mode, self.every = _parse_every(every)
        self.keep = keep
        self.store = store
        self._clock = clock
        self._last_write = clock()
        self.seq = int(ckpt.latest_step(ckpt_dir) or 0)
        self._events = 0

    def _due(self) -> bool:
        if self.mode == "time":
            return self._clock() - self._last_write >= self.every
        return self._events % int(self.every) == 0

    def record(self, stage: str, index: int, arrays: Dict[str, np.ndarray],
               stats: OocStats, **extra) -> bool:
        """Journal one completed unit of work (a partition round or class
        level); writes when the ``every`` gate (events or wall clock) is
        due.  Returns whether a snapshot was written.  The write is
        synchronous — the device pipeline is already overlapped with host
        work, and an async journal would leave a window where "completed"
        rounds are lost on crash."""
        self._events += 1
        if not self._due():
            return False
        self.seq += 1
        stats.checkpoints += 1
        if self.store is not None:
            self.store.absorb_into(stats)
        meta = {"stage": stage, "index": int(index),
                "run_key": self.run_key, "stats": stats.as_dict(), **extra}
        # narrow i64 -> i32 on the way out (phi/lb/sup are all < 2^31; the
        # restore paths cast back), halving the dominant snapshot cost
        arrays = {k: (np.asarray(v).astype(np.int32)
                      if np.asarray(v).dtype == np.int64 else np.asarray(v))
                  for k, v in arrays.items()}
        account = getattr(self.store, "io_account", None)
        payload = sum(int(a.nbytes) for a in arrays.values())
        if account is not None:
            with account.hold(payload, "checkpoint"):
                ckpt.save(self.ckpt_dir, self.seq, dict(arrays),
                          metadata=meta, keep=self.keep)
        else:
            ckpt.save(self.ckpt_dir, self.seq, dict(arrays), metadata=meta,
                      keep=self.keep)
        if self.mode == "time":
            self._last_write = self._clock()
        return True

    def load_latest(self):
        """``(arrays, meta)`` of the newest intact snapshot, or ``None``
        when the directory holds no usable one (empty, or every snapshot
        corrupt — the run then starts fresh, with a warning in the corrupt
        case).  A ``run_key`` mismatch raises: resuming a different run's
        journal is a caller error, not a recoverable state."""
        try:
            tree, meta = ckpt.restore(self.ckpt_dir)
        except FileNotFoundError:
            return None
        except ckpt.CheckpointCorruptionError as e:
            warnings.warn(
                f"no intact snapshot under {self.ckpt_dir!r} ({e}); "
                f"starting the run from scratch", stacklevel=2)
            return None
        if meta.get("run_key") != self.run_key:
            raise ValueError(
                f"checkpoint_dir {self.ckpt_dir!r} holds a journal for a "
                f"different run (run_key {meta.get('run_key')!r} != "
                f"{self.run_key!r}); refusing to resume")
        return tree, meta


def _local_truss(sub_edges: np.ndarray, n: int) -> np.ndarray:
    """Trussness of every edge of the subgraph (seed per-part local peel).

    One ``build_graph`` over the FULL vertex space, one host triangle
    enumeration and one dynamically-shaped device peel per call — the
    per-part cost model the batched engine replaces; kept as the benchmark
    baseline and as a second implementation for the batch-padding tests.
    """
    g = glib.build_graph(n, sub_edges)
    if g.m == 0:
        return np.zeros(0, np.int64)
    tris = list_triangles_np(g)
    sup = support_from_triangle_list(tris, g.m).astype(np.int32)
    if len(tris) == 0:
        tris = np.full((1, 3), g.m, np.int32)
    phi, _ = peel_classes(jnp.asarray(sup), jnp.asarray(tris), jnp.ones(g.m, bool))
    return np.asarray(phi).astype(np.int64)


def lower_bounding(
    n: int,
    edges: np.ndarray,
    budget: int,
    partitioner: str | Callable = "sequential",
    engine: str = "batched",
    *,
    partitioner_seed: int = 0,
    mesh=None,
    mesh_axis="data",
    kernel: str = "auto",
    journal: Optional[RoundJournal] = None,
    restored=None,
    max_retries: int = 2,
    engine_state: Optional[_Engine] = None,
    store: Optional[GraphStore] = None,
) -> LowerBoundResult:
    """Algorithm 3: per-edge lower bounds + exact round-1 Phi_2.

    With a ``mesh``, every round's bucket peels span the mesh axis
    (DESIGN.md §10); requires the batched engine.  ``mesh_axis`` may be a
    single axis name or a ``(lane, tri)`` tuple for multi-axis meshes
    (DESIGN.md §13); ``kernel`` routes each lane's peel engine
    (``"pallas" | "xla" | "auto"``, forwarded to
    ``peel.peel_classes_batched``).

    ``journal`` / ``restored`` / ``max_retries`` are the resilience hooks
    (DESIGN.md §12): a :class:`RoundJournal` snapshots the host-side fold
    state after each completed round, ``restored`` (an ``(arrays, meta)``
    pair from :meth:`RoundJournal.load_latest` at stage ``"lb"``) resumes
    from it, and ``max_retries`` bounds the lane-split retries a failed
    dispatch gets before the engine degrades.  ``engine_state`` shares one
    mutable :class:`_Engine` with the caller so a mesh drop here carries
    into stage 2.  Both engines compute identical bounds, but only the
    batched engine journals — its per-round state lives in flat host
    arrays; the per-part seed path is the benchmark baseline.

    ``store`` (batched engine only) routes the round loop's working graph
    through a :class:`~repro.core.store.GraphStore` — with a
    ``ChunkedDiskStore`` the graph lives on disk between rounds and the
    store's prefetch thread overlaps the chunk reads with the device peel
    (DESIGN.md §15); φ is bit-identical either way.
    """
    part_fn = _resolve_partitioner(partitioner, seed=partitioner_seed)
    edges = glib.canonical_edges(edges, n)
    if engine == "perpart":
        if mesh is not None:
            raise ValueError("mesh= requires the batched engine")
        if journal is not None or restored is not None:
            raise ValueError(
                "checkpointing requires the batched engine "
                "(engine='perpart' is the uninstrumented seed baseline)")
        if store is not None:
            raise ValueError(
                "store= requires the batched engine "
                "(engine='perpart' is the uninstrumented seed baseline)")
        return _lower_bounding_perpart(n, edges, budget, part_fn)
    if engine != "batched":
        raise ValueError(f"unknown engine {engine!r}")
    return _lower_bounding_batched(n, edges, budget, part_fn,
                                   mesh=mesh, mesh_axis=mesh_axis,
                                   kernel=kernel,
                                   journal=journal, restored=restored,
                                   max_retries=max_retries,
                                   engine_state=engine_state, store=store)


def _partition_rounds(
    n: int, edges: np.ndarray, budget: int, part_fn, stats: OocStats,
    *, with_incidence: bool = True, lane_multiple: int = 1,
    start_ids: Optional[np.ndarray] = None,
    store: Optional[GraphStore] = None,
) -> Iterator[Tuple[int, "plib.PartitionBatch", np.ndarray, int]]:
    """Producer side of the double-buffered round pipeline (DESIGN.md §9).

    Yields ``(round_idx, batch, cur_ids, cur_budget, zone_state)`` per
    partition round, with ``cur_ids`` mapping the batch's current-graph
    edge ids to original edge ids, ``cur_budget`` the working-set budget
    the round was built at (the value a resumed run must restart from,
    since the stall fallback below mutates it), and ``zone_state`` the
    locality partitioner's adaptive state as of this round's feedback
    (``None`` for stateless partitioners).  Which edges a round removes is known at batch-build
    time (a round's internal edges leave the working graph regardless of
    their peel results), so the generator applies ``Graph.remove_edges``
    and repartitions immediately — the consumer can keep the device busy
    with round r while this code builds round r + 1 on the host.

    ``start_ids`` restarts the generator from a working graph that is a
    subset of ``edges`` (the resume and budget-degrade paths, DESIGN.md
    §12); the default is the full edge list.  Round numbering continues
    from ``stats.rounds``, which a resumed run restores first.

    A round in which no edge became internal (a deterministic-partitioner
    stall; the paper's remedy is the randomized re-partition) doubles the
    working-set budget and yields nothing: with no internal edges a peel
    could not contribute any bound.

    Triangle lists are **incremental** across rounds: the full working
    graph is enumerated once (round 1), and every later round filters the
    previous list against the surviving edges — a triangle of the shrunken
    graph is exactly a triangle of the previous graph with all three edges
    alive — and remaps edge ids to the compacted numbering
    ``Graph.remove_edges`` produces.  The O(m^1.5) wedge enumeration per
    round becomes an O(T) mask (``OocStats.tri_rescans_avoided``); zoned
    covers pay one full scan up front instead of one zone scan per round,
    and ``build_partition_batch`` re-scopes the passed list so
    ``tri_total`` / ``tri_locality`` semantics are unchanged.

    With a ``store``, the working graph and the incremental triangle list
    are **spilled between rounds** (DESIGN.md §15): after each
    ``remove_edges`` the successor graph spills chunk-wise (untouched
    chunks alias the predecessor's files), the predecessor's chunks are
    released, and the next round's arrays are prefetched before the yield
    — so the background reads overlap the consumer's device peel exactly
    like the batch pipeline overlaps the host build.
    """
    if start_ids is None:
        g = glib.build_graph(n, edges, store=store)
        cur_ids = np.arange(g.m, dtype=np.int64)
    else:
        cur_ids = np.asarray(start_ids, dtype=np.int64)
        g = glib.build_graph(n, edges[cur_ids], store=store)
    if store is not None:
        g.spill()
        g.prefetch()
    cur_budget = budget
    tris_cur = None      # full triangle list of g, g-local edge ids
    tris_key = None      # store key the spilled triangle list lives under
    # shape ladder (sharded packing only, DESIGN.md §13): the shapes this
    # run has already compiled the shard_map peel for; a round that fits
    # an entry reuses it verbatim (compile-cache hit), one that doesn't
    # packs naturally and contributes its shape — on a mesh every
    # recompile is a pod-wide stall, and the dead padding a reused entry
    # adds costs each shard only 1/n_dev of its slots
    ladder: list = []
    while g.m:
        stats.rounds += 1
        # the round's host work; the span closes before the yield, so
        # the consumer's device work between rounds is not counted in it
        with span("round_build", round=stats.rounds) as sp:
            # the host-side "between rounds" fault site: the natural place for
            # the crash/kill injections the resume tests drive (DESIGN.md §12)
            faults.check(faults.PARTITIONER, stage=1, round=stats.rounds,
                         budget=cur_budget)
            parts = part_fn(g, cur_budget, stats.rounds)
            if not parts:
                break
            spilled_round = tris_cur is None and tris_key is not None
            if spilled_round:
                # chunk-stream the spilled list through the batch builder
                # (DESIGN.md §16): the builder retains only the rows assigned
                # into some part, so the host's peak triangle working set is
                # the round's bucket payload plus one store chunk — never the
                # whole 3·T list the old whole-array reload materialized
                stats.tri_rescans_avoided += 1
                tris_in = sup_lib.iter_triangle_chunks(store, tris_key)
            elif tris_cur is None:
                tris_cur = np.asarray(list_triangles(g), np.int64).reshape(-1, 3)
                tris_in = tris_cur
            else:
                stats.tri_rescans_avoided += 1
                tris_in = tris_cur
            batch = plib.build_partition_batch(
                g, parts, with_incidence=with_incidence,
                lane_multiple=lane_multiple, tris=tris_in,
                shape_ladder=ladder if lane_multiple > 1 else None)
            sp.count(parts=batch.n_parts,
                     lanes=sum(b.n_lanes for b in batch.buckets),
                     padded_slots=batch.padded_slots,
                     real_edges=batch.real_edges)
            if spilled_round:
                stats.tri_reload_peak_rows = max(stats.tri_reload_peak_rows,
                                                 batch.tri_peak_rows)
            if lane_multiple > 1:
                for b in batch.buckets:
                    shape = (b.cap_e, b.cap_t, b.n_lanes)
                    if shape not in ladder:
                        ladder.append(shape)
            stats.absorb_batch(batch)
            observe = getattr(part_fn, "observe", None)
            if observe is not None:
                observe(batch)     # adaptive zone sizing feedback (§11)
            removed = np.zeros(g.m, dtype=bool)
            for bucket in batch.buckets:
                removed[bucket.edge_ids[bucket.internal]] = True
            if not removed.any():
                # the batch is discarded un-launched; keep ``batches`` meaning
                # "device launches"
                stats.batches -= len(batch.buckets)
                cur_budget *= 2
                continue
            ids_snapshot = cur_ids
            cur_ids = cur_ids[~removed]
            g_prev, g = g, g.remove_edges(removed)
            remap = np.cumsum(~removed) - 1          # old id -> compacted id
            if tris_cur is not None and len(tris_cur):
                keep = ~removed[tris_cur].any(axis=1)
                tris_cur = remap[tris_cur[keep]]
            if store is not None:
                # spill the successor BEFORE releasing the predecessor: the
                # chunk-wise filter aliases untouched chunk files, and the
                # refcounts must see them registered before the old graph's
                # release decrements them
                g.spill()
                g_prev.release()
                if spilled_round:
                    # stream-filter the old spilled list into a fresh key: one
                    # chunk resident at a time, and the writer must not clobber
                    # the key it is still reading from, so the key alternates
                    # per round and the predecessor is released after close
                    new_key = store.graph_key() + "/tris"
                    with sup_lib.stream_spill_triangles(store, new_key) as w:
                        for chunk in sup_lib.iter_triangle_chunks(store,
                                                                  tris_key):
                            stats.tri_reload_peak_rows = max(
                                stats.tri_reload_peak_rows, int(len(chunk)))
                            keep = ~removed[chunk].any(axis=1)
                            w.append(remap[chunk[keep]])
                        spilled_rows = w.rows
                    if new_key != tris_key:
                        store.release(tris_key)
                    tris_key = new_key
                else:
                    if tris_key is None:
                        tris_key = store.graph_key() + "/tris"
                    sup_lib.spill_triangles(store, tris_key, tris_cur)
                    spilled_rows = len(tris_cur)
                stats.tri_spill_rows = max(stats.tri_spill_rows,
                                           int(spilled_rows))
                tris_cur = None
                # warm the next round's reads while the consumer peels this one
                g.prefetch()
                store.prefetch([tris_key])
        # zone state as of THIS round's observe — the value the next
        # round's planning reads, hence the one a resume from this round's
        # snapshot must restore.  Captured here because the double-buffered
        # consumer journals one round late, by which time the producer has
        # already observed the following round's batch.
        yield (stats.rounds, batch, ids_snapshot, cur_budget,
               _zone_state(part_fn))


def _retry_stage1_round(eng: _Engine, stats: OocStats, shape_cache,
                        round_idx: int, batch, ids, fold_bucket, exc,
                        cur_budget: int, max_retries: int) -> None:
    """Blocking retry ladder for a failed stage-1 round (DESIGN.md §12).

    The failed dispatch's donated device buffers are gone — a poisoned
    :class:`~repro.core.peel.PendingPeel` can never be re-finalized — but
    the :class:`~repro.core.partition.PartBucket` host arrays survive the
    donation, so the round is rebuilt by re-dispatching them.  The ladder,
    engaged only for retryable failures (:func:`faults.is_retryable`):

    1. lane-split retries — re-dispatch each bucket as
       ``split_bucket_lanes`` sub-buckets (split 2, then 4, … up to
       ``max_retries`` doublings), halving the device-resident footprint
       per launch each time;
    2. mesh drop — retire the sharded dispatch for the rest of the run
       (``eng.mesh = None``; per-shard overheads are gone and the smallest
       single-device launch is strictly smaller than a shard's slice);
    3. budget halving — raise :class:`_RestartRounds` so the driver
       restarts the round loop from the journaled host state with half the
       working-set budget (smaller parts => smaller buckets), down to
       ``_MIN_ROUND_BUDGET``; below the floor the failure propagates.

    Folds re-applied by a retry are idempotent (``lb`` is a running max,
    ``phi``/``in_gnew``/``alive`` are set-to-constant scatters), so a retry
    that failed halfway through folding simply re-folds everything.
    """
    split = 1
    attempt = 0
    while True:
        if not faults.is_retryable(exc):
            raise exc
        stats.retries += 1
        attempt += 1
        if split < (1 << max_retries):
            split *= 2
        elif eng.mesh is not None:
            eng.mesh = None
            stats.degraded += 1
        else:
            if cur_budget <= _MIN_ROUND_BUDGET:
                raise exc
            stats.degraded += 1
            raise _RestartRounds(max(cur_budget // 2, _MIN_ROUND_BUDGET))
        try:
            with span("retry", stage="lb", attempt=attempt):
                for bi, bucket in enumerate(batch.buckets):
                    for si, sub in enumerate(
                            plib.split_bucket_lanes(bucket, split)):
                        # a sub-bucket whose lane count no longer divides
                        # the mesh axis runs single-device (the point is a
                        # smaller footprint, not preserving the routing)
                        mesh = (eng.mesh if eng.mesh is not None
                                and sub.n_lanes % eng.n_dev == 0 else None)
                        h = peel_classes_batched(
                            sub.sup, sub.tris, sub.indptr, sub.tids,
                            sub.alive, shape_cache=shape_cache,
                            blocking=False, mesh=mesh,
                            mesh_axis=eng.mesh_axis, kernel=eng.kernel,
                            fault_ctx={"stage": 1, "round": round_idx,
                                       "bucket": bi, "sub": si,
                                       "retry": split})
                        stats.compiles += int(h.new_compile)
                        stats.count_lanes(h)
                        stats.batches += 1
                        phi_b, _ = h.result()
                        fold_bucket(round_idx, sub, ids, np.asarray(phi_b))
            return
        except Exception as e:
            exc = e


def _lower_bounding_batched(n, edges, budget, part_fn, mesh=None,
                            mesh_axis="data", kernel: str = "auto",
                            journal: Optional[RoundJournal] = None,
                            restored=None, max_retries: int = 2,
                            engine_state: Optional[_Engine] = None,
                            store: Optional[GraphStore] = None,
                            ) -> LowerBoundResult:
    m = len(edges)
    phi = np.zeros(m, dtype=np.int64)
    lb = np.full(m, 2, dtype=np.int64)
    in_gnew = np.zeros(m, dtype=bool)
    alive = np.ones(m, dtype=bool)        # still in the working graph
    stats = OocStats()
    eng = engine_state if engine_state is not None else _Engine(
        mesh=mesh, mesh_axis=mesh_axis, kernel=kernel)
    stats.devices = eng.devices
    start_budget = budget
    if restored is not None:
        # resume from a journaled "lb" snapshot: the fold state is four
        # flat arrays over original edge ids; the working graph is
        # edges[alive] (fresh ranks are fine — phi is exact under any
        # partition sequence, DESIGN.md §12)
        tree, meta = restored
        phi = tree["phi"].astype(np.int64)
        lb = tree["lb"].astype(np.int64)
        in_gnew = tree["in_gnew"].astype(bool)
        alive = tree["alive"].astype(bool)
        stats = OocStats.from_dict(meta["stats"])
        stats.resumed_round = int(meta["index"])
        stats.devices = eng.devices
        start_budget = int(meta.get("cur_budget", budget))
        _restore_zone_state(part_fn, meta.get("zone_state"))
    shape_cache: set = set()

    def fold_bucket(round_idx, bucket, ids, phi_b):
        """Fold one bucket's peel results into lb/phi/in_gnew/alive.

        Internal edges live in exactly one part, so the flat scatters are
        collision-free; every scatter is idempotent (lb is a max, the rest
        set constants), which is what lets the retry ladder re-fold."""
        int_mask = bucket.internal
        ids_int = bucket.edge_ids[int_mask]          # current-graph ids
        phi_int = phi_b[int_mask].astype(np.int64)
        glob = ids[ids_int]
        np.maximum.at(lb, glob, phi_int)
        if round_idx == 1:
            # Exact Phi_2: internal support == global support in G here.
            is2 = phi_int == 2
            phi[glob[is2]] = 2
            in_gnew[glob[~is2]] = True
        else:
            in_gnew[glob] = True
        alive[glob] = False

    def consume(pending):
        """Blocking half: land one round's folds, retrying on failure,
        then journal the completed round."""
        round_idx, batch, ids, handles, cur_b, zs = pending
        try:
            for bucket, handle in zip(batch.buckets, handles):
                phi_b, _ = handle.result()
                fold_bucket(round_idx, bucket, ids, np.asarray(phi_b))
        except Exception as exc:
            _retry_stage1_round(eng, stats, shape_cache, round_idx, batch,
                                ids, fold_bucket, exc, cur_b, max_retries)
        if journal is not None:
            journal.record("lb", round_idx,
                           {"phi": phi, "lb": lb, "in_gnew": in_gnew,
                            "alive": alive},
                           stats, cur_budget=int(cur_b), zone_state=zs)

    # Double-buffered rounds: dispatch round r non-blocking, then let the
    # generator build round r + 1 (NS sweep, triangle routing, lane packing)
    # while the device peels r; consume r's results one round late.  With a
    # mesh the same pipeline holds pod-wide: the handles are shard_map
    # dispatches whose lanes span the mesh axis (DESIGN.md §10).
    #
    # The outer loop is the budget-degrade restart (DESIGN.md §12): when
    # the retry ladder exhausts lane splits and the mesh drop, it raises
    # _RestartRounds and the round generator is rebuilt from the fold
    # state's alive mask at the smaller budget.  ``alive`` only changes in
    # fold_bucket, so an un-folded round's edges are all still present —
    # the restart re-partitions (and re-peels) exactly the unfinished work.
    while True:
        start_ids = np.nonzero(alive)[0]
        if not len(start_ids):
            break
        pending = None
        try:
            for round_idx, batch, ids, cur_b, zs in _partition_rounds(
                    n, edges, start_budget, part_fn, stats,
                    lane_multiple=eng.n_dev, start_ids=start_ids,
                    store=store):
                try:
                    handles = []
                    for bi, bucket in enumerate(batch.buckets):
                        h = peel_classes_batched(
                            bucket.sup, bucket.tris, bucket.indptr,
                            bucket.tids, bucket.alive,
                            shape_cache=shape_cache, blocking=False,
                            mesh=eng.mesh, mesh_axis=eng.mesh_axis,
                            kernel=eng.kernel,
                            fault_ctx={"stage": 1, "round": round_idx,
                                       "bucket": bi, "retry": 0})
                        stats.compiles += int(h.new_compile)
                        stats.count_lanes(h)
                        handles.append(h)
                    stats.sharded_rounds += int(
                        any(h.sharded for h in handles))
                except Exception as exc:
                    # the failed dispatch is dead, but the PREVIOUS round's
                    # handles are fine: land those folds first so a budget
                    # restart below cannot lose a completed round
                    if pending is not None:
                        consume(pending)
                        pending = None
                    _retry_stage1_round(eng, stats, shape_cache, round_idx,
                                        batch, ids, fold_bucket, exc,
                                        cur_b, max_retries)
                    if journal is not None:
                        journal.record("lb", round_idx,
                                       {"phi": phi, "lb": lb,
                                        "in_gnew": in_gnew, "alive": alive},
                                       stats, cur_budget=int(cur_b),
                                       zone_state=zs)
                    continue
                if pending is not None:
                    stats.overlapped += 1
                    consume(pending)
                pending = (round_idx, batch, ids, handles, cur_b, zs)
            if pending is not None:
                consume(pending)
            break
        except _RestartRounds as r:
            start_budget = r.budget

    if store is not None:
        store.absorb_into(stats)
    return LowerBoundResult(
        edges=edges, phi=phi, lb=lb, in_gnew=in_gnew, rounds=stats.rounds,
        scans=stats.scans, max_part_edges=stats.max_part_edges, stats=stats,
    )


def _lower_bounding_perpart(n, edges, budget, part_fn) -> LowerBoundResult:
    """Seed path: per-round rebuild, per-part NS scan + dynamic-shape peel."""
    m = len(edges)
    phi = np.zeros(m, dtype=np.int64)
    lb = np.full(m, 2, dtype=np.int64)
    alive = np.ones(m, dtype=bool)          # still in the working graph
    in_gnew = np.zeros(m, dtype=bool)       # emitted to G_new
    stats = OocStats()
    cur_budget = budget

    while alive.any():
        stats.rounds += 1
        cur_ids = np.nonzero(alive)[0]
        g = glib.build_graph(n, edges[cur_ids])
        parts = part_fn(g, cur_budget, stats.rounds)
        if not parts:
            break
        round_removed = np.zeros(len(cur_ids), dtype=bool)
        for P in parts:
            stats.scans += 1
            stats.parts += 1
            stats.batches += 1
            sub_ids, sub_edges, internal = glib.neighborhood_subgraph(g, P)
            if len(sub_ids) == 0:
                continue
            stats.max_part_edges = max(stats.max_part_edges, len(sub_ids))
            stats.real_edges += len(sub_ids)
            stats.padded_slots += len(sub_ids)
            phi_local = _local_truss(sub_edges, n)
            int_ids = sub_ids[internal]               # ids in current graph
            glob_ids = cur_ids[int_ids]               # ids in original graph
            lb[glob_ids] = np.maximum(lb[glob_ids], phi_local[internal])
            if stats.rounds == 1:
                is_phi2 = phi_local[internal] == 2
                phi[glob_ids[is_phi2]] = 2
                in_gnew[glob_ids[~is_phi2]] = True
            else:
                in_gnew[glob_ids] = True
            round_removed[int_ids] = True
        if not round_removed.any():
            cur_budget *= 2
            continue
        alive[cur_ids[round_removed]] = False

    return LowerBoundResult(
        edges=edges, phi=phi, lb=lb, in_gnew=in_gnew, rounds=stats.rounds,
        scans=stats.scans, max_part_edges=stats.max_part_edges, stats=stats,
    )


@dataclasses.dataclass
class BottomUpResult:
    edges: np.ndarray
    phi: np.ndarray
    kmax: int
    rounds: int
    scans: int
    candidate_sizes: List[int]   # |H| per k (I/O + working-set accounting)
    stats: Optional[OocStats] = None


def _retry_candidate_peel(eng: _Engine, stats: OocStats, exc, dispatch,
                          max_retries: int = 2, *, stage: str):
    """Blocking retry ladder for a failed stage-2 / top-down candidate peel
    (DESIGN.md §12).  The candidate's host arrays survive the donation, so
    a retry is a plain re-dispatch of the same level (``dispatch(retry,
    eng)`` must dispatch blocking and return the folded result).  After
    ``max_retries`` failures the mesh is dropped — single-device is the
    memory floor for a candidate peel, whose size is set by the k-class
    structure rather than the round budget — and the retry budget resets
    once on the degraded engine; then the failure propagates.  ``stage``
    names the ladder's ``truss.retry`` spans (``"s2"`` or ``"td"``).
    """
    attempt = 0
    while True:
        if not faults.is_retryable(exc):
            raise exc
        stats.retries += 1
        attempt += 1
        if attempt > max_retries:
            if eng.mesh is None:
                raise exc
            eng.mesh = None
            stats.degraded += 1
            attempt = 0
        try:
            with span("retry", stage=stage, attempt=attempt):
                return dispatch(attempt, eng)
        except Exception as e:
            exc = e


def bottom_up_decompose(
    n: int,
    edges: np.ndarray,
    budget: int,
    partitioner: str | Callable = "sequential",
    engine: str = "batched",
    *,
    partitioner_seed: int = 0,
    mesh=None,
    mesh_axis="data",
    kernel: str = "auto",
    checkpoint_dir: Optional[str] = None,
    checkpoint_every: Union[int, str] = 1,
    resume: bool = False,
    checkpoint_keep: int = 3,
    max_retries: int = 2,
    store: Optional[GraphStore] = None,
) -> BottomUpResult:
    """Algorithm 4: full decomposition under a working-set budget.

    With a ``mesh`` (batched engine only), stage-1 rounds split their
    bucket lanes over ``mesh_axis`` and stage-2 candidate peels run
    triangle-sharded — one partition round spans the pod (DESIGN.md §10);
    ``OocStats.devices`` / ``sharded_rounds`` record the routing.  A
    ``(lane, tri)`` tuple ``mesh_axis`` additionally shards each lane's
    triangle sweep over the second axis (DESIGN.md §13).  ``kernel``
    routes the per-lane peel engine (``"pallas" | "xla" | "auto"``);
    it never changes φ or the round trajectory, so it is not part of
    the checkpoint run key.
    ``partitioner_seed`` offsets the randomized partitioner's per-round
    reseed (ignored by the deterministic splitters).

    ``checkpoint_dir`` enables the round journal (DESIGN.md §12): every
    ``checkpoint_every``-th completed stage-1 round ("lb" snapshots) and
    stage-2 level ("s2" snapshots) is written through the atomic
    checkpoint path, keeping the newest ``checkpoint_keep``
    (``checkpoint_every`` also takes a duration string — ``"30s"`` — to
    gate snapshots by wall clock instead of event count); with
    ``resume=True`` the newest intact snapshot whose run_key matches this
    configuration is restored and the run continues — φ is bit-identical
    to an uninterrupted run.  ``max_retries`` bounds the lane-split
    retries a retryable dispatch failure gets before the engine degrades
    (mesh drop, then budget halving); ``OocStats.retries / degraded /
    checkpoints / resumed_round`` record all of it.

    ``store`` (batched engine only) runs stage 1's working graph through a
    :class:`~repro.core.store.GraphStore` (DESIGN.md §15); the store's I/O
    counters land in ``OocStats``.  Neither the store nor
    ``checkpoint_every``'s gating mode enters the run key — they change
    I/O behavior, never φ or the round trajectory, so a crashed disk-backed
    run may resume in-memory and vice versa.
    """
    if store is not None and engine != "batched":
        raise ValueError(
            "store= requires the batched engine "
            "(engine='perpart' is the uninstrumented seed baseline)")
    journal = None
    snap = None
    if checkpoint_dir is not None:
        if engine != "batched":
            raise ValueError(
                "checkpointing requires the batched engine "
                "(engine='perpart' is the uninstrumented seed baseline)")
        edges = glib.canonical_edges(edges, n)
        key = _run_key("bottom_up", n, edges, budget, partitioner,
                       partitioner_seed,
                       devices=_mesh_devices(mesh, mesh_axis))
        journal = RoundJournal(checkpoint_dir, key, every=checkpoint_every,
                               keep=checkpoint_keep, store=store)
        if resume:
            snap = journal.load_latest()

    eng = _Engine(mesh=mesh, mesh_axis=mesh_axis, kernel=kernel)
    if snap is not None and snap[1]["stage"] == "s2":
        # stage 1 is complete in the snapshot; rebuild the stage-2 state
        # directly and skip the partition rounds entirely
        tree, meta = snap
        edges = glib.canonical_edges(edges, n)
        phi = tree["phi"].astype(np.int64)
        lb = tree["lb"].astype(np.int64)
        remaining = tree["remaining"].astype(bool)
        stats = OocStats.from_dict(meta["stats"])
        stats.resumed_round = int(meta["index"])
        stats.devices = eng.devices
        k0 = int(meta["index"]) + 1     # the journaled level is complete
        lbres = None
    else:
        k0 = 2
        lbres = lower_bounding(
            n, edges, budget, partitioner, engine=engine,
            partitioner_seed=partitioner_seed, mesh=mesh,
            mesh_axis=mesh_axis, journal=journal,
            restored=snap if snap is not None
            and snap[1]["stage"] == "lb" else None,
            max_retries=max_retries, engine_state=eng, store=store)
        edges = lbres.edges
        phi = lbres.phi.copy()
        lb = lbres.lb
        remaining = lbres.in_gnew.copy()
        stats = lbres.stats
    cand_sizes: List[int] = []
    shape_cache: set = set()

    def candidate_masks(k_b: int):
        """U_k and NS(U_k) from the CURRENT ``remaining`` mask — the one
        extraction both engines share.  Returns ``(h_ids, internal)`` or
        None when no remaining edge admits class k_b."""
        elig = remaining & (lb <= k_b)
        if not elig.any():
            return None
        u_k = np.zeros(n, dtype=bool)
        eg = edges[elig]
        u_k[eg[:, 0]] = True
        u_k[eg[:, 1]] = True
        # H = NS(U_k) within G_new: every remaining edge with >=1 endpoint
        # in U_k.
        u_in = u_k[edges[:, 0]]
        v_in = u_k[edges[:, 1]]
        in_h = remaining & (u_in | v_in)
        internal = remaining & u_in & v_in
        return np.nonzero(in_h)[0], internal

    def build_candidate(k_b: int):
        """Host half of one batched stage-2 level: NS(U_k) extracted,
        compacted and triangle-enumerated.

        Called one level ahead while the device still peels level k
        (DESIGN.md §11): the ``remaining`` it reads then still contains the
        edges level k is about to remove, so its U is a *superset* of the
        true U_{k+1} — which is sound: every Φ_{k+1} edge has both endpoints
        in U_{k+1} ⊆ U', so it stays removable, and a removable edge's
        triangles all lie inside NS(U') (its endpoints are in U'), so its
        support never under-counts; over-included removable edges with
        trussness > k+1 keep support >= k through their own T_{k+2}
        triangles, whose partner edges are again inside NS(U').  The edges
        the pending peel then removes are killed at use time via the
        ``alive0`` mask of ``local_threshold_peel``.  Returns None when no
        remaining edge admits class k_b (the consumer re-checks after the
        pending removal lands and jumps k past empty classes).
        """
        with span("candidate_build", k=int(k_b)) as sp:
            masks = candidate_masks(k_b)
            if masks is None:
                return None
            h_ids, internal = masks
            sp.count(edges=len(h_ids))
            local_edges, verts = glib.compact_edge_list(edges[h_ids])
            sub = glib.build_graph(len(verts), local_edges)
            tris = np.asarray(list_triangles(sub), np.int32).reshape(-1, 3)
        return k_b, h_ids, tris, internal

    k = k0
    pre = None          # candidate pre-built while the previous level peeled
    while remaining.any():
        # Skip empty classes: no remaining edge admits class < min lb, so
        # jump k straight there instead of probing one k at a time.
        k = max(k, int(lb[remaining].min()))
        stats.scans += 1
        if engine == "perpart":
            # seed path: blocking per-level extraction + full-shape peel
            # (non-empty by the k-jump above)
            h_ids, internal = candidate_masks(k)
            cand_sizes.append(len(h_ids))
            sub = glib.build_graph(n, edges[h_ids])
            tris = list_triangles_np(sub)
            sup = support_from_triangle_list(tris, sub.m).astype(np.int32)
            if len(tris) == 0:
                tris = np.full((1, 3), sub.m, np.int32)
            # Map internal mask to subgraph ids (canonical order preserved).
            removable = jnp.asarray(internal[h_ids])
            _, _, removed = peel_threshold(
                jnp.asarray(sup), jnp.asarray(tris),
                jnp.ones(sub.m, bool), removable, jnp.int32(k - 2),
            )
            removed = np.asarray(removed)
        else:
            if pre is not None and pre[0] == k:
                cand = pre           # built while level k-1 was peeling
                stats.stage2_overlapped += 1
            else:
                cand = build_candidate(k)
            pre = None
            _, h_ids, tris, internal = cand
            cand_sizes.append(len(h_ids))
            # kill the edges the previous level removed after this
            # candidate was built; supports count fully-alive triangles
            alive_h = remaining[h_ids]
            if len(tris):
                t_alive = (alive_h[tris[:, 0]] & alive_h[tris[:, 1]]
                           & alive_h[tris[:, 2]])
                sup = support_from_triangle_list(
                    tris[t_alive], len(h_ids)).astype(np.int32)
            else:
                sup = np.zeros(len(h_ids), np.int32)
            handle = dispatch_exc = None
            try:
                handle = local_threshold_peel(
                    sup, tris, internal[h_ids], k - 2, alive0=alive_h,
                    shape_cache=shape_cache, blocking=False, mesh=eng.mesh,
                    mesh_axis=eng.mesh_axis, kernel=eng.kernel,
                    fault_ctx={"stage": 2, "k": int(k), "retry": 0})
                stats.compiles += int(handle.new_compile)
                stats.count_lanes(handle)
                stats.batches += 1
                stats.sharded_rounds += int(handle.sharded)
            except Exception as exc:
                dispatch_exc = exc      # enters the retry ladder below
            # pipeline: extract + compact level k+1's candidate on the host
            # while the device peels level k (DESIGN.md §11)
            pre = build_candidate(k + 1)
            try:
                if dispatch_exc is not None:
                    raise dispatch_exc
                _, removed = handle.result()
            except Exception as exc:
                # the level's host inputs survive the donation: re-dispatch
                # through the retry ladder (DESIGN.md §12)
                def redispatch(retry, e, _k=k, _sup=sup, _tris=tris,
                               _rm=internal[h_ids], _alive=alive_h):
                    h = local_threshold_peel(
                        _sup, _tris, _rm, _k - 2, alive0=_alive,
                        shape_cache=shape_cache, blocking=False,
                        mesh=e.mesh, mesh_axis=e.mesh_axis, kernel=e.kernel,
                        fault_ctx={"stage": 2, "k": int(_k),
                                   "retry": retry})
                    stats.compiles += int(h.new_compile)
                    stats.count_lanes(h)
                    stats.batches += 1
                    _, rem = h.result()
                    return rem

                removed = _retry_candidate_peel(eng, stats, exc, redispatch,
                                                max_retries, stage="s2")
        rm_glob = h_ids[removed]
        phi[rm_glob] = k
        remaining[rm_glob] = False
        if journal is not None:
            journal.record("s2", k,
                           {"phi": phi, "lb": lb, "remaining": remaining},
                           stats)
        k += 1

    kmax = int(phi.max()) if len(phi) else 2
    if store is not None:
        store.absorb_into(stats)    # delta-based: journal absorbs mid-run
    return BottomUpResult(
        edges=edges, phi=phi, kmax=kmax, rounds=stats.rounds,
        scans=stats.scans, candidate_sizes=cand_sizes, stats=stats,
    )


def _support_credit_triples(bucket, round_idx: int, bi: int, sub_idx: int,
                            retry: int, *,
                            chunk_rows: int = 1 << 16) -> np.ndarray:
    """Flat parent-edge-id triples of one bucket's captured triangles —
    the compute half of a ``partitioned_support`` round, kept PURE (no
    scatter into the global ``sup``).

    Unlike the stage-1 folds, triangle credits (``np.add.at``) are **not**
    idempotent, so the retry ladder must be able to recompute a failed
    bucket from its host arrays and fold exactly once afterwards; the
    ``"support"`` fault site fires here, before any credit exists.

    The lane-wise gather walks ``bucket.tris`` in slabs of ``chunk_rows``
    triangle slots so the padded ``(B, cap_t, 3)`` parent intermediate is
    never materialized whole — its peak is ``B * chunk_rows * 3`` — while
    the returned array still holds only the real (unpadded) triples.
    """
    faults.check(faults.SUPPORT, stage=1, round=round_idx, bucket=bi,
                 sub=sub_idx, retry=retry)
    B = bucket.n_lanes
    # local triangle ids -> parent edge ids, lane-wise; the drop slot
    # cap_e maps to -1, so padding rows vanish with the mask
    eid_pad = np.concatenate(
        [bucket.edge_ids, np.full((B, 1), -1, np.int64)], axis=1)
    lane = np.arange(B)[:, None, None]
    cap_t = bucket.tris.shape[1]
    step = max(1, int(chunk_rows))
    out: List[np.ndarray] = []
    for lo in range(0, cap_t, step):
        parent = eid_pad[lane, bucket.tris[:, lo:lo + step]]
        real = parent[:, :, 0] >= 0
        out.append(parent[real].reshape(-1))
    return np.concatenate(out) if out else np.zeros(0, np.int64)


def _retry_support_round(eng: _Engine, stats: OocStats, round_idx: int,
                         batch, exc, cur_budget: int,
                         max_retries: int) -> List[np.ndarray]:
    """Retry ladder for a failed triangle-credit round — the
    ``partitioned_support`` sibling of :func:`_retry_stage1_round`
    (DESIGN.md §12), engaged only for retryable failures:

    1. lane-split retries — recompute each bucket as
       ``split_bucket_lanes`` sub-buckets (split 2, then 4, … up to
       ``max_retries`` doublings; every triangle lives in exactly one lane
       of one bucket, so the union of sub-bucket triples is exactly the
       whole batch's);
    2. mesh drop — ``eng.mesh = None`` for the rest of the run (the
       credit scatters are host-side, but the shared engine state carries
       the degrade into any later device stage the caller runs);
    3. budget halving — raise :class:`_RestartRounds`; the un-credited
       round's internal edges are all still alive, so the restarted rounds
       re-credit exactly the unfinished triangles (the exactly-once
       invariant is per-working-graph).

    Returns the per-(sub-)bucket triple arrays; the caller folds them
    once, after the whole round has been recomputed successfully.
    """
    split = 1
    attempt = 0
    while True:
        if not faults.is_retryable(exc):
            raise exc
        stats.retries += 1
        attempt += 1
        if split < (1 << max_retries):
            split *= 2
        elif eng.mesh is not None:
            eng.mesh = None
            stats.degraded += 1
        else:
            if cur_budget <= _MIN_ROUND_BUDGET:
                raise exc
            stats.degraded += 1
            raise _RestartRounds(max(cur_budget // 2, _MIN_ROUND_BUDGET))
        try:
            with span("retry", stage="sup", attempt=attempt):
                return [_support_credit_triples(sub, round_idx, bi, si, split)
                        for bi, bucket in enumerate(batch.buckets)
                        for si, sub in enumerate(
                            plib.split_bucket_lanes(bucket, split))]
        except Exception as e:
            exc = e


def partitioned_support(
    n: int,
    edges: np.ndarray,
    budget: int,
    partitioner: str | Callable = "sequential",
    engine: str = "batched",
    with_stats: bool = False,
    *,
    partitioner_seed: int = 0,
    mesh=None,
    mesh_axis="data",
    journal: Optional[RoundJournal] = None,
    restored=None,
    max_retries: int = 2,
    store: Optional[GraphStore] = None,
):
    """Exact sup(e) w.r.t. the FULL graph, computed under a working-set
    budget (triangle-credit variant of Algorithm 3 used by the top-down
    algorithm; see DESIGN.md §7).

    Invariant: every triangle of G is credited exactly once — in the first
    round in which one of its edges becomes internal (all internal edges of a
    triangle lie in the same part, two disjoint parts cannot both hold two of
    a triangle's three vertices, and a triangle loses an edge from the
    working graph the moment it is first credited).

    The batched engine lists each NS(P)'s triangles through the compacted,
    skew-aware machinery and credits them in one vectorized scatter per
    bucket; no peeling is involved, so the batch is built without incidence
    and a ``mesh`` only records ``OocStats.devices`` for the caller
    (top-down threads it here so one stats object describes both stages —
    the credit scatters themselves are host-side and never span the mesh).

    ``journal`` / ``restored`` (batched engine only) snapshot the credit
    state after each completed round as ``"sup"``-stage snapshots and
    resume from one (DESIGN.md §12): the exactly-once crediting invariant
    is per-working-graph, so restarting the rounds from the journaled
    ``alive`` mask re-credits nothing — rounds after the snapshot were
    never folded into the journaled ``sup``.

    A failed round (the ``"support"`` fault site) drives the same
    degradation ladder as stage 1 — lane splits, mesh drop, budget-halving
    restart (:func:`_retry_support_round`); because the credits are not
    idempotent, a round's triples are all computed before any is folded,
    so a mid-round failure never half-credits.  ``max_retries`` bounds the
    lane-split rungs; ``store`` routes the working graph through a
    :class:`~repro.core.store.GraphStore` (batched engine only).
    """
    part_fn = _resolve_partitioner(partitioner, seed=partitioner_seed)
    edges = glib.canonical_edges(edges, n)
    m = len(edges)
    sup = np.zeros(m, dtype=np.int64)
    alive = np.ones(m, dtype=bool)
    stats = OocStats()
    if mesh is not None:
        if engine == "perpart":
            raise ValueError("mesh= requires the batched engine")
        stats.devices = _mesh_devices(mesh, mesh_axis)
    if store is not None and engine == "perpart":
        raise ValueError(
            "store= requires the batched engine "
            "(engine='perpart' is the uninstrumented seed baseline)")
    cur_budget = budget
    if restored is not None:
        if engine == "perpart":
            raise ValueError(
                "checkpointing requires the batched engine "
                "(engine='perpart' is the uninstrumented seed baseline)")
        tree, meta = restored
        sup = tree["sup"].astype(np.int64)
        alive = tree["alive"].astype(bool)
        dev = stats.devices
        stats = OocStats.from_dict(meta["stats"])
        stats.resumed_round = int(meta["index"])
        stats.devices = dev
        cur_budget = int(meta.get("cur_budget", budget))
        _restore_zone_state(part_fn, meta.get("zone_state"))

    if engine == "perpart":
        alive = np.ones(m, dtype=bool)
        while alive.any():
            stats.rounds += 1
            cur_ids = np.nonzero(alive)[0]
            g = glib.build_graph(n, edges[cur_ids])
            parts = part_fn(g, cur_budget, stats.rounds)
            if not parts:
                break
            round_removed = np.zeros(len(cur_ids), dtype=bool)
            for P in parts:
                stats.scans += 1
                sub_ids, sub_edges, internal = glib.neighborhood_subgraph(g, P)
                if len(sub_ids) == 0:
                    continue
                sub = glib.build_graph(n, sub_edges)
                tris = list_triangles_np(sub)
                if len(tris):
                    # subgraph edge id -> current-graph id -> original id
                    to_glob = cur_ids[sub_ids]
                    np.add.at(sup, to_glob[tris.reshape(-1)], 1)
                round_removed[sub_ids[internal]] = True
            if not round_removed.any():
                cur_budget *= 2   # stall fallback (see lower_bounding)
                continue
            alive[cur_ids[round_removed]] = False
        return (sup, stats) if with_stats else sup

    if engine != "batched":
        raise ValueError(f"unknown engine {engine!r}")

    # The triangle-credit counter is all host-side scatters (no device
    # peel), so the shared round generator is consumed directly — same
    # incremental maintenance and stall fallback as the peeling driver.
    # The outer loop is the budget-degrade restart (DESIGN.md §12): the
    # ladder raises _RestartRounds and the generator is rebuilt from the
    # credit state's alive mask at the smaller budget — un-credited rounds'
    # internal edges are all still alive, so nothing double-credits.
    eng = _Engine(mesh=mesh, mesh_axis=mesh_axis)
    while True:
        start_ids = np.nonzero(alive)[0]
        if not len(start_ids):
            break
        try:
            for round_idx, batch, ids, cur_b, zs in _partition_rounds(
                    n, edges, cur_budget, part_fn, stats,
                    with_incidence=False, start_ids=start_ids, store=store):
                with span("support_credit") as sp:
                    try:
                        trips = [
                            _support_credit_triples(bucket, round_idx, bi,
                                                    0, 0)
                            for bi, bucket in enumerate(batch.buckets)]
                    except Exception as exc:
                        trips = _retry_support_round(eng, stats, round_idx,
                                                     batch, exc, cur_b,
                                                     max_retries)
                    # fold only after EVERY bucket's triples exist: the
                    # credits are not idempotent, so a failed round must
                    # never be partially folded (the ladder recomputes it
                    # whole)
                    for trip in trips:
                        if len(trip):
                            np.add.at(sup, ids[trip], 1)
                    for bucket in batch.buckets:
                        alive[ids[bucket.edge_ids[bucket.internal]]] = False
                    sp.count(triangles=sum(len(t) for t in trips) // 3)
                if journal is not None:
                    journal.record("sup", round_idx,
                                   {"sup": sup, "alive": alive}, stats,
                                   cur_budget=int(cur_b), zone_state=zs)
            break
        except _RestartRounds as r:
            cur_budget = r.budget

    if store is not None:
        store.absorb_into(stats)
    return (sup, stats) if with_stats else sup
