#!/usr/bin/env python3
"""End-to-end smoke of truss decomposition on a TPU chip.

    python chip_smoke.py               # one chip: phases (a)-(d)
    python chip_smoke.py --chips 4     # four chips: the mesh phase only

One process drives every phase through the public entry point
``truss_decompose`` and checks each φ against a reference:

  (a) in-memory, R-MAT scale 10, against the serial oracle ``alg2_truss``;
  (b) in-memory, Graph500 R-MAT (0.57/0.19/0.19, edge factor 16) at
      scale 16;
  (c) out-of-core bottom-up with ``kernel="auto"`` at a working-set budget
      small enough that lanes route to the fused Pallas peel kernel; it
      must run Pallas lanes, at least one multi-lane Pallas bucket, and
      take no retry or degradation;
  (d) out-of-core top-down, plus the dense ``triangle_count`` Pallas
      kernel on a planted-clique graph against ``edge_support_np``.

(c) and (d) run on a smaller graph of the (b) family (``_OOC_SCALE``) and
are checked against its own in-memory φ.

``--chips 4`` runs bottom-up on a ``_MESH_SCALE`` graph of the same family
over a 4-device ``("data",)`` mesh, over a 2x2 ``("data", "tri")`` mesh and
on one device, and checks that the three φ agree, that rounds were sharded
and that the first sharded bucket's lanes were split over the "data" axis.

Each phase prints one JSON line.  The last line of standard output is
``{"ok": true, "device": {...}}`` and is printed only when every phase
passed; the script exits nonzero, without that line, when JAX finds no TPU
or any check fails.  ``JAX_COMPILATION_CACHE_DIR`` (else ``.jax_cache`` in
the repo) keeps compiled programs between runs.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import sys
import time
import traceback

_HERE = os.path.dirname(os.path.abspath(__file__))

# Graph500 R-MAT initiator; the fourth quadrant takes the rest (0.05)
_G500 = dict(a=0.57, b=0.19, c=0.19)
_SEED = 0
# phase (b): about 0.9M edges, near the paper's Amazon graph
_SCALE = 16
# NS edge cost per out-of-core part: small enough that most bucket lanes
# fit the fused kernel's VMEM budget (cap_e <= 8192) and route to Pallas,
# whatever the graph's size
_PART_BUDGET = 4096
# The out-of-core engines partition on the host in numpy, and that work
# grows about 3x per R-MAT scale step ((c) alone took 183 s at scale 13 on
# a one-chip v5e host), so their graphs are cut below (b)'s to keep the
# whole smoke well inside its 20-minute limit: scale 13 for (c)/(d), and
# scale 12 for the mesh phase, whose three runs hold four chips.
_OOC_SCALE = 13
_MESH_SCALE = 12


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or degraded result."""


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def _emit(record: dict) -> None:
    print(json.dumps(record, sort_keys=True), flush=True)


class _CompileCounter:
    """Counts backend compiles and persistent-cache hits via jax.monitoring."""

    def __init__(self):
        import jax

        self.counts = collections.Counter()
        jax.monitoring.register_event_duration_secs_listener(
            lambda name, _dur, **_kw: self.counts.update([name]))
        jax.monitoring.register_event_listener(
            lambda name, **_kw: self.counts.update([name]))

    def snapshot(self):
        return (self.counts["/jax/core/compile/backend_compile_duration"],
                self.counts["/jax/compilation_cache/cache_hits"])


class _Phase:
    """Times one phase and collects its compile counts and counters."""

    def __init__(self, name: str, counter: _CompileCounter, **info):
        self.record = {"phase": name, **info}
        self._counter = counter

    def __enter__(self):
        self._t0 = time.perf_counter()
        self._c0 = self._counter.snapshot()
        return self.record

    def __exit__(self, exc_type, exc, tb):
        c1 = self._counter.snapshot()
        self.record["wall_s"] = time.perf_counter() - self._t0
        self.record["compiles"] = c1[0] - self._c0[0]
        self.record["cache_hits"] = c1[1] - self._c0[1]
        if exc is not None:
            self.record["error"] = f"{type(exc).__name__}: {exc}"
        _emit(self.record)
        return False


_OOC_FIELDS = ("rounds", "batches", "max_part_edges", "pallas_lanes",
               "xla_lanes", "pallas_max_lanes", "sharded_rounds", "devices",
               "lane_shards", "lanes_per_shard", "retries", "degraded")


def _ooc_counters(stats) -> dict:
    # OocStats.compiles counts padded shapes; "compiles" is JAX's own count
    return {"padded_shapes": int(stats.compiles),
            **{f: int(getattr(stats, f)) for f in _OOC_FIELDS}}


def _check_not_degraded(stats) -> None:
    _check(stats.retries == 0, f"{stats.retries} retried dispatches")
    _check(stats.degraded == 0, f"{stats.degraded} engine degradations")


def rmat_graph(scale: int):
    from repro.data import graphgen

    return graphgen.rmat(scale, 16, seed=_SEED, **_G500)


def ooc_budget(n, edges) -> int:
    """The ``memory_budget`` (working-set entries) that ``truss_decompose``
    turns into parts of ``_PART_BUDGET`` NS edge cost."""
    from repro.core.graph import build_graph
    from repro.core.peel import estimate_working_set

    g = build_graph(n, edges)
    return max(1, _PART_BUDGET * estimate_working_set(g) // (2 * g.m))


def phase_small(counter) -> None:
    """(a) in-memory decomposition against the serial oracle."""
    from repro.core.peel import truss_decompose
    from repro.core.serial import alg2_truss

    n, edges = rmat_graph(10)
    with _Phase("a_inmem_vs_oracle", counter, scale=10, m=len(edges)) as r:
        phi = truss_decompose(n, edges)
        ref = alg2_truss(n, edges)
        r["kmax"] = int(phi.max())
        r["phi_equal"] = bool((phi == ref).all())
    _check(r["phi_equal"], "phase (a): φ differs from alg2_truss")


def phase_inmem(counter, n, edges, scale: int, name: str):
    """In-memory decomposition of an R-MAT graph; returns φ."""
    from repro.core.peel import truss_decompose

    with _Phase(name, counter, scale=scale, n=n, m=len(edges)) as r:
        phi, stats = truss_decompose(n, edges, with_stats=True)
        r["kmax"] = int(phi.max())
        r["engine"] = "dense" if stats is None else "frontier"
        if stats is not None:
            r["peel_rounds"] = int(stats.rounds)
    return phi


def phase_ooc(counter, engine: str, scale: int, n, edges, phi_ref,
              name: str, **kwargs):
    """One out-of-core run through ``truss_decompose``, checked against
    ``phi_ref`` unless it is None; returns (stats, φ)."""
    from repro.core.peel import truss_decompose

    budget = ooc_budget(n, edges)
    info = {}
    if kwargs.get("mesh") is not None:
        info["mesh_shape"] = dict(kwargs["mesh"].shape)
    with _Phase(name, counter, engine=engine, scale=scale, m=len(edges),
                memory_budget=budget, **info) as r:
        phi, stats = truss_decompose(n, edges, engine=engine,
                                     memory_budget=budget, with_stats=True,
                                     **kwargs)
        r.update(_ooc_counters(stats))
        if phi_ref is not None:
            r["phi_equal"] = bool((phi == phi_ref).all())
    _check(r.get("phi_equal", True), f"{name}: φ differs from its reference")
    _check_not_degraded(stats)
    return stats, phi


def phase_dense_kernel(counter) -> None:
    """(d) the dense triangle_count kernel, compiled, against numpy."""
    from repro.core.graph import build_graph
    from repro.core.support import edge_support_np
    from repro.data import graphgen
    from repro.kernels.triangle_count.ops import dense_edge_support

    n = 2048
    edges = graphgen.planted_cliques(n, 24, 40, 20000, seed=_SEED)
    with _Phase("d_dense_triangle_kernel", counter, n=n, m=len(edges)) as r:
        sup = dense_edge_support(n, edges, block="auto", interpret=False,
                                 use_kernel=True)
        ref = edge_support_np(build_graph(n, edges))
        r["max_support"] = int(ref.max())
        r["support_equal"] = bool((sup == ref).all())
    _check(r["support_equal"], "dense triangle_count kernel differs from "
                               "edge_support_np")


def run_one_chip(counter) -> None:
    phase_small(counter)
    phase_inmem(counter, *rmat_graph(_SCALE), _SCALE, "b_inmem")
    n, edges = rmat_graph(_OOC_SCALE)
    phi_ref = phase_inmem(counter, n, edges, _OOC_SCALE, "cd_reference")
    stats, _ = phase_ooc(counter, "bottom-up", _OOC_SCALE, n, edges,
                         phi_ref, "c_bottom_up", kernel="auto")
    _check(stats.pallas_lanes > 0, "phase (c): no lane took the Pallas "
                                   "kernel")
    _check(stats.pallas_max_lanes > 1, "phase (c): no multi-lane Pallas "
                                       "bucket")
    phase_ooc(counter, "top-down", _OOC_SCALE, n, edges, phi_ref,
              "d_top_down", kernel="auto")
    phase_dense_kernel(counter)


def run_mesh(counter, n_chips: int) -> None:
    from repro.launch.mesh import make_host_mesh

    n, edges = rmat_graph(_MESH_SCALE)
    _, phi_1 = phase_ooc(counter, "bottom-up", _MESH_SCALE, n, edges, None,
                         "mesh_1dev")
    for axes in (("data",), ("data", "tri")):
        mesh = make_host_mesh(n_chips, axes)
        name = "mesh_" + "x".join(axes)
        stats, _ = phase_ooc(counter, "bottom-up", _MESH_SCALE, n, edges,
                             phi_1, name, mesh=mesh, mesh_axes=axes)
        _check(stats.devices == n_chips, f"{name}: run spanned "
                                         f"{stats.devices} devices")
        _check(stats.sharded_rounds > 0, f"{name}: no sharded rounds")
        _check(stats.lane_shards == mesh.shape["data"],
               f"{name}: first sharded bucket held {stats.lane_shards} "
               f"lane slices, not one per 'data' device")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: phases (a)-(d) on one chip; 4: the mesh phase "
                         "only, on four chips")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(_HERE, "src", "repro")):
        print("chip_smoke: the repo's src/repro is not next to this script",
              file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(_HERE, "src"))
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {platform!r})",
              file=sys.stderr)
        return 3
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"found {len(devices)}", file=sys.stderr)
        return 3

    from repro.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    _emit({"phase": "setup", "platform": platform,
           "device_kind": devices[0].device_kind, "devices": len(devices),
           "jax": jax.__version__, "compile_cache": cache_dir})
    counter = _CompileCounter()
    try:
        if args.chips == 4:
            run_mesh(counter, args.chips)
        else:
            run_one_chip(counter)
    except Exception as e:
        traceback.print_exc()
        print(f"chip_smoke: FAILED: {type(e).__name__}: {e}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True,
                      "device": {"platform": platform,
                                 "kind": devices[0].device_kind,
                                 "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
