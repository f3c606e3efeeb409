"""Fused frontier-peel round as a single Pallas TPU kernel.

One invocation computes one WHOLE removal round for a batch of peel lanes:
given per-lane edge state (support, alive mask) and the round's removal
frontier ``rm = alive & (sup <= thresh)``, it produces the post-round state

    alive' = alive & ~rm
    sup'   = sup - #{died triangles incident to each surviving edge}

where a triangle dies when all three corners were alive and at least one was
removed.  This is the dense-sweep form of ``peel._frontier_round``'s
gather/dedup/scatter loop: because the entire frontier is removed in one
round (no cap_f chunking), the owner-dedup reduces to "each died triangle
decrements each of its surviving corners exactly once", and the kernel is
statically overflow-free — there is no cap_f/cap_t resume path.

Memory layout (DESIGN.md §13): grid is (lanes, triangle tiles).  Each lane's
edge-state rows — sup, alive, rm in; sup', alive' out; a f32 decrement
accumulator in scratch — live in VMEM for the whole sweep (BlockSpec index
maps pin them to the lane, so Pallas revisits the same block across the tile
loop).  The (bt, 3) triangle tile is the only streamed operand.  Corner
gathers and the decrement scatter both go through a one-hot (bt, E) matmul,
so the inner loop is MXU work with NO dynamic indexing — the layout Pallas
TPU lowers well, same trick as the ``triangle_count`` kernel's masked-dot
formulation.

The f32 accumulator is exact while per-round decrements stay below 2^24 per
edge — guaranteed here because an edge's decrement is bounded by its support,
an int32 well under 2^24 in every OOC lane (cap_e <= 2^20).
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

VMEM_BUDGET_BYTES = 12 * 1024 * 1024  # leave headroom below the ~16 MB/core
DEFAULT_TILE_CANDIDATES = (128, 256, 512, 1024)
# the pallas_call's name: stable, so a trace reader can find the kernel by it
KERNEL_NAME = "fused_round"


def kernel_vmem_bytes(cap_e: int, bt: int) -> int:
    """Conservative VMEM working set of one (lane, tile) kernel step.

    Five int32 edge-state rows + one f32 accumulator row (6 * cap_e words);
    the streamed (bt, 3) triangle tile, double-buffered and padded to a
    128-lane tile; four (bt, 1) f32 per-triangle columns, lane-padded the
    same way; and the transient (bt, cap_e) f32 one-hot used for the
    gather/scatter matmuls — counted twice for the operand copy the MXU
    pipeline holds in flight.  The TPU compiler's own scoped-VMEM need
    stays under this bound (``tests/test_tpu_compile.py`` compiles the
    kernel with the bound as its VMEM limit).
    """
    edge_rows = 6 * cap_e * 4
    tri_tile = 2 * bt * 128 * 4
    columns = 4 * bt * 128 * 4
    onehot = 2 * bt * cap_e * 4
    return edge_rows + tri_tile + columns + onehot


def _round_kernel(sup_ref, alive_ref, rm_ref, tris_ref,
                  sup_out_ref, alive_out_ref, dec_ref):
    """Grid (B, T // bt): lane i's edge state resident, tile j streamed.

    Edge-state refs are (1, 1, E) blocks of the (B, 1, E) lane-major rows;
    index 0 drops the lane dim, leaving the (1, E) row the body works on.
    """
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        dec_ref[...] = jnp.zeros_like(dec_ref)

    cap_e = sup_ref.shape[2]
    bt = tris_ref.shape[1]
    alive_f = alive_ref[0].astype(jnp.float32)
    rm_f = rm_ref[0].astype(jnp.float32)
    alive2_f = alive_f * (1.0 - rm_f)

    def gather(oh, row):
        return jax.lax.dot_general(oh, row, (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)

    cols = jax.lax.broadcasted_iota(jnp.int32, (bt, cap_e), 1)

    def onehot(c):
        # padding rows carry the drop slot cap_e -> all-zero row -> inert
        e_c = tris_ref[0, :, c]
        return (cols == e_c[:, None]).astype(jnp.float32)

    # pass 1: which triangles of this tile die this round?
    live = jnp.ones((bt, 1), jnp.float32)
    surv = jnp.ones((bt, 1), jnp.float32)
    for c in range(3):
        oh = onehot(c)
        live = live * gather(oh, alive_f)
        surv = surv * (1.0 - gather(oh, rm_f))
    died = live * (1.0 - surv)                                   # (bt, 1)

    # pass 2: each died triangle decrements each surviving corner once
    for c in range(3):
        oh = onehot(c)
        corner_alive2 = gather(oh, alive2_f)
        contrib = (died * corner_alive2).reshape(1, bt)
        dec_ref[...] += jnp.dot(contrib, oh,
                                preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        sup_out_ref[0] = sup_ref[0] - dec_ref[...].astype(jnp.int32)
        alive_out_ref[0] = alive_ref[0] * (1 - rm_ref[0])


def fused_round(sup, alive, rm, tris, *, bt: int = 256,
                interpret: bool = False):
    """One fused removal round over a batch of lanes.

    sup/alive/rm: (B, E) int32 (alive, rm are 0/1 masks, rm ⊆ alive);
    tris: (B, T, 3) int32 with T divisible by ``bt`` and padding rows on the
    per-lane drop slot E.  Returns (sup', alive') as (B, E) int32.

    ``interpret=True`` runs the Pallas interpreter (CPU test path);
    compiled mode targets TPU (Pallas has no CPU lowering).

    Inside the call the rows are laid out (B, 1, E) so that every edge-state
    block, (1, 1, E), equals the array in its last two dims — the Mosaic
    tiling rule a (1, E) block of a (B, E) array breaks for B > 1.
    """
    B, cap_e = sup.shape
    T = tris.shape[1]
    if T % bt:
        raise ValueError(f"tile {bt} must divide triangle count {T}")
    grid = (B, T // bt)
    row = pl.BlockSpec((1, 1, cap_e), lambda i, j: (i, 0, 0))
    sup_out, alive_out = pl.pallas_call(
        _round_kernel,
        name=KERNEL_NAME,
        grid=grid,
        in_specs=[row, row, row,
                  pl.BlockSpec((1, bt, 3), lambda i, j: (i, j, 0))],
        out_specs=[row, row],
        out_shape=[jax.ShapeDtypeStruct((B, 1, cap_e), jnp.int32),
                   jax.ShapeDtypeStruct((B, 1, cap_e), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((1, cap_e), jnp.float32)],
        interpret=interpret,
    )(sup[:, None], alive[:, None], rm[:, None], tris)
    return sup_out[:, 0], alive_out[:, 0]


def feasible_tiles(cap_e: int, cap_t: int,
                   candidates=DEFAULT_TILE_CANDIDATES,
                   budget_bytes: int = VMEM_BUDGET_BYTES):
    """Tile sizes that divide the (padded) triangle capacity and whose
    working set fits the VMEM budget, largest first (fewer grid steps)."""
    out = [bt for bt in candidates
           if cap_t % bt == 0 and kernel_vmem_bytes(cap_e, bt) <= budget_bytes]
    return sorted(set(out), reverse=True)


_TUNE_CACHE: dict = {}


def autotune_tiles(cap_e: int, cap_t: int, *,
                   candidates=None,
                   budget_bytes: int = VMEM_BUDGET_BYTES,
                   interpret: bool = False, repeats: int = 2,
                   seed: int = 0) -> int:
    """Pick the fastest feasible ``bt`` by timing one fused round per
    candidate on synthetic data; cached per (shape, backend) like the
    ``triangle_count`` tuner.  Raises ``ValueError`` when no candidate
    divides ``cap_t`` within the VMEM budget; a candidate that fails to
    compile or run raises too — a feasible tile the chip refuses is a bug
    in ``kernel_vmem_bytes``, not a tile to skip."""
    cands = tuple(candidates or DEFAULT_TILE_CANDIDATES)
    key = (cap_e, cap_t, jax.default_backend(), bool(interpret), cands,
           budget_bytes)
    if key in _TUNE_CACHE:
        return _TUNE_CACHE[key]
    feas = feasible_tiles(cap_e, cap_t, cands, budget_bytes)
    if not feas:
        raise ValueError(
            f"no tile in {cands} divides cap_t={cap_t} within the "
            f"{budget_bytes}-byte VMEM budget at cap_e={cap_e}")
    rng = np.random.default_rng(seed)
    sup = jnp.asarray(rng.integers(0, 8, (1, cap_e)), jnp.int32)
    alive = jnp.ones((1, cap_e), jnp.int32)
    rm = jnp.asarray(rng.integers(0, 2, (1, cap_e)), jnp.int32)
    tris = jnp.asarray(rng.integers(0, cap_e, (1, cap_t, 3)), jnp.int32)
    best, best_t = feas[0], float("inf")
    for bt in feas:
        fn = functools.partial(fused_round, bt=bt, interpret=interpret)
        jax.block_until_ready(fn(sup, alive, rm, tris))  # warm up
        t0 = time.perf_counter()
        for _ in range(repeats):
            jax.block_until_ready(fn(sup, alive, rm, tris))
        dt = (time.perf_counter() - t0) / repeats
        if dt < best_t:
            best, best_t = bt, dt
    _TUNE_CACHE[key] = best
    return best
