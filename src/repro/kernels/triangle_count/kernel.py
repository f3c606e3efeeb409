"""Pallas TPU kernel: blocked dense triangle counting S = (A @ A) ∘ A.

This is the paper's support computation (its hot spot) mapped onto the MXU
(DESIGN.md §2): the neighborhood-subgraph-fits-in-memory discipline becomes
adjacency *tiles* that fit in VMEM.  Grid (i, j, k) with the contraction k
innermost; each (i, j) output tile accumulates A[i,k] @ A[k,j] in an f32
VMEM scratch accumulator and applies the edge mask A[i,j] once on the last
k step.  All tile dims should be multiples of 128 to align with the MXU;
inputs may be bf16 (0/1 values are exact in bf16), accumulation is f32.

VMEM budget per step (see ``kernel_vmem_bytes`` and DESIGN.md §5): the
pipeliner double-buffers the three input tiles, the accumulator and output
tile are single instances — ``2*(bm*bk + bk*bn + bm*bn)*in_bytes +
2*bm*bn*4``.  With 256x256x256 f32 that is ~2 MiB, comfortably inside the
~16 MiB/core VMEM; bf16 inputs (0/1 adjacency is exact in bf16) halve the
input-tile traffic and let 512-wide k tiles fit.  ``autotune_tiles`` sweeps
the budget-feasible (bm, bn, bk) candidates and caches the fastest.
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# VMEM the tile working set may claim; real VMEM is ~16 MiB/core but the
# pipeliner needs headroom for semaphores/regs, so budget conservatively.
VMEM_BUDGET_BYTES = 12 * 1024 * 1024

DEFAULT_TILE_CANDIDATES = (
    (128, 128, 128),
    (128, 128, 256),
    (256, 128, 256),
    (256, 256, 128),
    (256, 256, 256),
    (256, 256, 512),
    (512, 256, 256),
)


def kernel_vmem_bytes(bm: int, bn: int, bk: int, in_dtype=jnp.float32) -> int:
    """Per-step VMEM working set of the blocked kernel (DESIGN.md §5).

    Double-buffered input tiles A[i,k], A[k,j], A[i,j] plus the f32
    accumulator scratch and output tile.
    """
    in_bytes = jnp.dtype(in_dtype).itemsize
    tiles_in = (bm * bk + bk * bn + bm * bn) * in_bytes * 2
    acc_out = bm * bn * 4 * 2
    return tiles_in + acc_out


def _kernel(a_ik, a_kj, a_ij, o_ref, acc_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        a_ik[...], a_kj[...], preferred_element_type=jnp.float32
    )

    @pl.when(k == pl.num_programs(2) - 1)
    def _finish():
        o_ref[...] = acc_ref[...] * a_ij[...].astype(jnp.float32)


def triangle_count_kernel(
    A: jnp.ndarray,
    *,
    bm: int = 256,
    bn: int = 256,
    bk: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    """S = (A @ A) ∘ A.  A: (n, n) in f32 or bf16, n divisible by the tiles.

    0/1 adjacency values and their per-tile dot products are exact in bf16
    up to n = 256 per k-tile step; accumulation across k steps is always f32
    (the scratch accumulator), so bf16 inputs lose no precision for counts
    below 2^24 triangles per edge.
    """
    n = A.shape[0]
    if A.shape != (n, n):
        raise ValueError(f"adjacency must be square, got {A.shape}")
    if A.dtype not in (jnp.float32, jnp.bfloat16):
        raise TypeError(f"adjacency dtype must be f32 or bf16, got {A.dtype}")
    bm, bn, bk = (min(b, n) for b in (bm, bn, bk))
    if n % bm or n % bn or n % bk:
        raise ValueError(f"tile shapes must divide n={n}, got "
                         f"(bm, bn, bk)=({bm}, {bn}, {bk})")
    grid = (n // bm, n // bn, n // bk)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(A, A, A)


# ---------------------------------------------------------------------------
# tile autotuning (DESIGN.md §5)
# ---------------------------------------------------------------------------

_TUNE_CACHE: dict = {}


def feasible_tiles(n: int, dtype=jnp.float32, candidates=None,
                   budget_bytes: int = VMEM_BUDGET_BYTES):
    """Candidate (bm, bn, bk) triples that divide n and fit the VMEM budget."""
    out = []
    for bm, bn, bk in (candidates or DEFAULT_TILE_CANDIDATES):
        bm, bn, bk = min(bm, n), min(bn, n), min(bk, n)
        if n % bm or n % bn or n % bk:
            continue
        if kernel_vmem_bytes(bm, bn, bk, dtype) > budget_bytes:
            continue
        if (bm, bn, bk) not in out:
            out.append((bm, bn, bk))
    return out or [(min(128, n),) * 3]


def autotune_tiles(
    n: int,
    dtype=jnp.float32,
    *,
    candidates=None,
    budget_bytes: int = VMEM_BUDGET_BYTES,
    interpret: bool = False,
    repeats: int = 2,
    seed: int = 0,
) -> tuple[int, int, int]:
    """Sweep the feasible tile shapes on a random 0/1 matrix; return the
    fastest.  Results are cached per (n, dtype, backend, interpret,
    candidates, budget).  A feasible tile that fails to compile or run
    raises: the VMEM model admitted it, so skipping it would hide a bug."""
    key = (n, jnp.dtype(dtype).name, jax.default_backend(), interpret,
           tuple(candidates) if candidates is not None else None,
           budget_bytes)
    if key in _TUNE_CACHE:
        return _TUNE_CACHE[key]
    rng = jax.random.PRNGKey(seed)
    A = (jax.random.uniform(rng, (n, n)) < 0.3).astype(dtype)
    best, best_t = None, float("inf")
    for tiles in feasible_tiles(n, dtype, candidates, budget_bytes):
        bm, bn, bk = tiles
        fn = jax.jit(functools.partial(
            triangle_count_kernel, bm=bm, bn=bn, bk=bk, interpret=interpret))
        jax.block_until_ready(fn(A))              # compile + warm up
        t0 = time.perf_counter()
        for _ in range(repeats):
            jax.block_until_ready(fn(A))
        t = (time.perf_counter() - t0) / repeats
        if t < best_t:
            best, best_t = tiles, t
    _TUNE_CACHE[key] = best
    return best
