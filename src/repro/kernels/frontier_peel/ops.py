"""Outer peel loops over the fused round kernel + auto-dispatch helpers.

``peel_classes_fused`` / ``peel_threshold_fused`` are the drop-in fused
counterparts of ``peel._peel_classes_vmapped`` and
``peel.peel_threshold_fixedcap``: a jit'd ``lax.while_loop`` whose body is
ONE ``pallas_call`` (the whole round) plus a handful of jnp reductions for
the k-jump glue — versus the XLA frontier engine's per-round
compact/gather/dedup/scatter dispatch chain.  The fused path needs no
edge→triangle incidence CSR at all (the kernel sweeps the triangle list
directly), so callers also skip the host-side ``triangle_incidence_np``
build.

``resolve_kernel`` is the ``kernel="auto"`` routing rule (DESIGN.md §13):
Pallas only on a TPU backend, only when a tile fits the VMEM budget, and
only when the lane is triangle-dense enough (3T >= E) for the dense sweep
to beat sparse gathers — the same backend discipline as
``support.edge_support_auto``'s dense-core kernel routing.  Off-TPU, forced
``kernel="pallas"`` runs the Pallas interpreter (the CI parity path).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.frontier_peel import kernel as fk

_BIG = jnp.int32(np.iinfo(np.int32).max // 2)

# mirrors peel.N_STATS layout (rounds, removed, gathered, max frontier);
# test_frontier_peel_kernel pins the two layouts together
N_STATS = 4
_S_ROUNDS, _S_REMOVED, _S_GATHERED, _S_MAXF = range(N_STATS)


def fused_working_set_bytes(cap_e: int, n_tris: int) -> int:
    """``estimate_working_set``-style per-round footprint of the fused path:
    the resident edge-state rows plus one streamed pass over the triangle
    list (tiles are transient, so the stream counts once)."""
    return 6 * cap_e * 4 + 3 * n_tris * 4


def resolve_kernel(kernel: str, cap_e: int, n_tris: int, *,
                   backend: str | None = None) -> str:
    """Resolve a ``kernel="pallas"|"xla"|"auto"`` knob to a concrete engine.

    "auto" picks Pallas only when (a) the backend is TPU — Pallas has no
    CPU lowering, so off-TPU auto always takes the XLA oracle, the
    ``edge_support_auto`` precedent; (b) some tile fits the VMEM budget for
    this cap_e; and (c) the lane is triangle-dense (3T >= E), where the
    dense sweep's MXU work beats the sparse gather chain.
    """
    if kernel in ("pallas", "xla"):
        return kernel
    if kernel != "auto":
        raise ValueError(f"unknown kernel {kernel!r}")
    backend = backend or jax.default_backend()
    if backend != "tpu":
        return "xla"
    from repro.core.support import triangle_density
    fits = [c for c in fk.DEFAULT_TILE_CANDIDATES
            if fk.kernel_vmem_bytes(cap_e, c) <= fk.VMEM_BUDGET_BYTES]
    if not fits or triangle_density(cap_e, n_tris) < 1.0:
        return "xla"
    return "pallas"


def resolve_tile(cap_e: int, n_tris: int, bt, interpret: bool) -> int:
    """Concrete tile size: explicit int passes through; "auto" takes the
    largest budget-feasible candidate no bigger than the (pow2-rounded)
    triangle count — divisibility is handled by padding, not rejection.

    Raises ``ValueError`` when no candidate fits the VMEM budget at this
    ``cap_e``: "auto" routing never gets here then (``resolve_kernel``
    checks the same fit), so the lane was forced onto the kernel with
    ``kernel="pallas"`` and would overflow VMEM on the chip."""
    if bt != "auto":
        return int(bt)
    fits = [c for c in fk.DEFAULT_TILE_CANDIDATES
            if fk.kernel_vmem_bytes(cap_e, c) <= fk.VMEM_BUDGET_BYTES]
    if not fits:
        raise ValueError(
            f"no fused-peel tile fits the {fk.VMEM_BUDGET_BYTES}-byte VMEM "
            f"budget at cap_e={cap_e}; use kernel='xla' or 'auto'")
    cover = 1
    while cover < max(1, n_tris):
        cover *= 2
    under = [c for c in fits if c <= max(cover, min(fits))]
    return max(under) if under else min(fits)


def _pad_tris(tris, bt: int, cap_e: int):
    """Pad the triangle dimension to a multiple of ``bt`` with rows on the
    per-lane drop slot ``cap_e`` (the bucket builders' padding convention —
    the kernel's one-hot is all-zero there, so padding rows are inert)."""
    B, T = tris.shape[0], tris.shape[1]
    T_pad = max(bt, -(-T // bt) * bt)
    if T_pad == T:
        return jnp.asarray(tris, jnp.int32)
    pad = jnp.full((B, T_pad - T, 3), cap_e, jnp.int32)
    return jnp.concatenate([jnp.asarray(tris, jnp.int32), pad], axis=1)


@partial(jax.jit, static_argnames=("bt", "interpret"), donate_argnums=(0,))
def _peel_classes_fused_impl(sup_b, tris_b, alive_b, *, bt, interpret):
    B, cap_e = sup_b.shape
    T = tris_b.shape[1]

    def cond(state):
        alive, _, _, _, _ = state
        return jnp.any(alive > 0)

    def body(state):
        alive, sup, phi, k, st = state
        rm = jnp.where(sup <= k[:, None] - 2, alive, 0)
        nf = jnp.sum(rm, axis=1)
        has_rm = nf > 0
        lane_alive = jnp.sum(alive, axis=1) > 0
        min_sup = jnp.min(jnp.where(alive > 0, sup, _BIG), axis=1)
        k2 = jnp.where(lane_alive & ~has_rm,
                       jnp.maximum(k + 1, min_sup + 2), k)
        phi2 = jnp.where(rm > 0, k[:, None], phi)
        sup2, alive2 = fk.fused_round(sup, alive, rm, tris_b,
                                      bt=bt, interpret=interpret)
        st2 = st.at[:, _S_ROUNDS].add(lane_alive.astype(jnp.int32))
        st2 = st2.at[:, _S_REMOVED].add(nf)
        # dense-sweep accounting: every remove round touches all 3T slots
        st2 = st2.at[:, _S_GATHERED].add(
            jnp.where(has_rm, jnp.int32(3 * T), 0))
        st2 = st2.at[:, _S_MAXF].max(nf)
        return alive2, sup2, phi2, k2, st2

    state0 = (
        jnp.asarray(alive_b, jnp.int32),
        jnp.asarray(sup_b, jnp.int32),
        jnp.zeros((B, cap_e), jnp.int32),
        jnp.full((B,), 2, jnp.int32),
        jnp.zeros((B, N_STATS), jnp.int32),
    )
    _, _, phi, _, st = jax.lax.while_loop(cond, body, state0)
    return phi, st


def peel_classes_fused(sup_b, tris_b, alive_b, *, bt="auto",
                       interpret: bool | None = None):
    """Trussness of every lane via fused lockstep rounds.

    Same contract as ``peel._peel_classes_vmapped``: (B, E) sup/alive and
    (B, T, 3) triangles in, (phi (B, E), stats (B, N_STATS)) out — but one
    kernel invocation per round and no incidence CSR inputs.  ``interpret``
    defaults to True off-TPU (interpreter parity path).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    cap_e = int(sup_b.shape[1])
    bt = resolve_tile(cap_e, int(tris_b.shape[1]), bt, interpret)
    tris_p = _pad_tris(jnp.asarray(tris_b, jnp.int32), bt, cap_e)
    return _peel_classes_fused_impl(
        jnp.asarray(sup_b, jnp.int32), tris_p,
        jnp.asarray(alive_b, jnp.int32), bt=bt, interpret=bool(interpret))


@partial(jax.jit, static_argnames=("bt", "interpret"))
def _peel_threshold_fused_impl(sup, tris, alive, removable, thresh, *,
                               bt, interpret):
    def cond(state):
        alive_c, sup_c = state
        return jnp.any((alive_c > 0) & (removable > 0) & (sup_c <= thresh))

    def body(state):
        alive_c, sup_c = state
        rm = jnp.where((removable > 0) & (sup_c <= thresh), alive_c, 0)
        sup2, alive2 = fk.fused_round(sup_c, alive_c, rm, tris,
                                      bt=bt, interpret=interpret)
        return alive2, sup2

    alive_f, _ = jax.lax.while_loop(cond, body, (alive, sup))
    return alive_f


def peel_threshold_fused(sup, tris, removable, thresh, alive0, *, bt="auto",
                         interpret: bool | None = None):
    """Single-level candidate peel (both OOC drivers' per-k kernel) via
    fused rounds.  (E,) sup / removable / alive0 and (T, 3) triangles in,
    final (E,) int32 alive mask out — no incidence CSR needed."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    cap_e = int(sup.shape[0])
    bt = resolve_tile(cap_e, int(tris.shape[0]), bt, interpret)
    tris_p = _pad_tris(jnp.asarray(tris, jnp.int32)[None], bt, cap_e)
    alive_f = _peel_threshold_fused_impl(
        jnp.asarray(sup, jnp.int32)[None], tris_p,
        jnp.asarray(alive0, jnp.int32)[None],
        jnp.asarray(removable, jnp.int32)[None],
        jnp.int32(thresh), bt=bt, interpret=bool(interpret))
    return alive_f[0]
