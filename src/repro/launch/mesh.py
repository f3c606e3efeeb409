"""Production mesh definition (TPU v5e pods).

Single pod: (data=16, model=16) = 256 chips.
Multi-pod:  (pod=2, data=16, model=16) = 512 chips — the 'pod' axis carries
only data parallelism (gradient all-reduce over DCI), model parallelism
stays inside a pod's ICI.

Defined as functions so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before any jax import).
"""

from __future__ import annotations

import jax

_AUTO = jax.sharding.AxisType.Auto


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(_AUTO,) * len(axes))


def make_host_mesh(n: int | None = None, axes=("data", "model")):
    """Small mesh over whatever devices exist (tests / examples)."""
    if n is not None and n <= 0:
        raise ValueError(f"mesh device count must be positive or None "
                         f"(= all local devices), got {n!r}")
    total = len(jax.devices()) if n is None else n
    nd = total
    if len(axes) == 1:
        return jax.make_mesh((nd,), axes, axis_types=(_AUTO,))
    d = 1
    while nd % 2 == 0 and d * d < nd:   # largest power-of-two split
        d *= 2
        nd //= 2
    return jax.make_mesh((d, total // d), axes,
                         axis_types=(_AUTO,) * 2)
