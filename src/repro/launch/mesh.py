"""Host meshes over the local devices for the truss engines' ``mesh=``.

Defined as functions so importing this module never touches jax device
state.
"""

from __future__ import annotations

import jax

_AUTO = jax.sharding.AxisType.Auto


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
