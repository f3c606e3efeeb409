"""A full decomposition out of core with every partition round spread over
a mesh of chips: ``truss_decompose(engine="bottom-up", memory_budget=...,
kernel=..., mesh=..., mesh_axis=...)`` at the configuration's part budget.

The mesh is made once, over the first ``cfg["chips"]`` devices, on the
configuration's one mesh axis (``mesh_axes``).  On a TPU with fewer chips
``make`` raises; without a TPU (the CPU tests) it takes the devices there
are, which still runs the program's shard_map path.  A job whose
``OocStats.devices`` is not the mesh's size ran on another layout than the
cell claims, and raises.  The answer is φ, compared as ``decompose``
compares it.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from perfbench.jobs import decompose

mismatches = decompose.mismatches

# what the job and the cell's metrics read off the program's OocStats
COUNTERS = ("devices", "mesh_lanes", "mesh_pad_lanes")


def mesh_of(cfg: dict):
    """A one-axis mesh over the first ``cfg["chips"]`` devices."""
    import jax

    axes = tuple(cfg["mesh_axes"])
    if len(axes) != 1:
        raise ValueError(f"one mesh axis is supported, the configuration "
                         f"names {axes}")
    want = int(cfg["chips"])
    devices = jax.devices()
    if len(devices) < want:
        if devices[0].platform == "tpu":
            raise RuntimeError(f"the configuration spans {want} chips, JAX "
                               f"found {len(devices)}")
        want = len(devices)
    return jax.sharding.Mesh(np.array(devices[:want]), axes,
                             axis_types=(jax.sharding.AxisType.Auto,))


def make(cfg: dict, traffic: dict, n: int, edges: np.ndarray):
    from repro.core import peel
    from repro.core.bottom_up import OocStats

    known = {f.name for f in dataclasses.fields(OocStats)}
    missing = [c for c in COUNTERS if c not in known]
    if missing:
        raise RuntimeError(
            f"the program under test does not count {', '.join(missing)} "
            "in OocStats; a job over a mesh reads them")
    mesh = mesh_of(cfg)
    kwargs = {"engine": "bottom-up",
              "memory_budget": decompose.memory_budget(cfg["part_budget"], n,
                                                       edges),
              "kernel": cfg["kernel"], "mesh": mesh,
              "mesh_axis": mesh.axis_names[0]}

    def run():
        phi, stats = peel.truss_decompose(n, edges, with_stats=True, **kwargs)
        if stats.devices != mesh.size:
            raise RuntimeError(f"the job spanned {stats.devices} devices, "
                               f"the mesh holds {mesh.size}")
        return np.asarray(phi), stats

    return run
