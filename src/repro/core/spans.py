"""Named host spans of the truss program, on the profiler's clock.

A span is a ``jax.profiler.TraceAnnotation`` called ``truss.<name>``.  With
no profiler running it records nothing and costs about a microsecond; under
``jax.profiler.trace`` it lands on the calling thread's line of the host
plane, on the clock the device planes are aligned to, and its counts become
the event's stats.  ``NAMES`` is the contract trace readers rely on:

  job              one public call (``truss_decompose``,
                   ``top_down_decompose``): engine, n, m (edge rows given)
  build_graph      ``graph.build_graph``: m
  list_triangles   host triangle listing: triangles
  edge_support     host support counting: m
  incidence        ``support.triangle_incidence_np``: slots
  upload           a host-to-device copy of graph data (``upload``): bytes
  round_build      one out-of-core partition round on the host: round,
                   parts, lanes, padded_slots, real_edges
  support_credit   one round of top-down stage 1's triangle credits:
                   triangles
  candidate_build  one stage-2 / top-down level's candidate: k, edges
  prune            top-down Steps 7-9, classified edges off every undecided
                   triangle: k, pruned (on the half after the wait)
  dispatch         enqueue of a device peel: engine, lanes, new_compile;
                   over a mesh also devices, and pad_lanes (engine
                   "mesh", a bucket's lanes split over the chips: its
                   lanes holding no live edge) or tri_rows (engine
                   "mesh_threshold", a triangle-sharded candidate peel:
                   triangle rows a device holds)
  device_wait      the host blocked on a device result (and its copy back);
                   the in-memory wait counts resumes
  retry            one attempt of a retry ladder: stage, attempt

Counts are plain Python values the host already holds; a device array as a
count would make the span wait on the device, so ``span`` refuses it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

PREFIX = "truss."
NAMES = ("job", "build_graph", "list_triangles", "edge_support", "incidence",
         "upload", "round_build", "support_credit", "candidate_build",
         "prune", "dispatch", "device_wait", "retry")
_PLAIN = (int, float, str, bool)


def _plain(counts: dict) -> dict:
    for key, value in counts.items():
        if type(value) not in _PLAIN:
            raise TypeError(
                f"span count {key}={value!r} is a {type(value).__name__}; "
                "counts must be plain int, float, str or bool")
    return counts


class Span(jax.profiler.TraceAnnotation):
    """A ``truss.*`` span; ``count`` adds counts known only at its end."""

    def count(self, **counts) -> None:
        self.set_metadata(**_plain(counts))


def span(name: str, **counts) -> Span:
    """The span ``truss.<name>`` with ``counts``, to use as a context."""
    if name not in NAMES:
        raise ValueError(f"unknown span {name!r}; known: {NAMES}")
    return Span(PREFIX + name, **_plain(counts))


def upload(*arrays) -> tuple[list, int]:
    """``jnp.asarray`` of each array inside one ``truss.upload`` span.
    Returns the device arrays and the bytes copied, the host arrays'
    ``nbytes``: an array already on the device copies nothing."""
    nbytes = int(sum(a.nbytes for a in arrays if isinstance(a, np.ndarray)))
    with span("upload", bytes=nbytes):
        return [jnp.asarray(a) for a in arrays], nbytes


def job(engine: str):
    """Decorator of a public entry point ``fn(n, edges, ...)``: each call
    runs inside one ``truss.job`` span.  ``engine`` is the count given
    where the call passes no ``engine=``.  The undecorated function is
    ``fn.__wrapped__``, for a call made from inside another job."""
    def wrap(fn):
        @functools.wraps(fn)
        def call(n, edges, *args, **kwargs):
            with span("job", engine=str(kwargs.get("engine", engine)),
                      n=int(n), m=len(edges)):
                return fn(n, edges, *args, **kwargs)
        return call
    return wrap
