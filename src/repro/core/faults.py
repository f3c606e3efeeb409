"""Deterministic fault injection for the out-of-core engines (DESIGN.md §12).

Long out-of-core decompositions fail in a handful of well-defined places:
a device peel OOMs at dispatch, a :class:`~repro.core.peel.PendingPeel`
finalize surfaces a ``JaxRuntimeError`` one round late, a checkpoint write
is torn by a crash, or the process dies outright between rounds.  Testing
the recovery paths by monkeypatching each call site separately sprawls and
drifts; this module instead names the injection sites once —

* ``"dispatch"``      — entry of a device peel (``peel_classes_batched`` /
  ``local_threshold_peel``), before any device work is enqueued;
* ``"finalize"``      — inside ``PendingPeel.result()``, before the blocking
  device readback (a failure here poisons the handle exactly like a real
  asynchronous device error surfacing at block time);
* ``"checkpoint-write"`` — inside ``checkpoint.manager.save`` after the
  array payload is on disk but before the manifest/rename commit point;
* ``"partitioner"``   — start of each partition round, before the
  partitioner runs (the natural host-side "crash between rounds" site)
* ``"support"``       — entry of a triangle-credit fold in
  ``partitioned_support`` (per bucket, before any credit is scattered into
  the global ``sup`` — the credits are NOT idempotent, so the retry ladder
  must recompute a failed bucket from scratch rather than re-fold);
* ``"chunk-read"``    — inside ``store.ChunkedDiskStore._read_chunk``,
  before a graph chunk is read back from disk;
* ``"chunk-write"``   — inside ``store.ChunkedDiskStore._write_chunk``,
  before a graph chunk spill commits (tmp+rename, same atomicity contract
  as the checkpoint writer — a ``kill`` here is the crash-mid-spill case)
* ``"maintain"``      — start of each single-edit step inside
  ``maintain.truss_maintain``, after the previous edit's φ committed to the
  journal but before the next edit mutates the working graph (the
  crash-mid-maintenance site of DESIGN.md §16)

— and lets a test describe failures declaratively as a :class:`FaultPlan`:
*at the 2nd stage-1 dispatch of round 3, raise a device OOM, twice*.  Rules
match on the site name plus any subset of the context keys the site reports
(stage, round, level, retry, step, ...), fire deterministically, and record
what fired in ``plan.log`` so tests assert on the injection itself, not
just its fallout.

Fault kinds:

* ``"oom"``      — raise a ``JaxRuntimeError`` whose message carries
  ``RESOURCE_EXHAUSTED`` (exactly what a real device OOM surfaces);
  classified retryable by :func:`is_retryable`, so the drivers' rebuild /
  lane-split / degrade ladder engages.
* ``"error"``    — raise :class:`InjectedFault` (NOT retryable): models a
  poisoned computation / host bug; drivers must propagate it.
* ``"truncate"`` — at the checkpoint-write site only: truncate the array
  payload on disk and return, so the snapshot *commits corrupted* — the
  manifest checksum must catch it at restore time and fall back.
* ``"crash"``    — raise ``OSError`` at the site: at the checkpoint-write
  site this dies before the rename, leaving only a ``.tmp`` directory (the
  atomicity contract's crash-mid-write case).
* ``"kill"``     — ``SIGKILL`` the current process: the crash-and-resume
  subprocess smoke (no atexit, no finally blocks — the real thing).

The active plan is process-global and installed with the :func:`active`
context manager (tests) or :func:`install` (subprocess drivers).  With no
plan installed every ``check`` is a no-op costing one attribute load, so
production runs pay nothing.
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import signal
from typing import Any, Dict, List, Optional

import jax

# site names (any string is accepted; these are the ones the engines report)
DISPATCH = "dispatch"
FINALIZE = "finalize"
CHECKPOINT_WRITE = "checkpoint-write"
PARTITIONER = "partitioner"
SUPPORT = "support"
CHUNK_READ = "chunk-read"
CHUNK_WRITE = "chunk-write"
MAINTAIN = "maintain"

_RETRYABLE_MARKERS = ("RESOURCE_EXHAUSTED", "OUT_OF_MEMORY", "out of memory",
                      "Out of memory")


class InjectedFault(RuntimeError):
    """A deliberately injected non-retryable failure (kind="error")."""


def make_oom(site: str, ctx: Dict[str, Any]) -> BaseException:
    """A ``jax.errors.JaxRuntimeError`` — the type real device failures
    raise — indistinguishable (to the retry classifier) from a real device
    allocation failure."""
    return jax.errors.JaxRuntimeError(
        f"RESOURCE_EXHAUSTED: injected device OOM at site={site!r} "
        f"ctx={ctx!r}")


def is_retryable(exc: BaseException) -> bool:
    """Whether a failure is worth a rebuild-and-retry (DESIGN.md §12).

    Retryable: device resource exhaustion — a ``JaxRuntimeError`` (or any
    ``RuntimeError``) whose message carries a RESOURCE_EXHAUSTED / OOM
    marker.  Shrinking the dispatch (lane split, mesh drop, smaller rounds)
    can genuinely fix these.  Everything else — :class:`InjectedFault`,
    shape errors, poisoned ``PendingPeel`` handles — signals a logic error
    where a retry would only mask the bug, so drivers propagate it.
    """
    if isinstance(exc, InjectedFault):
        return False
    if not isinstance(exc, RuntimeError):
        return False
    text = str(exc)
    return any(marker in text for marker in _RETRYABLE_MARKERS)


@dataclasses.dataclass
class FaultRule:
    """One deterministic failure: fire ``times`` times starting at the
    ``nth`` call that matches ``site`` + ``where``.

    ``where`` is a subset match against the context keys the site reports
    (e.g. ``{"stage": 1, "round": 3}``); an empty ``where`` matches every
    call at the site.  Sites report a ``retry`` key on re-dispatches, so a
    rule with ``times > 1`` and no ``where`` constraint on ``retry`` keeps
    failing retries too — that is how tests drive the drivers down the
    whole degradation ladder.
    """

    site: str
    kind: str = "oom"               # oom | error | truncate | crash | kill
    where: Dict[str, Any] = dataclasses.field(default_factory=dict)
    nth: int = 1                    # 1-based index of the first firing match
    times: int = 1                  # how many matching calls to fail
    seen: int = 0                   # matching calls observed (internal)
    fired: int = 0                  # failures delivered (internal)

    def matches(self, site: str, ctx: Dict[str, Any]) -> bool:
        if site != self.site:
            return False
        return all(k in ctx and ctx[k] == v for k, v in self.where.items())


@dataclasses.dataclass
class FaultPlan:
    """An ordered set of :class:`FaultRule`; ``log`` records every firing
    as ``(site, kind, ctx)`` for test assertions."""

    rules: List[FaultRule]
    log: List[Dict[str, Any]] = dataclasses.field(default_factory=list)

    def check(self, site: str, ctx: Dict[str, Any]) -> None:
        for rule in self.rules:
            if not rule.matches(site, ctx):
                continue
            rule.seen += 1
            if rule.seen < rule.nth or rule.fired >= rule.times:
                continue
            rule.fired += 1
            self.log.append({"site": site, "kind": rule.kind, "ctx": dict(ctx)})
            self._deliver(rule, site, ctx)
            return  # at most one failure per call

    def _deliver(self, rule: FaultRule, site: str, ctx: Dict[str, Any]):
        if rule.kind == "oom":
            raise make_oom(site, ctx)
        if rule.kind == "error":
            raise InjectedFault(
                f"injected non-retryable fault at site={site!r} ctx={ctx!r}")
        if rule.kind == "crash":
            raise OSError(f"injected crash at site={site!r} ctx={ctx!r}")
        if rule.kind == "kill":
            os.kill(os.getpid(), signal.SIGKILL)  # no cleanup, by design
        if rule.kind == "truncate":
            path = ctx.get("path")
            if path and os.path.exists(path):
                size = os.path.getsize(path)
                with open(path, "r+b") as f:
                    f.truncate(max(size // 2, 1))
            return  # torn write: the save commits a corrupted payload
        if rule.kind not in ("oom", "error", "crash", "kill", "truncate"):
            raise ValueError(f"unknown fault kind {rule.kind!r}")


_ACTIVE: Optional[FaultPlan] = None


def install(plan: Optional[FaultPlan]) -> None:
    """Install ``plan`` process-wide (None uninstalls).  Subprocess drivers
    use this; tests prefer the :func:`active` context manager."""
    global _ACTIVE
    _ACTIVE = plan


@contextlib.contextmanager
def active(plan: FaultPlan):
    """Scoped installation: the plan is active inside the with-block only."""
    global _ACTIVE
    prev = _ACTIVE
    _ACTIVE = plan
    try:
        yield plan
    finally:
        _ACTIVE = prev


def check(site: str, **ctx: Any) -> None:
    """The injection site hook: no-op unless a plan is installed."""
    plan = _ACTIVE
    if plan is not None:
        plan.check(site, ctx)
