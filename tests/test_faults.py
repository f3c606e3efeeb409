"""Deterministic fault injection: the FaultPlan machinery itself, and the
OOC drivers' retry / degradation ladder under injected device OOMs
(DESIGN.md §12).

The driver matrix injects a retryable OOM at every site × stage the engines
report and asserts the run *self-heals*: phi stays bit-identical to the
serial oracle while ``OocStats.retries`` records the recovery.  A
non-retryable :class:`InjectedFault` must instead propagate unchanged —
retrying a logic error would only mask it.
"""

import contextlib
import warnings

import numpy as np
import pytest

from repro.core import faults
from repro.core.bottom_up import bottom_up_decompose
from repro.core.partition import PartitionBudgetWarning
from repro.core.serial import alg2_truss
from repro.core.top_down import top_down_decompose
from tests.conftest import conformance_corpus

CORPUS = conformance_corpus()
_ORACLE = {name: alg2_truss(n, ce) for name, n, ce in CORPUS}
BUDGET = 64


@contextlib.contextmanager
def _quiet():
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", PartitionBudgetWarning)
        yield


# ---------------------------------------------------------------- plan unit

def test_rule_subset_match_nth_times():
    plan = faults.FaultPlan([faults.FaultRule(
        site=faults.DISPATCH, kind="error", where={"stage": 1},
        nth=2, times=2)])
    fired = 0
    for i in range(6):
        try:
            plan.check(faults.DISPATCH, {"stage": 1, "round": i})
        except faults.InjectedFault:
            fired += 1
    assert fired == 2                      # nth=2 skips the first match
    assert plan.rules[0].seen == 6
    assert [e["ctx"]["round"] for e in plan.log] == [1, 2]


def test_rule_ignores_other_sites_and_ctx():
    plan = faults.FaultPlan([faults.FaultRule(
        site=faults.FINALIZE, kind="error", where={"stage": 2})])
    plan.check(faults.DISPATCH, {"stage": 2})          # wrong site
    plan.check(faults.FINALIZE, {"stage": 1})          # wrong ctx value
    plan.check(faults.FINALIZE, {})                    # key absent
    assert plan.log == []
    with pytest.raises(faults.InjectedFault):
        plan.check(faults.FINALIZE, {"stage": 2, "k": 5})


def test_oom_is_retryable_injected_is_not():
    oom = faults.make_oom("dispatch", {"stage": 1})
    assert faults.is_retryable(oom)
    assert "RESOURCE_EXHAUSTED" in str(oom)
    assert not faults.is_retryable(faults.InjectedFault("x"))
    assert not faults.is_retryable(ValueError("RESOURCE_EXHAUSTED"))
    assert faults.is_retryable(RuntimeError("... Out of memory ..."))
    assert not faults.is_retryable(RuntimeError("shape mismatch"))


def test_real_jax_runtime_error_classification():
    """The runtime's own error type, raised by a real failing dispatch, is
    what the retry ladder sees on the chip: only its OOM message retries."""
    import jax
    import jax.numpy as jnp

    def failing(text):
        def cb(x):
            raise MemoryError(text)

        fn = jax.jit(lambda x: jax.pure_callback(
            cb, jax.ShapeDtypeStruct((3,), jnp.float32), x))
        with pytest.raises(jax.errors.JaxRuntimeError) as info:
            jax.block_until_ready(fn(jnp.ones(3, jnp.float32)))
        return info.value

    assert isinstance(faults.make_oom("dispatch", {}),
                      jax.errors.JaxRuntimeError)
    assert faults.is_retryable(failing("RESOURCE_EXHAUSTED: vmem"))
    assert not faults.is_retryable(failing("shape mismatch"))


def test_no_plan_is_noop_and_scoped():
    faults.check(faults.DISPATCH, stage=1)             # no plan: no-op
    plan = faults.FaultPlan([faults.FaultRule(site=faults.DISPATCH,
                                              kind="error")])
    with faults.active(plan):
        with pytest.raises(faults.InjectedFault):
            faults.check(faults.DISPATCH)
    faults.check(faults.DISPATCH)                      # uninstalled again


def test_unknown_kind_raises():
    plan = faults.FaultPlan([faults.FaultRule(site="x", kind="nonsense")])
    with pytest.raises(ValueError, match="unknown fault kind"):
        plan.check("x", {})


# ------------------------------------------------------- driver self-healing

@pytest.mark.parametrize("name,n,ce", CORPUS, ids=[c[0] for c in CORPUS])
@pytest.mark.parametrize("site,where", [
    (faults.DISPATCH, {"stage": 1}),
    (faults.DISPATCH, {"stage": 2}),
    (faults.FINALIZE, {"stage": 1}),
], ids=["dispatch-s1", "dispatch-s2", "finalize-s1"])
def test_bottom_up_recovers_from_oom(name, n, ce, site, where):
    plan = faults.FaultPlan([faults.FaultRule(site=site, kind="oom",
                                              where=dict(where), times=1)])
    with _quiet(), faults.active(plan):
        res = bottom_up_decompose(n, ce, budget=BUDGET)
    assert (res.phi == _ORACLE[name]).all(), name
    if plan.log:                 # graph actually exercised the site
        assert res.stats.retries >= 1, name


@pytest.mark.parametrize("name,n,ce", CORPUS, ids=[c[0] for c in CORPUS])
@pytest.mark.parametrize("site", [faults.DISPATCH, faults.FINALIZE],
                         ids=["dispatch", "finalize"])
def test_top_down_recovers_from_oom(name, n, ce, site):
    plan = faults.FaultPlan([faults.FaultRule(
        site=site, kind="oom", where={"stage": "td"}, times=1)])
    with _quiet(), faults.active(plan):
        res = top_down_decompose(n, ce, budget=BUDGET)
    assert (res.phi == _ORACLE[name]).all(), name
    if plan.log:
        assert res.stats.retries >= 1, name


def test_repeated_oom_walks_degradation_ladder():
    """Persistent stage-1 OOM: lane splits, then budget halving, then the
    failure propagates once the round budget floor is hit."""
    name, n, ce = CORPUS[0]
    plan = faults.FaultPlan([faults.FaultRule(
        site=faults.DISPATCH, kind="oom", where={"stage": 1},
        times=10**6)])
    with _quiet(), faults.active(plan):
        with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
            bottom_up_decompose(n, ce, budget=256)
    # the ladder kept retrying before giving up: lane splits re-dispatched
    # (retry > 0 in the context) and the budget-halving restarts re-entered
    # the round loop at least twice (256 -> 128 -> 64 floor)
    assert len(plan.log) >= 6
    assert any(e["ctx"].get("retry", 0) for e in plan.log)


def test_oom_then_recovery_mid_ladder():
    """OOM that clears after a few firings: the run degrades part-way down
    the ladder and still finishes exact."""
    name, n, ce = CORPUS[0]
    plan = faults.FaultPlan([faults.FaultRule(
        site=faults.DISPATCH, kind="oom", where={"stage": 1}, times=3)])
    with _quiet(), faults.active(plan):
        res = bottom_up_decompose(n, ce, budget=256)
    assert (res.phi == _ORACLE[name]).all()
    assert res.stats.retries >= 2
    assert res.stats.degraded >= 1       # a budget restart or mesh drop


@pytest.mark.parametrize("engine", ["bottom-up", "top-down"])
def test_injected_hard_error_propagates(engine):
    name, n, ce = CORPUS[0]
    fn = bottom_up_decompose if engine == "bottom-up" else top_down_decompose
    where = {"stage": 1} if engine == "bottom-up" else {"stage": "td"}
    plan = faults.FaultPlan([faults.FaultRule(
        site=faults.DISPATCH, kind="error", where=where)])
    with _quiet(), faults.active(plan):
        with pytest.raises(faults.InjectedFault):
            fn(n, ce, budget=BUDGET)
    # never reported as a retry: the drivers classified it non-retryable
    stats_retries = [e for e in plan.log if e["ctx"].get("retry", 0)]
    assert stats_retries == []


def test_partitioner_site_crash_propagates():
    name, n, ce = CORPUS[0]
    plan = faults.FaultPlan([faults.FaultRule(
        site=faults.PARTITIONER, kind="crash", nth=2)])
    with _quiet(), faults.active(plan):
        with pytest.raises(OSError, match="injected crash"):
            bottom_up_decompose(n, ce, budget=BUDGET)
    assert plan.log and plan.log[0]["ctx"]["round"] >= 1
