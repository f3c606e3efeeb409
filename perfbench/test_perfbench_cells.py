"""Each cell of BENCHMARK.json at a tiny scale on the CPU.

* Its job, run once through the program's entry point, gives the plain
  reference's answer.
* The reference agrees with the program's serial oracle, a second
  witness; the control (one removal sweep per level) does not.
* A whole run (set-up, window, check) with no chip and no trace says
  ``correct``; with the timed path broken underneath it says not correct,
  once for each fault a one-chip decomposition can have.  (The exchange
  between chips does not exist on one chip.)
"""

from __future__ import annotations

import time

import numpy as np
import pytest

from perfbench import control, graph500, harness, reference

TINY_SCALE = 7
BENCH = harness.benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]


def _tiny(cell_name: str):
    cell = harness.cell(BENCH, cell_name)
    cfg = dict(harness.config(BENCH, cell["config"]), scale=TINY_SCALE)
    return cell, cfg, harness.traffic(cell["traffic"])


def _measure(cell_name: str, seed: int = 2**33 + 5) -> dict:
    cell, cfg, mix = _tiny(cell_name)
    return harness.measure(cell, cfg, mix, seed, 0.2, False,
                           time.perf_counter(), None,
                           harness.metrics_for(BENCH, cell_name, False))


def test_benchmark_names_every_file():
    for c in BENCH["configs"]:
        cfg = harness.load_json(c["file"])
        assert cfg["name"] == c["name"]
    for w in BENCH["workloads"]:
        harness.load_module("jobs", harness.traffic(w["traffic"])["job"])
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert callable(harness.load_module("metrics", m["name"]).read)


@pytest.mark.parametrize("cell_name", CELLS)
def test_job_matches_reference(cell_name):
    cell, cfg, mix = _tiny(cell_name)
    jobmod = harness.load_module("jobs", mix["job"])
    n, edges, given = graph500.graph(cfg, 12345)
    answer, _ = jobmod.make(cfg, mix, n, given)()
    assert jobmod.mismatches(answer, reference.phi(n, edges), mix) == 0


@pytest.mark.parametrize("cell_name", CELLS)
def test_whole_run_is_correct(cell_name):
    res = _measure(cell_name)
    assert res["correct"] is True
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"job_s", "setup_s"}
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("seed", [0, 1, 2**33 + 1])
@pytest.mark.parametrize("cell_name", CELLS)
def test_reference_agrees_with_serial_oracle_and_control_does_not(
        cell_name, seed):
    from repro.core.serial import alg2_truss

    cell, cfg, mix = _tiny(cell_name)
    cfg["scale"] = 9
    n, edges, _ = graph500.graph(cfg, seed)
    ref = reference.phi(n, edges)
    assert np.array_equal(ref, alg2_truss(n, edges))
    jobmod = harness.load_module("jobs", mix["job"])
    assert jobmod.mismatches(control.answer(mix, n, edges), ref, mix) > 0


def test_graph_depends_on_seed_only_through_labels_and_order():
    cfg = dict(harness.config(BENCH, "graph500-s13-inmem"), scale=9)
    n, a, given_a = graph500.graph(cfg, 1)
    _, b, given_b = graph500.graph(cfg, 2)
    assert len(a) == len(b) and not np.array_equal(a, b)
    assert np.array_equal(np.sort(reference.phi(n, a)),
                          np.sort(reference.phi(n, b)))
    assert np.array_equal(graph500.canonical(n, given_a), a)
    ooc = dict(harness.config(BENCH, "graph500-s11-ooc"), scale=9)
    _, c, given_c = graph500.graph(ooc, 1)
    _, d, given_d = graph500.graph(ooc, 2)
    assert np.array_equal(c, d) and not np.array_equal(given_c, given_d)


def _unchanged(phi, _n, _edges, _real):
    return np.zeros_like(phi)


def _half_left_out(phi, n, edges, real):
    """Only the first half of the edge list reaches the decomposition."""
    half = np.asarray(edges)[: len(edges) // 2]
    part = real(n, half)
    canon = graph500.canonical(n, np.asarray(edges))
    sub = graph500.canonical(n, half)
    out = np.zeros_like(phi)
    out[np.searchsorted(canon[:, 0].astype(np.int64) * n + canon[:, 1],
                        sub[:, 0].astype(np.int64) * n + sub[:, 1])] = part
    return out


def _answer_altered(phi, _n, _edges, _real):
    out = phi.copy()
    out[np.argmax(out)] += 1    # an edge of the densest class
    return out


FAULTS = {"state_unchanged": _unchanged, "half_left_out": _half_left_out,
          "answer_altered": _answer_altered}


def _break(monkeypatch, cell_name, fault):
    from repro.core import peel, top_down

    if harness.traffic(harness.cell(BENCH, cell_name)["traffic"])["job"] \
            == "top_classes":
        real = top_down.top_down_decompose

        def broken(n, edges, **kw):
            res = real(n, edges, **kw)
            res.phi = fault(np.asarray(res.phi), n, edges,
                            lambda n_, e_: real(n_, e_, **kw).phi)
            return res

        monkeypatch.setattr(top_down, "top_down_decompose", broken)
    else:
        real = peel.truss_decompose

        def broken(n, edges, with_stats=False, **kw):
            phi, stats = real(n, edges, with_stats=True, **kw)
            return fault(np.asarray(phi), n, edges,
                         lambda n_, e_: real(n_, e_, **kw)), stats

        monkeypatch.setattr(peel, "truss_decompose", broken)


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell_name", CELLS)
def test_broken_timed_path_is_not_correct(monkeypatch, cell_name, fault):
    _break(monkeypatch, cell_name, FAULTS[fault])
    res = _measure(cell_name)
    assert res["correct"] is False
    assert res["checks"]["edges_wrong"]["value"] > 0


def test_peel_step_returning_its_state_is_not_correct(monkeypatch):
    """The in-memory frontier loop, broken where it steps: each call hands
    back the state it was given."""
    import jax.numpy as jnp

    from repro.core import peel

    def unchanged(sup, tris, indptr, tids, alive, phi, k, stats, **_):
        return alive, sup, phi, k, stats, jnp.bool_(False)

    monkeypatch.setattr(peel, "peel_classes_fixedcap", unchanged)
    res = _measure("graph500-s13-inmem.full")
    assert res["correct"] is False


def test_job_that_raises_is_failed_and_not_correct(monkeypatch):
    from repro.core import peel

    def boom(*_a, **_k):
        raise RuntimeError("device lost")

    monkeypatch.setattr(peel, "truss_decompose", boom)
    res = _measure("graph500-s13-inmem.full")
    assert res["correct"] is False
    assert res["failed"] == res["attempted"] >= 1
    assert res["checks"]["jobs_unanswered"]["value"] == res["attempted"] + 1
    assert "job_s" not in res["metrics"]


def test_allocator_thresholds_pin_on_glibc():
    from perfbench import run

    assert run.fix_allocator() is True


def test_degraded_job_is_failed_and_not_correct(monkeypatch):
    """A job that took a retry or a degradation ran another engine than the
    cell claims: it is failed, left out of ``job_s``, and not correct."""
    from repro.core import peel

    real = peel.truss_decompose

    def degraded(n, edges, with_stats=False, **kw):
        phi, stats = real(n, edges, with_stats=True, **kw)
        stats.retries += 1
        return phi, stats

    monkeypatch.setattr(peel, "truss_decompose", degraded)
    res = _measure("graph500-s11-ooc.full")
    assert res["correct"] is False
    assert res["checks"]["edges_wrong"]["value"] == 0
    assert res["checks"]["jobs_degraded"]["value"] == res["attempted"] + 1
    assert res["failed"] == res["attempted"] >= 1
    assert "job_s" not in res["metrics"]


@pytest.mark.parametrize("cell_name", CELLS)
def test_repeated_job_recomputes(monkeypatch, cell_name):
    """Jobs of a window share compiled programs and nothing else: each call
    lists triangles (in memory) or builds partition rounds (out of core)
    anew and returns a fresh answer."""
    from repro.core import partition, peel

    cell, cfg, mix = _tiny(cell_name)
    module, name = ((peel, "list_triangles_np")
                    if cfg["memory"] == "in_memory"
                    else (partition, "build_partition_batch"))
    real = getattr(module, name)
    calls = []
    monkeypatch.setattr(module, name,
                        lambda *a, **k: calls.append(1) or real(*a, **k))
    jobmod = harness.load_module("jobs", mix["job"])
    n, _, given = graph500.graph(cfg, 77)
    run = jobmod.make(cfg, mix, n, given)
    first, _ = run()
    per_job = len(calls)
    second, _ = run()
    assert per_job > 0 and len(calls) == 2 * per_job
    a, b = (first, second) if mix["job"] == "decompose" else \
        (first[0], second[0])
    assert a is not b and np.array_equal(a, b)


def test_traced_run_without_a_chip_reads_no_device_metric():
    cell_name = "graph500-s11-ooc.full"
    cell, cfg, mix = _tiny(cell_name)
    res = harness.measure(cell, cfg, mix, 9, 0.2, True, time.perf_counter(),
                          None, harness.metrics_for(BENCH, cell_name, True))
    assert res["correct"] is True
    # counters are read; the trace of a CPU run holds no TPU plane
    assert set(res["metrics"]) == {"ooc_batches", "lane_padding_waste"}
    assert "busy_s" not in res["device"] and res["device"]["window_s"] > 0
    assert res["breakdown"]["device_ops"] == []
    assert len(res["breakdown"]["idle_gaps"]) == 1
