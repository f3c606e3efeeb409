"""One run of one benchmark cell: set-up, a measured window, the check.

Everything that belongs to one configuration, traffic mix, job kind or
metric is a file of its own, found by name:

* ``configs/<config>.json`` — the deployment: graph family, scale, memory
  regime, guarantees;
* ``traffic/<traffic>.json`` — the mix: which job kind (``"job"``) and its
  parameters;
* ``jobs/<job>.py`` — how a job of that kind calls the program and how
  its answer is compared with the plain reference;
* ``metrics/<metric>.py`` — a reader ``read(run)`` that returns the
  metric's value (or a dict with ``"value"`` and more keys), or None
  where the run holds nothing for it to read.

A cell is a ``workloads`` entry of ``BENCHMARK.json``; its metrics are the
entries of ``end_to_end`` (``--trace 0``) or ``per_layer`` (``--trace 1``)
that list it under ``workloads`` or, without that key, every cell.

The window is a closed loop with one caller: jobs run back to back until
``seconds`` have passed, and the last one is let finish.
"""

from __future__ import annotations

import dataclasses
import glob
import importlib.util
import json
import os
import resource
import shutil
import sys
import tempfile
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# The traced run's host stack sampler: every 20 ms names the idle gaps
# (0.1-0.6 s each) by 5 or more samples; every 2 ms cost an out-of-core
# job 4-5% and raised its idle share by 2 points on a TPU v5e.
SAMPLE_INTERVAL_S = 0.02


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``perfbench/<kind>/<name>.py`` as a module."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} module {name!r} at {path}")
    spec = importlib.util.spec_from_file_location(
        f"perfbench.{kind}.{name.replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(bench: dict, name: str, root: str = ROOT) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return load_json(os.path.join(root, c["file"]))
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def traffic(name: str) -> dict:
    return load_json(os.path.join(HERE, "traffic", name + ".json"))


def metrics_for(bench: dict, cell_name: str, trace: bool) -> list[dict]:
    entries = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in entries
            if cell_name in m.get("workloads", [cell_name])]


@dataclasses.dataclass
class Job:
    seconds: float
    answer: object = None
    counters: object = None
    error: str | None = None
    cpu_s: float = 0.0    # the process's CPU seconds, all threads

    @property
    def degraded(self) -> bool:
        """A retry or a degradation means the run measured another engine
        than the one it claims."""
        c = self.counters
        return bool(getattr(c, "retries", 0) or getattr(c, "degraded", 0))


@dataclasses.dataclass
class Run:
    """What a metric reader may read."""

    cell: dict
    cfg: dict
    traffic: dict
    seed: int
    setup_s: float
    window_s: float = 0.0
    jobs: list = dataclasses.field(default_factory=list)
    compiles_in_window: int = 0
    trace: object = None          # trace.Trace of the window, or None
    trace_window: tuple | None = None   # (start, end) in trace time
    device_kind: str | None = None

    @property
    def completed(self) -> list:
        """Jobs that answered on the engine the cell claims."""
        return [j for j in self.jobs if j.error is None and not j.degraded]


def _cpu() -> float:
    r = resource.getrusage(resource.RUSAGE_SELF)
    return r.ru_utime + r.ru_stime


def _run_job(fn) -> Job:
    c0 = _cpu()
    t0 = time.perf_counter()
    try:
        answer, counters = fn()
        job = Job(time.perf_counter() - t0, answer, counters)
    except Exception:
        job = Job(time.perf_counter() - t0,
                  error=traceback.format_exc(limit=8))
    job.cpu_s = _cpu() - c0
    return job


def measure(cell_entry: dict, cfg: dict, mix: dict, seed: int,
            seconds: float, trace: bool, t_start: float,
            device: dict | None, metric_entries: list[dict],
            log=sys.stderr) -> dict:
    """Set up, run the window, check the answers; returns the result
    line.  ``t_start`` is ``time.perf_counter()`` at process start."""
    import jax

    from perfbench import device as dev
    from perfbench import graph500, reference

    jobmod = load_module("jobs", mix["job"])
    n, edges, given = graph500.graph(cfg, seed)
    fn = jobmod.make(cfg, mix, n, given)
    warm = _run_job(fn)
    if warm.error is not None:
        print(warm.error, file=log)
    run = Run(cell=cell_entry, cfg=cfg, traffic=mix, seed=seed,
              setup_s=time.perf_counter() - t_start,
              device_kind=device["kind"] if device else None)
    print(f"setup: {run.setup_s:.3f} s, n {n}, m {len(edges)}", file=log)

    counter = dev.CompileCounter()
    sampler = tdir = None
    if trace:
        from perfbench.hostsample import Sampler

        tdir = tempfile.mkdtemp(prefix="perfbench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        sampler = Sampler(SAMPLE_INTERVAL_S)
        sampler.start()
        jax.profiler.start_trace(tdir, profiler_options=opts)
    c0 = counter.compiles
    wall0 = time.time_ns()
    w0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("perfbench.window"):
        while time.perf_counter() - w0 < seconds:
            with jax.profiler.TraceAnnotation("perfbench.job"):
                run.jobs.append(_run_job(fn))
    run.window_s = time.perf_counter() - w0
    wall1 = time.time_ns()
    run.compiles_in_window = counter.compiles - c0
    memory_peak = dev.memory_peak_bytes()
    if trace:
        from perfbench import trace as tr

        jax.profiler.stop_trace()
        sampler.stop()
        paths = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                          recursive=True)
        if paths:
            run.trace = tr.load(paths[0])
            span = run.trace.window()
            if span is not None:
                run.trace_window = span
                offset = wall0 - span[0]     # trace time -> wall-clock ns
            elif run.trace.profile_start_ns:
                offset = run.trace.profile_start_ns
                run.trace_window = (wall0 - offset, wall1 - offset)
        shutil.rmtree(tdir, ignore_errors=True)
    print(f"window: {len(run.jobs)} jobs in {run.window_s:.3f} s, "
          f"{run.compiles_in_window} compiles inside it; job seconds "
          f"{[round(j.seconds, 3) for j in run.jobs]}; CPU seconds "
          f"{[round(j.cpu_s, 3) for j in run.jobs]}", file=log)
    for j in run.jobs:
        if j.error is not None:
            print(j.error, file=log)
    fn = None    # the program's state goes before the reference runs

    t_ref = time.perf_counter()
    ref_phi = reference.phi(n, edges)
    print(f"reference: {time.perf_counter() - t_ref:.3f} s", file=log)

    answered = [warm] + run.jobs
    unanswered = sum(j.error is not None for j in answered)
    wrong = max((jobmod.mismatches(j.answer, ref_phi, mix)
                 for j in answered if j.error is None), default=0)
    checks = {"edges_wrong": {"value": wrong, "limit": 0},
              "jobs_unanswered": {"value": unanswered, "limit": 0},
              "jobs_degraded": {"value": sum(j.degraded for j in answered),
                                "limit": 0}}
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    metrics = {}
    for entry in metric_entries:
        got = load_module("metrics", entry["name"]).read(run)
        if got is None:
            continue
        if not isinstance(got, dict):
            got = {"value": got}
        metrics[entry["name"]] = {**got, "unit": entry["unit"]}

    result = {
        "correct": correct,
        "attempted": len(run.jobs),
        "failed": sum(j.error is not None or j.degraded for j in run.jobs),
        "metrics": metrics,
        "device": dict(device or {}, memory_peak_bytes=memory_peak),
    }
    if trace and run.trace is not None and run.trace_window is not None:
        lo, hi = run.trace_window
        busy = run.trace.busy_s(run.trace_window)
        if busy is not None:
            result["device"]["busy_s"] = busy
        result["device"]["window_s"] = (hi - lo) * 1e-9
        result["breakdown"] = {
            "device_ops": run.trace.top_ops(run.trace_window),
            "idle_gaps": run.trace.idle_gaps(
                run.trace_window,
                lambda a, b: sampler.label_between(int(a + offset),
                                                   int(b + offset))),
        }
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=log)
    result["checks"] = checks
    return result
