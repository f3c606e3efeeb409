#!/usr/bin/env python3
"""Run one benchmark cell once on the chip it is started on.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Run from the root of a checkout (``BENCHMARK.json`` names the cells).
The last line of standard output is the result, one JSON object; the
numbers that decide ``correct`` are the last lines of standard error and
the last key of the result.  Exits 3, printing no result, when JAX finds
no TPU or fewer chips than the cell asks for, and 2 when the program
under test (``src/repro``) is not in the checkout.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def fix_allocator() -> bool:
    """Pin glibc malloc's mmap and trim thresholds; False where the C
    library is not glibc.

    glibc moves both as a process frees large blocks, so whether a job's
    large temporaries reuse the heap's pages or map fresh ones, a page
    fault a page, depends on what earlier jobs happened to free: on a TPU
    v5e host the in-memory job's host work took either about 4.5 or about
    5.5 CPU seconds by that alone, and runs split between the two.  Pinned,
    blocks up to 32 MiB (glibc's largest threshold on 64 bits) come from
    the heap and freed memory stays mapped, the state the process reaches
    by itself in some runs, so every job after the warm one runs alike.
    """
    import ctypes

    try:
        mallopt = ctypes.CDLL("libc.so.6").mallopt
    except (OSError, AttributeError):
        return False
    m_trim_threshold, m_mmap_threshold = -1, -3
    return bool(mallopt(m_mmap_threshold, 32 << 20)
                and mallopt(m_trim_threshold, 1 << 30))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative integer")

    pinned = fix_allocator()
    sys.path.insert(0, ROOT)
    from perfbench import harness

    bench = harness.benchmark(ROOT)
    cell = harness.cell(bench, args.workload)
    cfg = harness.config(bench, cell["config"], ROOT)
    mix = harness.traffic(cell["traffic"])
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print("perfbench: the program under test (src/repro) is not in "
              "this checkout", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))

    import jax

    from perfbench import device

    cache = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
             or os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    try:
        stamp = device.stamp(int(cell["chips"]))
    except device.NoChip as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 3
    print(f"device: {json.dumps(stamp)}; compile cache {cache}; malloc "
          f"thresholds {'pinned' if pinned else 'left as they are'}",
          file=sys.stderr)

    result = harness.measure(
        cell, cfg, mix, args.seed, args.seconds, bool(args.trace), T_START,
        stamp, harness.metrics_for(bench, args.workload, bool(args.trace)))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
