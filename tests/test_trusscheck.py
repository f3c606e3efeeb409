"""trusscheck: golden positive/negative fixtures per rule, the historical
bug reproductions the rules codify (PR 3 / PR 4 / PR 6), --fix round
trips, and the self-run gate (the repo must check clean, DESIGN.md §14).

The fixture tests drive :func:`repro.analysis.check_paths` on snippets
written under a tmp tree shaped like the repo (``src/repro/...``) so the
path-scoped rules (library roots, hot modules, required fault hooks) see
the layout they key on.
"""

import ast
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

from repro import analysis
from repro.analysis.fixes import apply_fixes

REPO_ROOT = Path(__file__).resolve().parents[1]


def _write(tmp_path, source, rel="src/repro/mod.py"):
    f = tmp_path / rel
    f.parent.mkdir(parents=True, exist_ok=True)
    f.write_text(textwrap.dedent(source), encoding="utf-8")
    return f


def _check(tmp_path, source, *, rel="src/repro/mod.py", only=None):
    f = _write(tmp_path, source, rel)
    return f, analysis.check_paths([str(f)], only=only)


def _ids(report):
    return sorted(f.rule_id for f in report.active)


# ---------------------------------------------------------------------------
# TRK102 falsy-zero guards (the PR-3 class)
# ---------------------------------------------------------------------------

PR3_BUG = """
    def truss_decompose(g, memory_budget=None):
        if memory_budget:   # BUG: 0 silently routed to the default engine
            return "out-of-core"
        return "in-memory"
"""

PR3_FIXED = """
    def truss_decompose(g, memory_budget=None):
        if memory_budget is not None and memory_budget <= 0:
            raise ValueError(f"memory_budget must be positive, got "
                             f"{memory_budget!r}")
        if memory_budget is not None:
            return "out-of-core"
        return "in-memory"
"""


def test_trk102_flags_the_pr3_budget_fallback(tmp_path):
    _, report = _check(tmp_path, PR3_BUG, only=["TRK102"])
    assert _ids(report) == ["TRK102"]
    assert "memory_budget" in report.active[0].message


def test_trk102_clean_on_the_pr3_fix(tmp_path):
    _, report = _check(tmp_path, PR3_FIXED, only=["TRK102"])
    assert _ids(report) == []


def test_trk102_flags_or_default_and_annotation_suspects(tmp_path):
    _, report = _check(tmp_path, """
        def pack(lane_capacity=None, depth: int | None = None):
            cap = lane_capacity or 1
            d = depth or 4
            return cap + d
    """, only=["TRK102"])
    # `lane_capacity` matches the name patterns; `depth` only via its
    # `int | None` annotation — both or-defaults swallow a legitimate 0
    assert _ids(report) == ["TRK102", "TRK102"]


def test_trk102_ignores_non_numeric_names(tmp_path):
    _, report = _check(tmp_path, """
        def load(path=None, verbose=False):
            if path:
                return path
            if verbose:
                print("default")
            return "default"
    """, only=["TRK102"])
    assert _ids(report) == []


# ---------------------------------------------------------------------------
# TRK103 bare asserts (the PR-6 class)
# ---------------------------------------------------------------------------

PR6_BUG = """
    def restore(blob):
        assert blob["magic"] == 7, "corrupt snapshot"   # erased under -O
        return blob["state"]
"""


def test_trk103_flags_the_pr6_bare_assert(tmp_path):
    _, report = _check(tmp_path, PR6_BUG, only=["TRK103"])
    assert _ids(report) == ["TRK103"]


def test_trk103_clean_on_typed_raise(tmp_path):
    _, report = _check(tmp_path, """
        def restore(blob):
            if blob["magic"] != 7:
                raise ValueError("corrupt snapshot")
            return blob["state"]
    """, only=["TRK103"])
    assert _ids(report) == []


def test_trk103_scoped_to_library_roots(tmp_path):
    # same assert outside src/repro (tests, scripts) is fine
    _, report = _check(tmp_path, PR6_BUG, rel="scratch/helper.py",
                       only=["TRK103"])
    assert _ids(report) == []


# ---------------------------------------------------------------------------
# TRK101 donation safety (the PR-4 class)
# ---------------------------------------------------------------------------

PR4_BUG = """
    import jax

    peel_step = jax.jit(lambda s, t: s, donate_argnums=(0,))

    def finalize_with_retry(sup, tris):
        for attempt in range(2):
            try:
                return peel_step(sup, tris)   # retry re-donates dead memory
            except RuntimeError:
                continue
        raise RuntimeError("gave up")
"""

PR4_FIXED = """
    import jax

    peel_step = jax.jit(lambda s, t: s, donate_argnums=(0,))

    def finalize_with_retry(sup_host, tris):
        for attempt in range(2):
            try:
                sup = jax.numpy.asarray(sup_host)   # rebuilt every attempt
                return peel_step(sup, tris)
            except RuntimeError:
                continue
        raise RuntimeError("gave up")
"""


def test_trk101_flags_the_pr4_donated_retry(tmp_path):
    _, report = _check(tmp_path, PR4_BUG, only=["TRK101"])
    assert "TRK101" in _ids(report)
    assert "sup" in report.active[0].message


def test_trk101_clean_when_buffer_rebuilt_per_iteration(tmp_path):
    _, report = _check(tmp_path, PR4_FIXED, only=["TRK101"])
    assert _ids(report) == []


def test_trk101_flags_read_after_donation(tmp_path):
    _, report = _check(tmp_path, """
        import jax

        step = jax.jit(lambda x: x, donate_argnums=(0,))

        def drive(buf):
            out = step(buf)
            return out + buf.sum()   # buf was consumed by the donation
    """, only=["TRK101"])
    assert _ids(report) == ["TRK101"]


def test_trk101_fresh_expression_arguments_are_safe(tmp_path):
    _, report = _check(tmp_path, """
        import jax
        import jax.numpy as jnp

        step = jax.jit(lambda x: x, donate_argnums=(0,))

        def drive(host_buf):
            for _ in range(3):
                out = step(jnp.asarray(host_buf))   # new buffer every call
            return out
    """, only=["TRK101"])
    assert _ids(report) == []


def test_trk101_donate_argnames_resolves_to_position(tmp_path):
    # donate_argnames names a position-1 parameter; the read-after-donation
    # must be caught at that position, not at the position-0 convention
    _, report = _check(tmp_path, """
        import jax
        from functools import partial

        @partial(jax.jit, donate_argnames=("state",))
        def advance(cfg, state):
            return state

        def drive(cfg, state):
            out = advance(cfg, state)
            return out + state.sum()   # state was donated at position 1
    """, only=["TRK101"])
    assert _ids(report) == ["TRK101"]
    assert "state" in report.active[0].message


def test_trk101_donate_argnames_no_false_positive_at_position_0(tmp_path):
    # only `state` (position 1) donates; reading the position-0 arg after
    # the call is safe — the old (0,) fallback would flag `cfg` here
    _, report = _check(tmp_path, """
        import jax

        def advance(cfg, state):
            return state

        advance_j = jax.jit(advance, donate_argnames="state")

        def drive(cfg, state):
            out = advance_j(cfg, state)
            return out + cfg.sum()   # cfg (position 0) is NOT donated
    """, only=["TRK101"])
    assert _ids(report) == []


def test_trk101_donate_argnames_resolves_lambda_params(tmp_path):
    _, report = _check(tmp_path, """
        import jax

        step = jax.jit(lambda cfg, buf: buf, donate_argnames=("buf",))

        def drive(cfg, buf):
            out = step(cfg, buf)
            return out + buf.sum()   # buf donated at position 1
    """, only=["TRK101"])
    assert _ids(report) == ["TRK101"]


# ---------------------------------------------------------------------------
# TRK104 recompile hazards (the PR-7 shape discipline)
# ---------------------------------------------------------------------------

def test_trk104_flags_undisciplined_loop_dispatch(tmp_path):
    _, report = _check(tmp_path, """
        def rounds(batches):
            for batch in batches:
                out = peel_classes_batched(batch)
            return out
    """, only=["TRK104"])
    assert _ids(report) == ["TRK104"]
    assert "shape_cache" in report.active[0].message


def test_trk104_clean_with_shape_cache_or_outside_loops(tmp_path):
    _, report = _check(tmp_path, """
        def rounds(batches, cache):
            for batch in batches:
                out = peel_classes_batched(batch, shape_cache=cache)
            once = peel_classes_batched(batches[0])   # no loop, no hazard
            return out, once
    """, only=["TRK104"])
    assert _ids(report) == []


def test_trk104_flags_local_jit_binding_with_loop_varying_args(tmp_path):
    # the class the first rule missed: the jitted callable is defined in
    # the same file (no config entry), and its in-loop argument shrinks
    # every iteration — each iteration is a fresh trace + compile
    _, report = _check(tmp_path, """
        import jax

        step = jax.jit(lambda x: x.sum())

        def drive(frontiers):
            total = 0
            for f in frontiers:
                total += step(f[f >= 0])   # compacted: new shape per round
            return total
    """, only=["TRK104"])
    assert _ids(report) == ["TRK104"]
    assert "`step`" in report.active[0].message
    assert "`f`" in report.active[0].message


def test_trk104_flags_jit_decorated_def_called_in_loop(tmp_path):
    _, report = _check(tmp_path, """
        import jax
        from functools import partial

        @partial(jax.jit, static_argnums=())
        def fold(acc, x):
            return acc + x

        def drive(chunks):
            acc = 0
            for c in chunks:
                acc = fold(acc, c)
            return acc
    """, only=["TRK104"])
    assert _ids(report) == ["TRK104"]
    assert "`fold`" in report.active[0].message


def test_trk104_local_jit_clean_with_loop_invariant_args(tmp_path):
    # every argument is bound outside the loop: one trace, N cache hits —
    # hoisting isn't required when the shapes cannot vary
    _, report = _check(tmp_path, """
        import jax

        step = jax.jit(lambda x: x * 2)

        def drive(x0, n):
            for _ in range(n):
                y = step(x0)
            return y
    """, only=["TRK104"])
    assert _ids(report) == []


def test_trk104_local_jit_allowlisted_with_shape_invariant(tmp_path):
    _, report = _check(tmp_path, """
        import jax

        step = jax.jit(lambda x: x * 2)

        def drive(x):
            for _ in range(3):
                # trusscheck: allow[TRK104] -- x is loop-carried with a fixed shape
                x = step(x)
            return x
    """, only=["TRK104"])
    assert report.errors == []
    assert [f.rule_id for f in report.findings if f.allowlisted] == ["TRK104"]


# ---------------------------------------------------------------------------
# TRK105 host syncs in the hot round loops
# ---------------------------------------------------------------------------

HOT = "src/repro/core/peel.py"

def test_trk105_flags_loop_sync_in_hot_module(tmp_path):
    _, report = _check(tmp_path, """
        import jax

        step = jax.jit(lambda x: x)

        def drive(xs):
            out = None
            for x in xs:
                out = step(x)
                n = int(out)   # blocks the double-buffered pipeline
            return out
    """, rel=HOT, only=["TRK105"])
    assert _ids(report) == ["TRK105"]


def test_trk105_sync_after_the_loop_is_fine(tmp_path):
    _, report = _check(tmp_path, """
        import jax

        step = jax.jit(lambda x: x)

        def drive(xs):
            out = None
            for x in xs:
                out = step(x)
            return int(out)   # one sync, outside the loop
    """, rel=HOT, only=["TRK105"])
    assert _ids(report) == []


def test_trk105_scoped_to_hot_modules(tmp_path):
    _, report = _check(tmp_path, """
        import jax

        step = jax.jit(lambda x: x)

        def drive(xs):
            for x in xs:
                n = int(step(x).sum())
                print(n)
    """, rel="src/repro/launch/bench.py", only=["TRK105"])
    assert _ids(report) == []


# ---------------------------------------------------------------------------
# TRK106 fault-site coverage
# ---------------------------------------------------------------------------

def test_trk106_flags_unregistered_site(tmp_path):
    _, report = _check(tmp_path, """
        def risky(faults):
            faults.check("bogus-site", round=1)
    """, only=["TRK106"])
    assert _ids(report) == ["TRK106"]
    assert "bogus-site" in report.active[0].message


def test_trk106_accepts_sites_from_the_registry(tmp_path):
    # a faults.py up the tree defines the registry the rule parses
    _write(tmp_path, 'DISPATCH = "dispatch"\nCUSTOM = "custom-site"\n',
           rel="src/repro/core/faults.py")
    _, report = _check(tmp_path, """
        def risky(faults):
            faults.check("custom-site", round=1)
            faults.check(faults.DISPATCH, round=2)
    """, rel="src/repro/core/top_down.py", only=["TRK106"])
    assert _ids(report) == []


def test_trk106_requires_the_configured_hooks(tmp_path):
    _, report = _check(tmp_path, """
        def peel_classes_batched(batch):
            return batch
    """, rel=HOT, only=["TRK106"])
    assert _ids(report) == ["TRK106"]
    assert "faults.check" in report.active[0].message


def test_trk106_plain_hook_names_do_not_bind_to_methods(tmp_path):
    # the configured ("checkpoint/manager.py", "save") hook is satisfied by
    # the module-level save; AsyncWriter.save delegating to it must not be
    # required to hook twice
    _, report = _check(tmp_path, """
        from repro.core import faults

        def save(state):
            faults.check(faults.CHECKPOINT_WRITE, step=0)
            return state

        class AsyncWriter:
            def save(self, state):
                return save(state)
    """, rel="src/repro/checkpoint/manager.py", only=["TRK106"])
    assert _ids(report) == []


def test_trk106_driver_dispatch_requires_fault_ctx(tmp_path):
    f, report = _check(tmp_path, """
        def rounds(batches):
            for b in batches:
                out = peel_classes_batched(b, shape_cache=None)
            return out
    """, rel="src/repro/core/bottom_up.py", only=["TRK106"])
    assert _ids(report) == ["TRK106"]
    assert "fault_ctx" in report.active[0].message
    f.write_text(textwrap.dedent("""
        def rounds(batches):
            for b in batches:
                out = peel_classes_batched(
                    b, shape_cache=None,
                    fault_ctx={"stage": "stage2", "round": 0})
            return out
    """), encoding="utf-8")
    assert _ids(analysis.check_paths([str(f)], only=["TRK106"])) == []


# ---------------------------------------------------------------------------
# TRK107 Pallas invariants
# ---------------------------------------------------------------------------

PALLAS_BUG = """
    from jax.experimental import pallas as pl

    def launch(x, bm: int = 128):
        return pl.pallas_call(_kern, grid=(x.shape[0] // bm,))(x)
"""

PALLAS_FIXED = """
    from jax.experimental import pallas as pl

    VMEM_BUDGET_BYTES = 12 * 1024 * 1024

    def kernel_vmem_bytes(bm):
        return bm * 4 * 2

    def launch(x, bm: int = 128):
        if x.shape[0] % bm:
            raise ValueError("bm must divide the row count")
        need = kernel_vmem_bytes(bm)
        if need > VMEM_BUDGET_BYTES:
            raise ValueError("tile working set exceeds the VMEM budget")
        return pl.pallas_call(_kern, grid=(x.shape[0] // bm,))(x)
"""


def test_trk107_flags_unguarded_tile_and_missing_vmem_estimate(tmp_path):
    _, report = _check(tmp_path, PALLAS_BUG, only=["TRK107"])
    msgs = " ".join(f.message for f in report.active)
    assert _ids(report) == ["TRK107", "TRK107"]
    assert "tile knob `bm`" in msgs and "VMEM" in msgs


def test_trk107_clean_with_live_guard_and_budget_compare(tmp_path):
    _, report = _check(tmp_path, PALLAS_FIXED, only=["TRK107"])
    assert _ids(report) == []


def test_trk107_assert_is_not_a_live_guard(tmp_path):
    # the -O lane erases asserts, so an asserted divisibility check does
    # not satisfy the rule (it still separately trips TRK103)
    _, report = _check(tmp_path, """
        from jax.experimental import pallas as pl

        VMEM_BUDGET_BYTES = 1 << 20

        def kernel_vmem_bytes(bm):
            return bm * 4

        def launch(x, bm: int = 128):
            assert x.shape[0] % bm == 0
            if kernel_vmem_bytes(bm) > VMEM_BUDGET_BYTES:
                raise ValueError("over budget")
            return pl.pallas_call(_kern, grid=(x.shape[0] // bm,))(x)
    """, only=["TRK107"])
    assert _ids(report) == ["TRK107"]
    assert "tile knob `bm`" in report.active[0].message


# ---------------------------------------------------------------------------
# TRK100 pragma hygiene + allowlisting
# ---------------------------------------------------------------------------

def test_pragma_with_rationale_allowlists_the_finding(tmp_path):
    _, report = _check(tmp_path, """
        def restore(blob):
            assert blob  # trusscheck: allow[TRK103] -- test-only scaffold
            return blob
    """, only=["TRK103"])
    assert report.errors == []
    assert [f.rule_id for f in report.findings if f.allowlisted] == ["TRK103"]


def test_pragma_on_the_line_above_counts(tmp_path):
    _, report = _check(tmp_path, """
        def restore(blob):
            # trusscheck: allow[TRK103] -- test-only scaffold
            assert blob
            return blob
    """, only=["TRK103"])
    assert report.errors == []


def test_pragma_without_rationale_is_its_own_finding(tmp_path):
    _, report = _check(tmp_path, """
        def restore(blob):
            assert blob  # trusscheck: allow[TRK103]
            return blob
    """, only=["TRK103"])
    assert _ids(report) == ["TRK100", "TRK103"]


def test_stale_pragma_is_flagged(tmp_path):
    _, report = _check(tmp_path, """
        def restore(blob):
            # trusscheck: allow[TRK103] -- nothing here anymore
            return blob
    """, only=["TRK103"])
    assert _ids(report) == ["TRK100"]
    assert "stale" in report.active[0].message


# ---------------------------------------------------------------------------
# --fix round trips
# ---------------------------------------------------------------------------

def test_fix_rewrites_assert_to_typed_raise(tmp_path):
    f, report = _check(tmp_path, """
        def restore(blob):
            assert blob["magic"] == 7, "corrupt snapshot"
            return blob["state"]
    """, only=["TRK103"])
    assert apply_fixes(str(f), report.findings) == 1
    fixed = f.read_text(encoding="utf-8")
    ast.parse(fixed)                      # still valid syntax
    assert "raise ValueError" in fixed and "assert" not in fixed
    assert _ids(analysis.check_paths([str(f)], only=["TRK103"])) == []
    ns = {}
    exec(compile(fixed, str(f), "exec"), ns)
    with pytest.raises(ValueError, match="corrupt snapshot"):
        ns["restore"]({"magic": 0})


def test_fix_rewrites_falsy_guard_and_or_default(tmp_path):
    f, report = _check(tmp_path, """
        def pack(lane_capacity=None):
            if lane_capacity:
                cap = lane_capacity
            cap = lane_capacity or 64
            return cap
    """, only=["TRK102"])
    assert apply_fixes(str(f), report.findings) == 2
    fixed = f.read_text(encoding="utf-8")
    ast.parse(fixed)
    assert _ids(analysis.check_paths([str(f)], only=["TRK102"])) == []
    ns = {}
    exec(compile(fixed, str(f), "exec"), ns)
    # the behaviour change IS the fix: 0 no longer falls back to 64
    assert ns["pack"](0) == 0
    assert ns["pack"](None) == 64
    assert ns["pack"](8) == 8


def test_fix_leaves_allowlisted_and_multiline_findings_alone(tmp_path):
    f, report = _check(tmp_path, """
        def restore(blob):
            assert blob  # trusscheck: allow[TRK103] -- scaffold
            assert (blob["magic"]
                    == 7)
            return blob
    """, only=["TRK103"])
    before = f.read_text(encoding="utf-8")
    assert apply_fixes(str(f), report.findings) == 0
    assert f.read_text(encoding="utf-8") == before


# ---------------------------------------------------------------------------
# runner plumbing + the self-run gate
# ---------------------------------------------------------------------------

def test_unknown_rule_ids_are_rejected():
    with pytest.raises(ValueError, match="TRK999"):
        analysis.build_rules(["TRK999"])


def test_cli_exit_codes_and_json(tmp_path):
    f = _write(tmp_path, PR6_BUG)
    env_src = str(REPO_ROOT / "src")
    dirty = subprocess.run(
        [sys.executable, "-m", "repro.analysis", str(f), "--json", "-"],
        capture_output=True, text=True, env={"PYTHONPATH": env_src,
                                             "PATH": "/usr/bin:/bin"})
    assert dirty.returncode == 1
    assert '"TRK103"' in dirty.stdout
    clean = _write(tmp_path, "X = 1\n", rel="src/repro/clean.py")
    ok = subprocess.run(
        [sys.executable, "-m", "repro.analysis", str(clean)],
        capture_output=True, text=True, env={"PYTHONPATH": env_src,
                                             "PATH": "/usr/bin:/bin"})
    assert ok.returncode == 0
    assert "clean" in ok.stdout


def test_self_run_repo_is_clean():
    """The CI gate: src/repro checks clean modulo explicit allowlists."""
    report = analysis.check_paths([str(REPO_ROOT / "src" / "repro")])
    # every module of the package was walked
    assert report.files_checked == len(
        list((REPO_ROOT / "src" / "repro").rglob("*.py")))
    assert [f.render() for f in report.errors] == []
    # every allowlist that exists carries a rationale (TRK100 enforces it,
    # but pin the invariant directly too)
    for f in report.findings:
        if f.allowlisted:
            assert f.rule_id in ("TRK104", "TRK105")


# ---------------------------------------------------------------------------
# regression tests for the sites the sweep fixed (satellite b)
# ---------------------------------------------------------------------------

def test_build_partition_batch_rejects_zero_lane_capacity():
    from repro.core import graph as glib
    from repro.core.partition import build_partition_batch
    edges = glib.canonical_edges(
        np.array([[0, 1], [1, 2], [0, 2], [2, 3]]), 4)
    g = glib.build_graph(4, edges)
    parts = [np.array([0, 1, 2, 3], dtype=np.int32)]
    with pytest.raises(ValueError, match="lane_capacity"):
        build_partition_batch(g, parts, lane_capacity=0)
    # None still means "natural pow4 classes"
    batch = build_partition_batch(g, parts, lane_capacity=None)
    assert batch.n_parts == 1


def test_make_host_mesh_rejects_zero_devices():
    from repro.launch.mesh import make_host_mesh
    with pytest.raises(ValueError, match="positive"):
        make_host_mesh(0)


def test_triangle_count_kernel_rejects_bad_tiles_loudly():
    import jax.numpy as jnp
    from repro.kernels.triangle_count.kernel import triangle_count_kernel
    A = jnp.zeros((6, 6), jnp.float32)
    with pytest.raises(ValueError, match="divide"):
        triangle_count_kernel(A, bm=4, bn=4, bk=4, interpret=True)
    with pytest.raises(TypeError, match="dtype"):
        triangle_count_kernel(A.astype(jnp.int32), bm=2, bn=2, bk=2,
                              interpret=True)
