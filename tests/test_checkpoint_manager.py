"""checkpoint.manager: atomicity, integrity checksums, keep-k pruning,
AsyncWriter error surfacing, exotic-dtype roundtrip and elastic restore
validation (DESIGN.md §12 checkpoint contract).

Crash and torn-write cases are driven through the deterministic
``"checkpoint-write"`` fault-injection site, which sits exactly between the
payload write and the manifest/rename commit point — the window the atomic
tmp+rename protocol must make safe.
"""

import os

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

from repro.checkpoint import manager as ckpt
from repro.core import faults


def _tree(step):
    rng = np.random.default_rng(step)
    return {"phi": rng.integers(0, 9, 50).astype(np.int64),
            "alive": rng.random(50) < 0.5}


# ----------------------------------------------------------------- atomicity

def test_crash_mid_write_leaves_previous_snapshot_intact(tmp_path):
    d = str(tmp_path)
    ckpt.save(d, 1, _tree(1), metadata={"stage": "lb"})
    plan = faults.FaultPlan([faults.FaultRule(
        site=faults.CHECKPOINT_WRITE, kind="crash")])
    with faults.active(plan):
        with pytest.raises(OSError, match="injected crash"):
            ckpt.save(d, 2, _tree(2))
    # step 2 never committed: only a .tmp remains, and restore still finds
    # the intact step 1
    assert ckpt.all_steps(d) == [1]
    assert os.path.isdir(os.path.join(d, "step_0000000002.tmp"))
    tree, meta = ckpt.restore(d)
    assert meta == {"stage": "lb"}
    np.testing.assert_array_equal(tree["phi"], _tree(1)["phi"])
    # a later save of the same step clears the stale .tmp and commits
    ckpt.save(d, 2, _tree(2))
    assert ckpt.all_steps(d) == [1, 2]
    assert not os.path.exists(os.path.join(d, "step_0000000002.tmp"))


def test_truncated_payload_detected_and_fallback(tmp_path):
    """A snapshot torn AFTER the rename (checksum mismatch) is skipped by
    restore(step=None) with a warning; an explicit step raises."""
    d = str(tmp_path)
    ckpt.save(d, 1, _tree(1), metadata={"idx": 1})
    plan = faults.FaultPlan([faults.FaultRule(
        site=faults.CHECKPOINT_WRITE, kind="truncate")])
    with faults.active(plan):
        ckpt.save(d, 2, _tree(2), metadata={"idx": 2})  # commits corrupted
    assert ckpt.all_steps(d) == [1, 2]
    with pytest.warns(UserWarning, match="skipping corrupt"):
        tree, meta = ckpt.restore(d)
    assert meta == {"idx": 1}                 # fell back to step 1
    with pytest.raises(ckpt.CheckpointCorruptionError, match="sha256"):
        ckpt.restore(d, step=2)


def test_all_snapshots_corrupt_raises_corruption_error(tmp_path):
    d = str(tmp_path)
    plan = faults.FaultPlan([faults.FaultRule(
        site=faults.CHECKPOINT_WRITE, kind="truncate", times=3)])
    with faults.active(plan):
        for s in (1, 2, 3):
            ckpt.save(d, s, _tree(s))
    with pytest.warns(UserWarning), \
            pytest.raises(ckpt.CheckpointCorruptionError, match="no intact"):
        ckpt.restore(d)


def test_missing_dir_raises_file_not_found(tmp_path):
    with pytest.raises(FileNotFoundError):
        ckpt.restore(str(tmp_path / "nope"))


# ------------------------------------------------------------ keep-k pruning

def test_keep_k_prunes_oldest(tmp_path):
    d = str(tmp_path)
    for s in range(1, 6):
        ckpt.save(d, s, _tree(s), keep=2)
    assert ckpt.all_steps(d) == [4, 5]
    assert ckpt.latest_step(d) == 5
    tree, _ = ckpt.restore(d)
    np.testing.assert_array_equal(tree["phi"], _tree(5)["phi"])


def test_keep_nonpositive_keeps_everything(tmp_path):
    d = str(tmp_path)
    for s in range(1, 4):
        ckpt.save(d, s, _tree(s), keep=0)
    assert ckpt.all_steps(d) == [1, 2, 3]


# ------------------------------------------------------ AsyncWriter surfacing

def test_async_writer_surfaces_worker_error_on_next_wait(tmp_path):
    d = str(tmp_path)
    w = ckpt.AsyncWriter(d)
    plan = faults.FaultPlan([faults.FaultRule(
        site=faults.CHECKPOINT_WRITE, kind="crash")])
    with faults.active(plan):
        w.save(1, _tree(1))           # worker thread hits the injected crash
        with pytest.raises(OSError, match="injected crash"):
            w.wait()
    # the error is cleared after surfacing; the writer remains usable
    w.wait()
    w.save(2, _tree(2))
    w.wait()
    assert ckpt.all_steps(d) == [2]


# ------------------------------------------------------------ dtype roundtrip

def test_bf16_roundtrip(tmp_path):
    d = str(tmp_path)
    arr = np.arange(16, dtype=np.float32).astype(ml_dtypes.bfloat16)
    ckpt.save(d, 1, {"w": arr})
    like = {"w": np.zeros(16, dtype=ml_dtypes.bfloat16)}
    tree, _ = ckpt.restore(d, like)
    assert tree["w"].dtype == ml_dtypes.bfloat16
    np.testing.assert_array_equal(
        tree["w"].astype(np.float32), arr.astype(np.float32))


def test_like_none_returns_plain_named_tree(tmp_path):
    d = str(tmp_path)
    ckpt.save(d, 1, {"sup": np.arange(4), "nested": {"lb": np.ones(2)}})
    tree, _ = ckpt.restore(d)
    assert set(tree) == {"sup", "nested/lb"}


# ------------------------------------------- elastic restore shape validation

def test_restore_wrong_leaf_count_raises_structure_error(tmp_path):
    d = str(tmp_path)
    ckpt.save(d, 1, _tree(1))
    with pytest.raises(ckpt.CheckpointStructureError, match="leaves"):
        ckpt.restore(d, {"phi": np.zeros(50)})


def test_restore_wrong_shape_raises_structure_error(tmp_path):
    """Real exceptions, not bare asserts: these must fire under python -O
    too (the CI matrix runs this file with -O)."""
    d = str(tmp_path)
    ckpt.save(d, 1, _tree(1))
    like = {"phi": np.zeros(49, np.int64), "alive": np.zeros(50, bool)}
    with pytest.raises(ckpt.CheckpointStructureError, match="shape"):
        ckpt.restore(d, like)


def test_structure_error_is_not_swallowed_by_fallback(tmp_path):
    """Only corruption falls back to older snapshots — a structural
    mismatch is a caller bug and must raise even with older steps around."""
    d = str(tmp_path)
    ckpt.save(d, 1, _tree(1))
    ckpt.save(d, 2, _tree(2))
    with pytest.raises(ckpt.CheckpointStructureError):
        ckpt.restore(d, {"phi": np.zeros(50)})
    assert issubclass(ckpt.CheckpointStructureError, ckpt.CheckpointError)
    assert issubclass(ckpt.CheckpointCorruptionError, ckpt.CheckpointError)


# ------------------------------------------------------- pytree snapshots

def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "b": {"c": jnp.ones(4, jnp.bfloat16)},
            "step": jnp.asarray(7, jnp.int32)}
    d = str(tmp_path / "ck")
    ckpt.save(d, 3, tree, {"note": "x"})
    like = jax.tree.map(lambda x: np.zeros(x.shape, x.dtype), tree)
    out, meta = ckpt.restore(d, like)
    assert meta["note"] == "x"
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_checkpoint_keep_k(tmp_path):
    d = str(tmp_path / "ck")
    for s in range(5):
        ckpt.save(d, s, {"x": jnp.zeros(1)}, keep=2)
    assert ckpt.all_steps(d) == [3, 4]


def test_checkpoint_atomic_tmp_ignored(tmp_path):
    d = str(tmp_path / "ck")
    ckpt.save(d, 1, {"x": jnp.zeros(1)})
    os.makedirs(os.path.join(d, "step_0000000002.tmp"))  # simulated crash
    assert ckpt.latest_step(d) == 1
