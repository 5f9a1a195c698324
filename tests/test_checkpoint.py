"""Checkpoint manager: bit-exact restore, compression, atomicity, CRC
fallback, retention, elastic template restore."""

import glob
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.checkpoint.manager import CheckpointManager


@pytest.fixture
def state():
    k = jax.random.PRNGKey(0)
    return {
        "params": {
            "w": jax.random.normal(k, (64, 64), jnp.float32),
            "e": (jax.random.normal(k, (128, 32)) * 0.01).astype(jnp.bfloat16),
        },
        "opt": {"m": jnp.zeros((64, 64), jnp.float32)},
        "step": jnp.int32(7),
    }


def test_save_restore_bit_exact(tmp_path, state):
    mgr = CheckpointManager(str(tmp_path), compress=True)
    mgr.save(state, 1)
    restored, step = mgr.restore_latest(jax.eval_shape(lambda: state))
    assert step == 1
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_compression_helps_on_structured_state(tmp_path):
    # optimizer moments start at zero: hugely compressible
    state = {"m": jnp.zeros((512, 512), jnp.float32)}
    mgr = CheckpointManager(str(tmp_path), compress=True)
    mgr.save(state, 1)
    assert mgr.stats(1)["ratio"] > 20


def test_crc_detects_corruption_and_falls_back(tmp_path, state):
    mgr = CheckpointManager(str(tmp_path), compress=True, keep=5)
    mgr.save(state, 1)
    mgr.save(state, 2)
    files = sorted(
        glob.glob(os.path.join(str(tmp_path), "step_00000002", "*.gplz")),
        key=os.path.getsize,
    )
    with open(files[-1], "r+b") as f:
        f.seek(os.path.getsize(files[-1]) // 2)
        f.write(b"\xa5" * 32)
    restored, step = mgr.restore_latest(jax.eval_shape(lambda: state))
    assert step == 1  # fell back past the damaged step


def test_retention_gc(tmp_path, state):
    mgr = CheckpointManager(str(tmp_path), compress=False, keep=2)
    for s in (1, 2, 3, 4):
        mgr.save(state, s)
    assert mgr.steps() == [3, 4]


def test_no_tmp_dirs_left(tmp_path, state):
    mgr = CheckpointManager(str(tmp_path), compress=False)
    mgr.save(state, 1)
    assert not [d for d in os.listdir(tmp_path) if d.endswith(".tmp")]


def test_save_batches_dtype_classes(tmp_path, state, monkeypatch):
    """Leaves of a dtype class go through one batched dispatch, not one
    compress() call per leaf."""
    from repro.checkpoint import manager as mgr_mod
    from repro.core import lzss

    calls = {"many": 0, "single": 0}
    real_many = lzss.compress_many

    def counting_many(arrays, cfg):
        calls["many"] += 1
        return real_many(arrays, cfg)

    def forbidden_single(*a, **k):
        calls["single"] += 1
        raise AssertionError("save() must use the batched pipeline API")

    monkeypatch.setattr(mgr_mod.lzss, "compress_many", counting_many)
    monkeypatch.setattr(mgr_mod.lzss, "compress", forbidden_single)
    mgr = CheckpointManager(str(tmp_path), compress=True)
    mgr.save(state, 1)
    assert calls["single"] == 0
    # state has 3 compressible leaves (2 f32 in one geometry bucket + 1 bf16)
    # -> at most one dispatch per (symbol_size, bucket) group
    assert 1 <= calls["many"] <= 3
    restored, step = mgr.restore_latest(jax.eval_shape(lambda: state))
    assert step == 1
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_restore_latest_skips_truncated_container(tmp_path, state):
    """A container that fails validation (ValueError) is damaged data:
    restore_latest falls back to the previous step."""
    mgr = CheckpointManager(str(tmp_path), compress=True, keep=5)
    mgr.save(state, 1)
    mgr.save(state, 2)
    blob = max(
        glob.glob(os.path.join(str(tmp_path), "step_00000002", "*.gplz")),
        key=os.path.getsize,
    )
    with open(blob, "r+b") as f:
        f.truncate(os.path.getsize(blob) // 2)
    restored, step = mgr.restore_latest(jax.eval_shape(lambda: state))
    assert step == 1


def test_restore_latest_propagates_decoder_failure(tmp_path, state, monkeypatch):
    """A decoder that fails for a reason other than damaged data (say a
    kernel that cannot compile on the device) must surface, not read as
    "no checkpoint" and silently restart training from step 0."""
    from repro.core import lzss

    mgr = CheckpointManager(str(tmp_path), compress=True)
    mgr.save(state, 1)

    def broken(*args, **kwargs):
        raise RuntimeError("Mosaic failed to compile TPU kernel")

    monkeypatch.setattr(lzss, "decompress_many", broken)
    with pytest.raises(RuntimeError, match="Mosaic"):
        mgr.restore_latest(jax.eval_shape(lambda: state))
