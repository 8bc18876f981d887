"""The on-disk layout of a committed step: one raw file per leaf with a
manifest in the COMMIT marker, read in byte ranges on a thread pool into
arrays allocated from the template; older npz layouts still restore."""
import json
import os
import threading
import time

import jax
import ml_dtypes
import numpy as np
import pytest

import repro.checkpoint.checkpointer as ck
from repro.checkpoint import Checkpointer, restore_pytree, save_pytree
from repro.core import tracing


def _leaf(shape, dtype, seed=0):
    rng = np.random.default_rng(seed)
    if np.dtype(dtype).kind in "iu":
        info = np.iinfo(dtype)
        return rng.integers(info.min, info.max, size=shape, dtype=dtype,
                            endpoint=True)
    return rng.normal(size=shape).astype(dtype)


def _bits(a: np.ndarray) -> bytes:
    return np.ascontiguousarray(a).tobytes()


@pytest.mark.parametrize("dtype", [np.float32, ml_dtypes.bfloat16, np.int32,
                                   np.uint32])
@pytest.mark.parametrize("shape", [(), (7,), (3, 5, 2)])
def test_leaf_round_trip_is_bit_exact(tmp_path, dtype, shape):
    tree = {"x": _leaf(shape, dtype), "nested": {"y": _leaf(shape, dtype, 1)}}
    save_pytree(tree, str(tmp_path), 3)
    back = restore_pytree(jax.tree.map(np.zeros_like, tree), str(tmp_path), 3)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        assert b.dtype == a.dtype and b.shape == a.shape
        assert _bits(b) == _bits(a)


def test_step_holds_one_file_per_leaf_and_the_manifest(tmp_path):
    tree = {"a": np.arange(6, dtype=np.float32).reshape(2, 3),
            "b": {"c": np.ones((4,), np.int32),
                  "d": np.asarray(2.5, ml_dtypes.bfloat16)}}
    path = save_pytree(tree, str(tmp_path), 7)
    assert sorted(os.listdir(path)) == [
        "COMMIT", "leaf_0.bin", "leaf_1.bin", "leaf_2.bin"]
    with open(os.path.join(path, "COMMIT")) as f:
        manifest = json.load(f)
    assert manifest["step"] == 7 and manifest["arrays"] == 3
    assert manifest["leaves"] == [
        {"key": "a", "file": "leaf_0.bin", "shape": [2, 3],
         "dtype": "float32", "nbytes": 24},
        {"key": "b|c", "file": "leaf_1.bin", "shape": [4],
         "dtype": "int32", "nbytes": 16},
        {"key": "b|d", "file": "leaf_2.bin", "shape": [],
         "dtype": "bfloat16", "nbytes": 2}]
    for entry, leaf in zip(manifest["leaves"], jax.tree.leaves(tree)):
        with open(os.path.join(path, entry["file"]), "rb") as f:
            assert f.read() == _bits(leaf)


def test_a_non_contiguous_leaf_is_written_in_logical_order(tmp_path):
    base = np.arange(24, dtype=np.float32).reshape(4, 6)
    tree = {"t": base.T, "s": base[:, ::2]}
    save_pytree(tree, str(tmp_path), 1)
    back = restore_pytree(jax.tree.map(np.zeros_like, tree), str(tmp_path), 1)
    for key in tree:
        np.testing.assert_array_equal(back[key], tree[key])


def test_a_large_leaf_is_read_in_ranges_across_threads(tmp_path, monkeypatch):
    """With the chunk cut to 4 KiB, a 100,012-byte leaf is written piece by
    piece and read as 25 ranges, the last one short, at least two of them
    at once."""
    tree = {"big": _leaf((25_000 + 3,), np.float32), "small": np.ones(3)}
    monkeypatch.setattr(ck, "CHUNK_BYTES", 4096)
    monkeypatch.setattr(ck, "READ_THREADS", 4)
    path = save_pytree(tree, str(tmp_path), 1)
    with open(os.path.join(path, "leaf_0.bin"), "rb") as f:
        assert f.read() == _bits(tree["big"])
    real = ck._read_range
    both = threading.Barrier(2, timeout=10)
    jobs = []

    def read_range(job):
        jobs.append((os.path.basename(job[0]), job[1], job[2].nbytes,
                     threading.current_thread().name))
        if len(jobs) <= 2:
            both.wait()               # two ranges in flight at once
        real(job)

    monkeypatch.setattr(ck, "_read_range", read_range)
    back = restore_pytree(jax.tree.map(np.zeros_like, tree), str(tmp_path), 1)
    assert _bits(back["big"]) == _bits(tree["big"])
    np.testing.assert_array_equal(back["small"], tree["small"])
    big = sorted((at, n) for name, at, n, _ in jobs if name == "leaf_0.bin")
    nbytes = tree["big"].nbytes
    assert big == [(at, min(4096, nbytes - at))
                   for at in range(0, nbytes, 4096)]
    assert len(big) == 25 and big[-1] == (24 * 4096, 1708)
    assert len({thread for *_, thread in jobs}) >= 2
    assert all(t.startswith("ckpt-read") for *_, t in jobs)


@pytest.mark.parametrize("template, error, match", [
    ({"a": np.zeros((3, 2), np.float32)}, ValueError, "shape mismatch"),
    ({"a": np.zeros((2, 3), np.float64)}, ValueError, "dtype mismatch"),
    ({"a": np.zeros((2, 3), ml_dtypes.bfloat16)}, ValueError,
     "dtype mismatch"),
    ({"a": np.zeros((2, 3), np.float32), "z": np.zeros(1)}, KeyError,
     "checkpoint missing z"),
])
def test_a_template_that_disagrees_with_the_manifest_raises(
        tmp_path, template, error, match):
    save_pytree({"a": np.ones((2, 3), np.float32)}, str(tmp_path), 1)
    with pytest.raises(error, match=match):
        restore_pytree(template, str(tmp_path), 1)


def test_the_template_may_be_shapes_alone(tmp_path):
    tree = {"w": _leaf((4, 4), np.float32), "m": _leaf((2,), np.int32)}
    save_pytree(tree, str(tmp_path), 2)
    shapes = jax.eval_shape(lambda: jax.tree.map(jax.numpy.asarray, tree))
    back = restore_pytree(shapes, str(tmp_path), 2)
    for key in tree:
        assert _bits(back[key]) == _bits(tree[key])


@pytest.mark.parametrize("damage", ["missing", "short", "long"])
def test_a_damaged_leaf_file_raises_oserror(tmp_path, damage):
    tree = {"a": np.ones((64,), np.float32), "b": np.ones((8,), np.float32)}
    path = save_pytree(tree, str(tmp_path), 1)
    leaf = os.path.join(path, "leaf_0.bin")
    if damage == "missing":
        os.unlink(leaf)
    else:
        with open(leaf, "r+b") as f:
            f.truncate(100 if damage == "short" else 300)
    with pytest.raises(OSError):
        restore_pytree(tree, str(tmp_path), 1)


def test_a_leaf_file_cut_short_during_the_read_raises_oserror(tmp_path,
                                                             monkeypatch):
    tree = {"a": np.ones((4096,), np.float32)}
    path = save_pytree(tree, str(tmp_path), 1)
    monkeypatch.setattr(ck, "CHUNK_BYTES", 4096)
    real = ck._read_range

    def truncating(job):
        with open(os.path.join(path, "leaf_0.bin"), "r+b") as f:
            f.truncate(6000)
        real(job)

    monkeypatch.setattr(ck, "_read_range", truncating)
    with pytest.raises(OSError, match="ends at byte"):
        restore_pytree(tree, str(tmp_path), 1)


def _read_span(t0):
    [read] = tracing.spans(prefix="ckpt.restore.read", since=t0)
    return read.attrs


def test_a_step_directory_holding_an_npz_still_restores(tmp_path):
    """The step layout before one file per leaf: ``arrays.npz`` beside a
    COMMIT marker with no leaf manifest."""
    tree = {"a": np.arange(4, dtype=np.float32), "b": {"c": np.int32(3)}}
    step = tmp_path / "step_00000004"
    step.mkdir()
    np.savez(step / "arrays.npz", **{"a": tree["a"], "b|c": tree["b"]["c"]})
    (step / "COMMIT").write_text(json.dumps({"step": 4, "arrays": 2}))
    ckpt = Checkpointer(str(tmp_path), keep=2)
    assert ckpt.latest_step() == 4
    t0 = time.monotonic()
    back = ckpt.restore(tree)
    np.testing.assert_array_equal(back["a"], tree["a"])
    assert back["b"]["c"] == 3
    assert _read_span(t0) == {"layout": "npz", "files": 1, "bytes": 20}
    ckpt.save(tree, 6)                                 # leaves on top
    t0 = time.monotonic()
    ckpt.restore(tree)
    assert _read_span(t0) == {"layout": "leaves", "files": 2, "bytes": 20}


def test_a_flat_npz_still_restores_and_reports_its_layout(tmp_path):
    tree = {"a": np.arange(6, dtype=np.float32).reshape(2, 3)}
    np.savez(str(tmp_path / "ckpt_00000005.npz"), a=tree["a"] + 1)
    t0 = time.monotonic()
    back = restore_pytree(tree, str(tmp_path))
    np.testing.assert_array_equal(back["a"], tree["a"] + 1)
    assert _read_span(t0) == {"layout": "npz", "files": 1, "bytes": 24}
