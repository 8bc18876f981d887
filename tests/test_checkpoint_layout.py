"""The on-disk layout of a committed step: one raw file per leaf with a
manifest in the COMMIT marker, restored by mapping each file copy-on-write;
older npz layouts still restore."""
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

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


def test_a_leaf_that_cannot_be_mapped_raises_oserror_naming_its_file(
        tmp_path):
    """The size check comes first; a file cut short after it fails the
    mapping, which raises ``OSError`` and names the file."""
    path = save_pytree({"a": np.ones((64,), np.float32)}, str(tmp_path), 1)
    leaf = os.path.join(path, "leaf_0.bin")
    with open(leaf, "r+b") as f:
        f.truncate(100)
    with pytest.raises(OSError, match="cannot map .*leaf_0.bin"):
        ck._map_leaf(leaf, 256, (64,), np.dtype(np.float32))


def test_a_restored_leaf_written_to_leaves_its_file_unchanged(tmp_path):
    tree = {"a": _leaf((16, 4), np.float32), "b": _leaf((3,), np.int32)}
    path = save_pytree(tree, str(tmp_path), 1)
    back = restore_pytree(tree, str(tmp_path), 1)
    assert all(type(x) is np.ndarray and x.flags.writeable
               for x in jax.tree.leaves(back))
    back["a"][...] = 0
    back["b"] += 1
    assert not back["a"].any()
    for entry, leaf in (("leaf_0.bin", tree["a"]), ("leaf_1.bin", tree["b"])):
        with open(os.path.join(path, entry), "rb") as f:
            assert f.read() == _bits(leaf)
    again = restore_pytree(tree, str(tmp_path), 1)
    for key in tree:
        assert _bits(again[key]) == _bits(tree[key])


@pytest.mark.parametrize("removal", ["rmtree", "gc"])
def test_restored_leaves_outlive_their_step_directory(tmp_path, removal):
    """A mapping stays readable, bit for bit, after its step is removed by
    hand or by the checkpointer's garbage collection."""
    tree = {"w": _leaf((128, 32), ml_dtypes.bfloat16),
            "m": _leaf((5,), np.uint32)}
    ckpt = Checkpointer(str(tmp_path), keep=1)
    path = ckpt.save(tree, 1)
    back = ckpt.restore(tree, 1)
    if removal == "rmtree":
        shutil.rmtree(path)
    else:
        ckpt.save(jax.tree.map(np.zeros_like, tree), 2)
        assert ckpt.latest_step() == 2
    assert not os.path.exists(path)
    for key in tree:
        assert _bits(back[key]) == _bits(tree[key])


@pytest.mark.parametrize("shape", [(0, 3), (0,)])
def test_a_zero_size_leaf_restores(tmp_path, shape):
    tree = {"empty": np.zeros(shape, np.float32), "x": _leaf((4,), np.float32)}
    path = save_pytree(tree, str(tmp_path), 1)
    assert os.path.getsize(os.path.join(path, "leaf_0.bin")) == 0
    t0 = time.monotonic()
    back = restore_pytree(tree, str(tmp_path), 1)
    assert back["empty"].shape == shape and back["empty"].dtype == np.float32
    assert _bits(back["x"]) == _bits(tree["x"])
    assert _read_span(t0) == {"layout": "leaves", "files": 2, "bytes": 16,
                              "mapped": 16}


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
    assert _read_span(t0) == {"layout": "npz", "files": 1, "bytes": 20,
                              "mapped": 0}
    ckpt.save(tree, 6)                                 # leaves on top
    t0 = time.monotonic()
    ckpt.restore(tree)
    assert _read_span(t0) == {"layout": "leaves", "files": 2, "bytes": 20,
                              "mapped": 20}


def test_a_flat_npz_still_restores_and_reports_its_layout(tmp_path):
    tree = {"a": np.arange(6, dtype=np.float32).reshape(2, 3)}
    np.savez(str(tmp_path / "ckpt_00000005.npz"), a=tree["a"] + 1)
    t0 = time.monotonic()
    back = restore_pytree(tree, str(tmp_path))
    np.testing.assert_array_equal(back["a"], tree["a"] + 1)
    assert _read_span(t0) == {"layout": "npz", "files": 1, "bytes": 24,
                              "mapped": 0}


@pytest.mark.parametrize("layout", ["leaves", "step_npz", "flat_npz"])
def test_the_read_span_counts_the_mapped_bytes(tmp_path, layout):
    """``mapped`` is the state's bytes on the leaf layout, 0 on either npz
    layout; ``bytes`` is the state's bytes on all three."""
    tree = {"a": _leaf((8, 8), np.float32), "b": _leaf((6,), np.int32)}
    if layout == "leaves":
        save_pytree(tree, str(tmp_path), 3)
    elif layout == "step_npz":
        step = tmp_path / "step_00000003"
        step.mkdir()
        np.savez(step / "arrays.npz", **tree)
        (step / "COMMIT").write_text(json.dumps({"step": 3, "arrays": 2}))
    else:
        np.savez(str(tmp_path / "ckpt_00000003.npz"), **tree)
    t0 = time.monotonic()
    back = restore_pytree(tree, str(tmp_path), 3)
    for key in tree:
        assert _bits(back[key]) == _bits(tree[key])
    attrs = _read_span(t0)
    assert attrs["bytes"] == 280
    assert attrs["mapped"] == (280 if layout == "leaves" else 0)


PUT_JOB = r"""
import functools, json, shutil, threading
import jax
import numpy as np
from repro.checkpoint import restore_pytree, save_pytree
from repro.configs import get_config
from repro.distributed.sharding import to_shardings
from repro.distributed.steps import init_train_state, make_train_fn
from repro.launch.programs import _local_mesh, _put_restored

cfg = get_config("qwen3-1.7b").replace(
    num_layers=2, d_model=64, num_heads=8, num_kv_heads=4, head_dim=16,
    d_ff=128, vocab_size=256)
mesh = _local_mesh("fsdp_tp")
with jax.set_mesh(mesh):
    _, pspecs = make_train_fn(cfg, mesh)
    shardings = to_shardings(pspecs, mesh)
    init = jax.jit(functools.partial(init_train_state, cfg),
                   out_shardings=shardings)
    saved = init(jax.random.PRNGKey(0))
    path = save_pytree(saved, CKPT, 4)
    saved = jax.tree.map(np.asarray, saved)
    host = restore_pytree(jax.eval_shape(init, jax.random.PRNGKey(0)),
                          CKPT, 4)
    started = []                   # the watcher may end before we look
    start = threading.Thread.start
    threading.Thread.start = lambda t: (started.append(t), start(t))[1]
    state = _put_restored(host, shardings)
    threading.Thread.start = start
    del host
    [watcher] = [t for t in started if t.name == "ckpt-restore-put"]
    watcher.join(60)
    shutil.rmtree(path)
    equal = [np.asarray(s.data).tobytes() == want[s.index].tobytes()
             for leaf, want in zip(jax.tree.leaves(state),
                                   jax.tree.leaves(saved))
             for s in leaf.addressable_shards]
print(json.dumps({"mesh": list(mesh.devices.shape),
                  "watcher_alive": watcher.is_alive(), "shards": len(equal),
                  "equal": all(equal), "leaves": len(jax.tree.leaves(state)),
                  "sharded": sum(
                      len({s.index for s in leaf.addressable_shards}) > 1
                      for leaf in jax.tree.leaves(state))}))
"""


def test_the_put_of_a_mapped_restore_lands_bit_equal_on_every_shard(tmp_path):
    """On four CPU devices, in a process of its own (this one holds a
    one-device backend): ``_put_restored`` of a mapped restore onto the
    ``(1, 4)`` mesh's shardings lands the saved state, bit for bit, on every
    shard, and the step's files can be deleted once the put's watcher thread
    has ended."""
    src = Path(__file__).resolve().parents[1] / "src"
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(src),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = f"CKPT = {str(tmp_path / 'ck')!r}\n" + PUT_JOB
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    got = json.loads(r.stdout.strip().splitlines()[-1])
    assert got["mesh"] == [1, 4] and not got["watcher_alive"]
    assert got["shards"] == 4 * got["leaves"] and got["equal"]
    assert got["sharded"] > 0
