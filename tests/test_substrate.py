"""Substrate tests: checkpointer, data pipeline, optimizer, sharding rules."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import Checkpointer, latest_step
from repro.data import FileTokenDataset, SyntheticLMDataset
from repro.optim import AdamWConfig, adamw_init, adamw_update, cosine_schedule


# ----------------------------------------------------------------------
# Checkpointer


def test_checkpoint_latest_and_gc(tmp_path):
    ck = Checkpointer(str(tmp_path), keep=2)
    tree = {"a": np.arange(6, dtype=np.float32).reshape(2, 3),
            "b": {"c": np.ones((4,), np.int32)}}
    for s in (5, 10, 15):
        ck.save(tree, s)
    assert ck.latest_step() == 15
    files = sorted(os.listdir(tmp_path))
    assert files == ["step_00000010", "step_00000015"]  # gc kept 2
    back = ck.restore(tree)
    np.testing.assert_array_equal(back["a"], tree["a"])
    np.testing.assert_array_equal(back["b"]["c"], tree["b"]["c"])


def test_checkpoint_shape_mismatch_raises(tmp_path):
    ck = Checkpointer(str(tmp_path))
    ck.save({"a": np.ones((2, 2))}, 1)
    with pytest.raises(ValueError, match="shape mismatch"):
        ck.restore({"a": np.ones((3, 3))})


def test_checkpoint_gc_tolerates_junk_and_half_written(tmp_path):
    """_gc and latest_step skip non-step entries, staging dirs and steps
    missing their COMMIT marker (a writer killed mid-checkpoint) instead of
    crashing or resuming from a torn checkpoint."""
    ck = Checkpointer(str(tmp_path), keep=2)
    tree = {"a": np.ones((2,), np.float32)}
    (tmp_path / "notes.txt").write_text("user junk")
    (tmp_path / ".tmp-step_00000042-abc").mkdir()      # abandoned staging dir
    half = tmp_path / "step_00000099"                  # killed mid-write:
    half.mkdir()                                       # arrays, no COMMIT
    np.savez(half / "arrays.npz", a=np.zeros((2,), np.float32))
    for s in (1, 2, 3):
        ck.save(tree, s)                               # _gc runs each save
    assert ck.latest_step() == 3                       # 99 is invisible
    back = ck.restore(tree)
    np.testing.assert_array_equal(back["a"], tree["a"])
    left = sorted(os.listdir(tmp_path))
    assert "notes.txt" in left and "step_00000099" in left  # skipped, kept
    assert "step_00000001" not in left                 # gc dropped oldest
    with pytest.raises(FileNotFoundError):             # uncommitted = absent
        ck.restore(tree, 99)


def test_checkpoint_reads_legacy_flat_format(tmp_path):
    """Pre-PR-7 flat ckpt_*.npz files still restore and participate in gc."""
    tree = {"a": np.arange(4, dtype=np.float32)}
    np.savez(str(tmp_path / "ckpt_00000005.npz"), a=tree["a"] * 2)
    assert latest_step(str(tmp_path)) == 5
    ck = Checkpointer(str(tmp_path), keep=2)
    back = ck.restore(tree, 5)
    np.testing.assert_array_equal(back["a"], tree["a"] * 2)
    ck.save(tree, 7)                                   # new format on top
    assert ck.latest_step() == 7
    ck.save(tree, 9)                                   # keep=2 -> legacy gc'd
    assert sorted(os.listdir(tmp_path)) == ["step_00000007", "step_00000009"]


# ----------------------------------------------------------------------
# Data pipeline


def test_synthetic_dataset_deterministic_and_restartable():
    d1 = SyntheticLMDataset(4, 32, 1000, seed=7)
    d2 = SyntheticLMDataset(4, 32, 1000, seed=7)
    for _ in range(3):
        d1.next_batch()
    b3 = d1.next_batch()
    d2.load_state_dict({"step": 3})
    b3b = d2.next_batch()
    np.testing.assert_array_equal(b3["tokens"], b3b["tokens"])
    np.testing.assert_array_equal(b3["labels"], b3b["labels"])
    assert b3["tokens"].shape == (4, 32)
    # labels are next-token shifted
    full_like = b3["tokens"][:, 1:]
    np.testing.assert_array_equal(full_like, b3["labels"][:, :-1])


def test_synthetic_dataset_is_learnable_structure():
    d = SyntheticLMDataset(8, 64, 500, seed=0, noise_prob=0.0)
    b = d.next_batch()
    # with zero noise each row is periodic with the motif length
    row = b["tokens"][0]
    assert (row[:8] == row[8:16]).all()


def test_file_dataset_roundtrip(tmp_path):
    path = str(tmp_path / "corpus.bin")
    tokens = np.arange(10_000, dtype=np.int32) % 777
    FileTokenDataset.write_corpus(path, tokens)
    ds = FileTokenDataset(path, batch_size=2, seq_len=16)
    b = ds.next_batch()
    assert b["tokens"].shape == (2, 16)
    np.testing.assert_array_equal(b["tokens"][0], tokens[:16])
    np.testing.assert_array_equal(b["labels"][0], tokens[1:17])


# ----------------------------------------------------------------------
# Optimizer


def test_adamw_converges_on_quadratic():
    params = {"w": jnp.array([5.0, -3.0])}
    opt = AdamWConfig(lr=0.1, weight_decay=0.0, grad_clip_norm=0.0)
    state = adamw_init(params)
    for _ in range(200):
        grads = jax.grad(lambda p: jnp.sum(p["w"] ** 2))(params)
        params, state, _ = adamw_update(opt, grads, state, params)
    assert float(jnp.abs(params["w"]).max()) < 1e-2


def test_adamw_grad_clipping():
    params = {"w": jnp.zeros(3)}
    opt = AdamWConfig(lr=1.0, grad_clip_norm=1.0, weight_decay=0.0)
    state = adamw_init(params)
    huge = {"w": jnp.full(3, 1e6)}
    _, _, metrics = adamw_update(opt, huge, state, params)
    assert metrics["grad_norm"] > 1e5  # reported raw norm


def test_cosine_schedule_shape():
    f = cosine_schedule(1.0, warmup_steps=10, total_steps=100)
    assert float(f(jnp.array(0))) == 0.0
    assert abs(float(f(jnp.array(10))) - 1.0) < 1e-6
    assert float(f(jnp.array(100))) < 1e-6
    assert float(f(jnp.array(55))) < float(f(jnp.array(20)))


# ----------------------------------------------------------------------
# Train loop integration: loss decreases on learnable data (small model)


def test_training_reduces_loss(rng):
    from repro.configs import get_config
    from repro.configs.base import ShapeConfig
    from repro.distributed.steps import init_train_state, make_train_fn
    from repro.launch.programs import _local_mesh

    cfg = get_config("tony-paper-mlp").replace(
        num_layers=2, d_model=64, num_heads=2, num_kv_heads=2, head_dim=32,
        d_ff=128, vocab_size=128, max_position=64)
    data = SyntheticLMDataset(8, 32, cfg.vocab_size, seed=1)
    mesh = _local_mesh("fsdp_tp")
    with jax.set_mesh(mesh):
        fn, _ = make_train_fn(cfg, mesh, "fsdp_tp",
                              shape=ShapeConfig("t", 32, 8, "train"))
        state = init_train_state(cfg, rng)
        losses = []
        for _ in range(30):
            batch = {k: jnp.asarray(v) for k, v in data.next_batch().items()}
            state, m = fn(state, batch)
            losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.5, losses[:3] + losses[-3:]
