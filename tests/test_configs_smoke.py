"""Per-architecture smoke tests (deliverable f): a REDUCED variant of each
assigned family runs one forward + one train step on CPU; output shapes and
finiteness asserted. Full configs are checked only for their published widths and sizes."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCH_IDS, INPUT_SHAPES, get_config, get_smoke_config
from repro.configs.base import ShapeConfig
from repro.distributed.steps import init_train_state, make_train_fn
from repro.launch.programs import _local_mesh
from repro.models import model as M

EXPECTED = {
    "recurrentgemma-2b": dict(num_layers=26, d_model=2560, num_heads=10,
                              num_kv_heads=1, d_ff=7680, vocab_size=256_000),
    "llama-3.2-vision-90b": dict(num_layers=100, d_model=8192, num_heads=64,
                                 num_kv_heads=8, d_ff=28_672, vocab_size=128_256),
    "llama3-405b": dict(num_layers=126, d_model=16_384, num_heads=128,
                        num_kv_heads=8, d_ff=53_248, vocab_size=128_256),
    "llama4-maverick-400b-a17b": dict(num_layers=48, d_model=5120, num_heads=40,
                                      num_kv_heads=8, d_ff=8192,
                                      vocab_size=202_048, num_experts=128,
                                      experts_per_token=1),
    "rwkv6-3b": dict(num_layers=32, d_model=2560, d_ff=8960, vocab_size=65_536),
    "llama4-scout-17b-a16e": dict(num_layers=48, d_model=5120, num_heads=40,
                                  num_kv_heads=8, d_ff=8192, vocab_size=202_048,
                                  num_experts=16, experts_per_token=1),
    "deepseek-coder-33b": dict(num_layers=62, d_model=7168, num_heads=56,
                               num_kv_heads=8, d_ff=19_200, vocab_size=32_256),
    "whisper-base": dict(num_layers=6, encoder_layers=6, d_model=512,
                         num_heads=8, d_ff=2048, vocab_size=51_865),
    "qwen3-1.7b": dict(num_layers=28, d_model=2048, num_heads=16,
                       num_kv_heads=8, d_ff=6144, vocab_size=151_936,
                       use_qk_norm=True),
    "llama3.2-3b": dict(num_layers=28, d_model=3072, num_heads=24,
                        num_kv_heads=8, d_ff=8192, vocab_size=128_256),
}


@pytest.mark.parametrize("arch", sorted(EXPECTED))
def test_full_config_matches_assignment(arch):
    cfg = get_config(arch)
    for field, want in EXPECTED[arch].items():
        assert getattr(cfg, field) == want, (arch, field)
    assert cfg.source, "every config must cite its source"


def _smoke_batch(cfg, rng, B=2, T=16):
    tokens = jax.random.randint(rng, (B, T), 0, cfg.vocab_size)
    batch = {"tokens": tokens, "labels": tokens}
    if cfg.is_encoder_decoder:
        batch["frames"] = jax.random.normal(
            rng, (B, cfg.num_media_tokens, cfg.d_model), jnp.dtype(cfg.dtype))
    elif cfg.uses_media:
        batch["media"] = jax.random.normal(
            rng, (B, cfg.num_media_tokens, cfg.d_model), jnp.dtype(cfg.dtype))
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_forward_shapes_and_finite(arch, rng):
    cfg = get_smoke_config(arch)
    assert cfg.d_model <= 512 and cfg.num_experts <= 4
    assert len(cfg.layer_defs()) == cfg.num_layers
    params = M.init_params(cfg, rng)
    batch = _smoke_batch(cfg, rng)
    logits, aux = M.forward(cfg, params, batch)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all())
    assert bool(jnp.isfinite(aux["moe_aux"]))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_one_train_step(arch, rng):
    cfg = get_smoke_config(arch)
    mesh = _local_mesh("fsdp_tp")
    shape = ShapeConfig("smoke", 16, 2, "train")
    with jax.set_mesh(mesh):
        fn, _ = make_train_fn(cfg, mesh, "fsdp_tp", shape=shape)
        state = init_train_state(cfg, rng)
        step0 = int(state["step"])
        state, metrics = fn(state, _smoke_batch(cfg, rng))
        assert int(state["step"]) == step0 + 1
        assert bool(jnp.isfinite(metrics["loss"]))
        assert bool(jnp.isfinite(metrics["grad_norm"]))
        for leaf in jax.tree.leaves(state["params"]):
            assert bool(jnp.isfinite(leaf).all())


def test_input_shapes_assignment():
    assert INPUT_SHAPES["train_4k"].seq_len == 4096
    assert INPUT_SHAPES["train_4k"].global_batch == 256
    assert INPUT_SHAPES["prefill_32k"].seq_len == 32_768
    assert INPUT_SHAPES["prefill_32k"].global_batch == 32
    assert INPUT_SHAPES["decode_32k"].global_batch == 128
    assert INPUT_SHAPES["long_500k"].seq_len == 524_288
    assert INPUT_SHAPES["long_500k"].global_batch == 1


def test_long_context_skip_is_whisper_only():
    skips = [a for a in ARCH_IDS if a != "tony-paper-mlp"
             and not get_config(a).supports_long_context]
    assert skips == ["whisper-base"]


def test_moe_active_params_much_smaller_than_total():
    cfg = get_config("llama4-maverick-400b-a17b")
    assert cfg.active_param_count() < cfg.param_count() / 5
    dense = get_config("llama3-405b")
    assert dense.active_param_count() == dense.param_count()


def test_param_counts_match_public_scale():
    """Sanity: assigned configs land near their nameplate sizes."""
    approx = {
        "llama3-405b": 405e9,
        "deepseek-coder-33b": 33e9,
        "qwen3-1.7b": 2e9,
        "llama3.2-3b": 3.2e9,
        "rwkv6-3b": 3.1e9,
        "recurrentgemma-2b": 2.7e9,
        "llama4-maverick-400b-a17b": 400e9,
    }
    for arch, want in approx.items():
        got = get_config(arch).param_count()
        assert 0.5 * want < got < 1.6 * want, (arch, got, want)
