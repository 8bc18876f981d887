"""Model correctness: decode-vs-forward equivalence per family, masking,
rope, recurrent state carry."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_smoke_config
from repro.models import attention as A
from repro.models import model as M
from repro.models import recurrent as R

DECODE_ARCHS = ["qwen3-1.7b", "llama3.2-3b", "recurrentgemma-2b", "rwkv6-3b",
                "whisper-base", "llama-3.2-vision-90b", "deepseek-coder-33b"]


def _ctx_for(cfg, params, batch):
    if cfg.is_encoder_decoder:
        return M.encode(cfg, params, batch["frames"])
    if cfg.uses_media:
        return batch["media"].astype(jnp.dtype(cfg.dtype))
    return None


def _decode_all(cfg, params, tokens, cache_len, ctx):
    state = M.init_decode_state(cfg, params, tokens.shape[0], cache_len,
                                context=ctx)
    outs = []
    for t in range(tokens.shape[1]):
        lg, state = M.decode_step(cfg, params, state, tokens[:, t:t + 1],
                                  cache_len)
        outs.append(lg)
    return jnp.concatenate(outs, axis=1), state


@pytest.mark.parametrize("arch", DECODE_ARCHS)
def test_decode_matches_forward(arch, rng):
    cfg = get_smoke_config(arch)
    params = M.init_params(cfg, rng)
    B, T = 2, 12
    tokens = jax.random.randint(rng, (B, T), 0, cfg.vocab_size)
    batch = {"tokens": tokens, "labels": tokens}
    if cfg.is_encoder_decoder:
        batch["frames"] = jax.random.normal(rng, (B, cfg.num_media_tokens, cfg.d_model))
    elif cfg.uses_media:
        batch["media"] = jax.random.normal(rng, (B, cfg.num_media_tokens, cfg.d_model))
    full, _ = M.forward(cfg, params, batch)
    dec, _ = _decode_all(cfg, params, tokens, T, _ctx_for(cfg, params, batch))
    assert jnp.max(jnp.abs(dec - full)) < 5e-4


def test_moe_decode_matches_forward_when_no_drops(rng):
    cfg = get_smoke_config("llama4-scout-17b-a16e").replace(capacity_factor=16.0)
    params = M.init_params(cfg, rng)
    tokens = jax.random.randint(rng, (2, 12), 0, cfg.vocab_size)
    full, _ = M.forward(cfg, params, {"tokens": tokens, "labels": tokens})
    dec, _ = _decode_all(cfg, params, tokens, 12, None)
    assert jnp.max(jnp.abs(dec - full)) < 5e-4


def test_sliding_window_decode_ring_buffer(rng):
    """Windowed ring-buffer decode == full forward with the same window."""
    cfg = get_smoke_config("llama3.2-3b").replace(
        num_layers=2, window_size=4,
        block_pattern=(("local", "mlp"),), decode_window=0)
    params = M.init_params(cfg, rng)
    B, T = 2, 12
    tokens = jax.random.randint(rng, (B, T), 0, cfg.vocab_size)
    full, _ = M.forward(cfg, params, {"tokens": tokens, "labels": tokens})
    dec, _ = _decode_all(cfg, params, tokens, T, None)  # local cache = window 4
    assert jnp.max(jnp.abs(dec - full)) < 5e-4


def test_causal_mask_no_future_leak(rng):
    cfg = get_smoke_config("qwen3-1.7b")
    params = M.init_params(cfg, rng)
    t1 = jax.random.randint(rng, (1, 10), 0, cfg.vocab_size)
    t2 = t1.at[:, -1].set((t1[:, -1] + 7) % cfg.vocab_size)
    l1, _ = M.forward(cfg, params, {"tokens": t1, "labels": t1})
    l2, _ = M.forward(cfg, params, {"tokens": t2, "labels": t2})
    # positions before the changed final token must be identical
    assert jnp.max(jnp.abs(l1[:, :-1] - l2[:, :-1])) < 1e-5


def test_encoder_is_bidirectional(rng):
    cfg = get_smoke_config("whisper-base")
    params = M.init_params(cfg, rng)
    frames = jax.random.normal(rng, (1, cfg.num_media_tokens, cfg.d_model))
    f2 = frames.at[:, -1].add(1.0)
    e1 = M.encode(cfg, params, frames)
    e2 = M.encode(cfg, params, f2)
    # changing the LAST frame changes EARLIER encoder outputs (bidirectional)
    assert jnp.max(jnp.abs(e1[:, 0] - e2[:, 0])) > 0


def test_vlm_cross_attention_sees_media(rng):
    cfg = get_smoke_config("llama-3.2-vision-90b")
    params = M.init_params(cfg, rng)
    tokens = jax.random.randint(rng, (1, 8), 0, cfg.vocab_size)
    media1 = jax.random.normal(rng, (1, cfg.num_media_tokens, cfg.d_model))
    l1, _ = M.forward(cfg, params, {"tokens": tokens, "media": media1})
    l2, _ = M.forward(cfg, params, {"tokens": tokens, "media": media1 + 1.0})
    assert jnp.max(jnp.abs(l1 - l2)) > 0


def test_rglru_assoc_scan_vs_sequential(rng):
    cfg = get_smoke_config("recurrentgemma-2b")
    stacked = M.init_params(cfg, rng)["decoder"][0][0]["mixer"]
    p = jax.tree.map(lambda a: a[0], stacked)  # first layer of the scan group
    x = jax.random.normal(rng, (2, 16, cfg.resolved_lru_width))
    h_par = R.rglru_scan(p, x)
    a, b = R._rglru_coeffs(p, x)

    def step(h, ab):
        a_t, b_t = ab
        h = a_t * h + b_t
        return h, h

    _, h_seq = jax.lax.scan(step, jnp.zeros((2, x.shape[-1])),
                            (a.transpose(1, 0, 2), b.transpose(1, 0, 2)))
    assert jnp.max(jnp.abs(h_par - h_seq.transpose(1, 0, 2))) < 1e-5


def test_rwkv_state_carry_matches_split_sequence(rng):
    """Running T steps then continuing == running T+K in one shot."""
    cfg = get_smoke_config("rwkv6-3b")
    params = M.init_params(cfg, rng)
    tokens = jax.random.randint(rng, (1, 16), 0, cfg.vocab_size)
    full, _ = M.forward(cfg, params, {"tokens": tokens, "labels": tokens})
    dec, _ = _decode_all(cfg, params, tokens, 16, None)
    assert jnp.max(jnp.abs(dec - full)) < 5e-4


def test_attention_window_mask():
    m = A.make_mask(6, 6, causal=True, window=3)
    # row 5 can see columns 3,4,5 only
    assert m[5].tolist() == [False, False, False, True, True, True]
    m2 = A.make_mask(4, 4, causal=True, window=0)
    assert m2[2].tolist() == [True, True, True, False]


def test_scan_vs_unrolled_layers_identical(rng):
    cfg = get_smoke_config("qwen3-1.7b").replace(num_layers=4)
    params = M.init_params(cfg, rng)
    tokens = jax.random.randint(rng, (2, 8), 0, cfg.vocab_size)
    batch = {"tokens": tokens, "labels": tokens}
    l_scan, _ = M.forward(cfg, params, batch)
    l_unroll, _ = M.forward(cfg.replace(scan_layers=False), params, batch)
    assert jnp.max(jnp.abs(l_scan - l_unroll)) < 1e-5


def _unscaled_angles(positions, head_dim, theta):
    """rope_angles as it was written before linear scaling."""
    half = head_dim // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freq
    return jnp.sin(ang), jnp.cos(ang)


@pytest.mark.parametrize("positions", [jnp.arange(4096),
                                       jnp.array([[0, 7], [4095, 16383]])])
def test_rope_angles_at_factor_one_trace_the_unscaled_computation(positions):
    """Factor 1.0 divides nothing: the same jaxpr as the unscaled formula,
    so an unscaled model compiles the program it compiled before, and the
    same bits."""
    from repro.models.layers import rope_angles

    want = jax.make_jaxpr(_unscaled_angles, static_argnums=(1, 2))(
        positions, 128, 1e6)
    for got in (jax.make_jaxpr(rope_angles, static_argnums=(1, 2))(
                    positions, 128, 1e6),
                jax.make_jaxpr(rope_angles, static_argnums=(1, 2, 3))(
                    positions, 128, 1e6, 1.0)):
        assert str(got) == str(want)
    for a, b in zip(rope_angles(positions, 128, 1e6),
                    _unscaled_angles(positions, 128, 1e6)):
        assert jnp.array_equal(a, b)


def test_rope_angles_with_linear_scaling_are_at_positions_over_factor():
    from repro.models.layers import rope_angles

    pos = jnp.arange(16384)
    scaled = rope_angles(pos, 128, 1e5, 4.0)
    for a, b in zip(scaled, _unscaled_angles(pos / 4.0, 128, 1e5)):
        assert jnp.array_equal(a, b)
    assert not jnp.allclose(scaled[0], rope_angles(pos, 128, 1e5)[0])
