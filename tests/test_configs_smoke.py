"""Per-architecture smoke tests (assignment requirement): a REDUCED config
of the same family runs one forward/train step on CPU, asserting output
shapes and no NaNs."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCHS, get_arch, reduced
from repro.models import Model


def _batch(cfg, b=2, s=16):
    batch = {"tokens": jnp.ones((b, s), jnp.int32),
             "labels": jnp.ones((b, s), jnp.int32)}
    if cfg.family == "audio":
        batch["audio_frames"] = jnp.ones(
            (b, cfg.num_audio_frames, cfg.d_model), jnp.bfloat16)
    if cfg.family == "vlm":
        batch["vision_embeds"] = jnp.ones((b, s // 4, cfg.d_model),
                                          jnp.bfloat16)
        batch["positions"] = jnp.broadcast_to(
            jnp.arange(s, dtype=jnp.int32)[None, None], (3, b, s))
    return batch


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_forward_train_step(arch):
    cfg = reduced(get_arch(arch))
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    loss, metrics = jax.jit(model.forward_train)(params, _batch(cfg))
    assert loss.shape == ()
    assert jnp.isfinite(loss), f"{arch}: loss not finite"
    assert jnp.isfinite(metrics["ce"])
    if cfg.moe is not None:
        assert metrics["aux"] > 0, f"{arch}: MoE aux loss should be > 0"


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_one_train_update(arch):
    from repro.train.loop import make_train_step
    from repro.train.optimizer import optimizer_for, schedule_for
    cfg = reduced(get_arch(arch))
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    opt = optimizer_for(cfg)
    step_fn = jax.jit(make_train_step(model, opt,
                                      schedule_for(cfg.name, 1e-3, 100)))
    p, o, m = step_fn(params, opt.init(params), _batch(cfg),
                      jnp.asarray(0, jnp.int32))
    assert jnp.isfinite(m["loss"])
    assert jnp.isfinite(m["gnorm"])
    # params actually changed
    moved = any(
        float(jnp.max(jnp.abs(a.astype(jnp.float32)
                              - b.astype(jnp.float32)))) > 0
        for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(params)))
    assert moved, f"{arch}: update did not change params"


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_prefill_decode_shapes(arch):
    cfg = reduced(get_arch(arch))
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    b, s, mx = 2, 8, 32
    cache = model.init_cache(b, mx)
    batch = _batch(cfg, b, s)
    batch.pop("labels")
    if cfg.family == "vlm":
        batch["vision_embeds"] = batch["vision_embeds"][:, :2]
    logits, cache = jax.jit(model.prefill)(params, batch, cache)
    assert logits.shape == (b, 1, cfg.vocab_size)
    dec = {"tokens": jnp.ones((b, 1), jnp.int32)}
    if cfg.family == "vlm":
        dec["positions"] = jnp.full((3, b, 1), s, jnp.int32)
    lg, cache = jax.jit(model.decode_step)(params, dec, cache,
                                           jnp.asarray(s, jnp.int32))
    assert lg.shape == (b, 1, cfg.vocab_size)
    assert bool(jnp.isfinite(lg).all()), f"{arch}: decode logits not finite"


def test_param_counts_sane():
    # full configs should be within ~35% of the published sizes
    expected = {
        "llama3.2-3b": 3.2e9, "qwen1.5-32b": 32.5e9, "gemma2-27b": 27e9,
        "minicpm-2b": 2.7e9, "qwen2-vl-2b": 1.5e9,
        "qwen3-moe-235b-a22b": 235e9, "jamba-1.5-large-398b": 398e9,
        "whisper-base": 74e6, "xlstm-1.3b": 1.3e9,
        # published config.json: one dense layer, 26 MoE layers of 64
        # experts + 2 shared, MLA, untied vocabulary -> 15.96B
        "moonlight-16b-a3b": 16.0e9,
    }
    for name, target in expected.items():
        model = Model(get_arch(name))
        n = sum(s.size for s in jax.tree.leaves(model.param_structs()))
        assert 0.55 * target < n < 1.6 * target, \
            f"{name}: {n/1e9:.2f}B vs expected {target/1e9:.1f}B"
