"""Per-arch smoke tests (deliverable f) + decode/forward consistency.

Every assigned architecture instantiates its reduced config and runs one
forward/train step on CPU, asserting output shapes and finiteness.  The
full configs are exercised only via the dry-run (ShapeDtypeStructs).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, RunConfig, reduced
from repro.models import (decode_step, forward, init, init_cache, loss_fn,
                          n_periods, period_slots)

RC = RunConfig(remat=False, attn_impl="naive")
KEY = jax.random.PRNGKey(0)

ALL_ARCHS = sorted(ARCHS)

# Heavyweight reduced configs (>~4s per jitted train step on CI CPU):
# their end-to-end smokes carry the @slow marker and run in the dedicated
# CI slow job, keeping the default tier-1 gate fast.  The cheap archs
# stay in the default run so every test session still compiles + steps
# real models.
SLOW_ARCHS = frozenset({
    "jamba-1.5-large-398b", "mamba2-780m", "qwen2-moe-a2.7b",
    "llama-3.2-vision-90b", "minitron-4b", "llama4-scout-17b-a16e",
    "musicgen-large", "qwen2-7b", "mistral-nemo-12b",
})


def _slow_param(arch):
    return pytest.param(arch, marks=pytest.mark.slow) \
        if arch in SLOW_ARCHS else arch


def _batch(cfg, b=2, l=32, key=KEY):
    if cfg.family == "audio":
        tok = jax.random.randint(key, (b, l, cfg.audio.n_codebooks), 0,
                                 cfg.vocab)
    else:
        tok = jax.random.randint(key, (b, l), 0, cfg.vocab)
    batch = {"tokens": tok, "targets": tok}
    if cfg.family == "vlm":
        batch["image_embeds"] = jax.random.normal(
            key, (b, cfg.vision.n_image_tokens, cfg.d_model), jnp.bfloat16)
    return batch


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_arch_smoke_forward_shapes_and_finite(arch):
    cfg = reduced(ARCHS[arch])
    params = init(KEY, cfg)
    batch = _batch(cfg)
    logits, aux = forward(params, batch["tokens"], cfg, RC,
                          image_embeds=batch.get("image_embeds"))
    if cfg.family == "audio":
        assert logits.shape == (2, 32, cfg.audio.n_codebooks, cfg.vocab)
    else:
        assert logits.shape == (2, 32, cfg.vocab)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))


@pytest.mark.parametrize("arch", [_slow_param(a) for a in ALL_ARCHS])
def test_arch_smoke_train_step(arch):
    from repro.train import make_train_step
    from repro.optim import make_optimizer
    cfg = reduced(ARCHS[arch])
    params = init(KEY, cfg)
    opt_init, _ = make_optimizer("adamw")
    opt = opt_init(params)
    rc = RunConfig(remat=False, attn_impl="naive", learning_rate=1e-2,
                   warmup_steps=1)
    step = jax.jit(make_train_step(cfg, rc))
    # step 1: past warmup, lr > 0, update visible in bf16
    p2, o2, metrics = step(params, opt, _batch(cfg), jnp.int32(1))
    assert jnp.isfinite(metrics["loss"])
    # parameters actually moved
    moved = any(
        not np.allclose(np.asarray(a, np.float32),
                        np.asarray(b, np.float32))
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p2)))
    assert moved


@pytest.mark.parametrize("arch", [
    "qwen2-7b", "mamba2-780m", "deepseek-v2-lite",
    pytest.param("jamba-1.5-large-398b", marks=pytest.mark.slow),
    pytest.param("qwen2-moe-a2.7b", marks=pytest.mark.slow)])
def test_decode_matches_forward(arch):
    """Prefill-by-decode then compare each step's logits to the full
    forward — exercises KV caches, mamba state recurrences, rope offsets.

    MoE capacity is raised so no tokens drop (batched dispatch drops
    differently than single-token decode — expected capacity-MoE
    behaviour, not a cache bug)."""
    import dataclasses
    cfg = reduced(ARCHS[arch])
    if cfg.moe:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    params = init(KEY, cfg)
    b, l = 2, 12
    toks = jax.random.randint(KEY, (b, l), 0, cfg.vocab)
    full_logits, _ = forward(params, toks, cfg, RC)

    cache = init_cache(cfg, RC, b, 16)
    outs = []
    step = jax.jit(lambda p, c, t, pos: decode_step(p, c, t, pos, cfg, RC))
    for t in range(l):
        lg, cache = step(params, cache, toks[:, t:t + 1], jnp.int32(t))
        outs.append(lg[:, 0])
    dec_logits = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(
        np.asarray(dec_logits, np.float32),
        np.asarray(full_logits, np.float32), rtol=0.08, atol=0.08)


def test_decode_int8_cache_close_to_bf16():
    cfg = reduced(ARCHS["qwen2-7b"])
    params = init(KEY, cfg)
    b, l = 2, 8
    toks = jax.random.randint(KEY, (b, l), 0, cfg.vocab)
    outs = {}
    for dt in ("bfloat16", "int8"):
        rc = RunConfig(remat=False, attn_impl="naive", kv_cache_dtype=dt)
        cache = init_cache(cfg, rc, b, 16)
        step = jax.jit(
            lambda p, c, t, pos: decode_step(p, c, t, pos, cfg, rc))
        for t in range(l):
            lg, cache = step(params, cache, toks[:, t:t + 1],
                             jnp.int32(t))
        outs[dt] = np.asarray(lg, np.float32)
    # int8 cache quantization noise stays small
    rel = np.abs(outs["int8"] - outs["bfloat16"]).max() / (
        np.abs(outs["bfloat16"]).max() + 1e-6)
    assert rel < 0.12, rel


def test_period_structure():
    assert len(period_slots(ARCHS["jamba-1.5-large-398b"])) == 8
    assert n_periods(ARCHS["jamba-1.5-large-398b"]) == 9
    assert len(period_slots(ARCHS["llama-3.2-vision-90b"])) == 5
    assert n_periods(ARCHS["llama-3.2-vision-90b"]) == 20
    assert n_periods(ARCHS["qwen2-7b"]) == 28


def test_param_counts_roughly_match_names():
    """Config param counts land near the advertised sizes."""
    approx = {
        "qwen2-7b": 7.6e9, "qwen1.5-32b": 32e9, "mistral-nemo-12b": 12e9,
        "minitron-4b": 4.2e9, "mamba2-780m": 0.78e9,
        "jamba-1.5-large-398b": 398e9, "deepseek-v2-lite": 15.7e9,
    }
    for name, want in approx.items():
        got = ARCHS[name].param_count()
        assert 0.55 * want < got < 1.7 * want, (name, got, want)


def test_moe_routing_conservation():
    """Disabling noise: MoE output is a convex combination per token."""
    from repro.models.moe import moe_apply, moe_init
    cfg = reduced(ARCHS["qwen2-moe-a2.7b"])
    p = moe_init(KEY, cfg, jnp.float32)
    x = jax.random.normal(KEY, (2, 16, cfg.d_model), jnp.float32)
    y, aux = moe_apply(p, x, cfg)
    assert y.shape == x.shape
    assert jnp.isfinite(aux) and aux >= 0


def test_mamba_chunked_matches_stepwise():
    """SSD chunked scan == token-by-token recurrence (the duality)."""
    from repro.models.mamba2 import ssd_chunked, ssd_decode_step
    b, l, h, p, n = 1, 16, 2, 4, 8
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (b, l, h, p), jnp.float32) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, l, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.2)
    B = jax.random.normal(ks[3], (b, l, 1, n), jnp.float32) * 0.5
    C = jax.random.normal(ks[4], (b, l, 1, n), jnp.float32) * 0.5
    y_chunk, fin = ssd_chunked(x, dt, A, B, C, chunk=4)
    st = jnp.zeros((b, h, n, p))
    ys = []
    for t in range(l):
        yt, st = ssd_decode_step(st, x[:, t], dt[:, t], A, B[:, t],
                                 C[:, t])
        ys.append(yt)
    y_step = jnp.stack(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_chunk), np.asarray(y_step),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(fin), np.asarray(st),
                               rtol=2e-4, atol=2e-4)
