"""Latent attention (MLA), YaRN RoPE, a leading dense layer and an
unnormalised expert-parallel MoE (deepseek-v2-lite) against the
benchmark's plain float32 reference (bench/reference/moe.py), at a small
DeepSeek-shaped size on the CPU, on the benchmark's seeded INT8 weights.

The small model keeps every mechanism of the published one: latent
attention (latent 32, per head 16 + 8 rotated query and key channels,
values of 16) with YaRN over the published 4096 x 40, one leading dense
layer ahead of two scanned MoE layers whose router scores 8 experts
(top-3, gates as the softmax gives them) of which this share holds 2,
numbered from 2, beside the shared experts.

Tolerances, as max |logit difference| (max |logit| is about 4.7 here:
the untied head is a unit-variance projection):
  * float32 model: 1e-3.  Same weights, float32 throughout, the
    absorbed decode form against the reference's unabsorbed one
    (measured 5.0e-6).
  * bfloat16 model as served: 0.2.  Activations and the latent cache
    round to bfloat16 in every layer (measured 0.115).
Each departure from the published equations is far outside the float32
one and outside the bfloat16 one too: the gates renormalised moves the
logits by 0.35, the wrong share of experts by 1.1, plain RoPE in place
of YaRN by 2.4, the latent's norm left out by 2.9, the rotated channels
paired as halves instead of interleaved by 4.3.
"""
import dataclasses
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from bench.lib import weights  # noqa: E402
from bench.lib.spec import Bench  # noqa: E402
from repro.configs import ARCHS, RunConfig, reduced  # noqa: E402
from repro.models import decode_step, forward, init, init_cache  # noqa
from repro.models.layers import apply_rope, rope_freqs  # noqa: E402
from repro.models.model import (init_paged_cache, latent_width,  # noqa
                                layer_groups)
from repro.models.attention import mla_scale  # noqa: E402
from repro.models.moe import moe_apply, moe_init  # noqa: E402
from repro.quant import quantize_model_params  # noqa: E402
from repro.serving import ContinuousBatchingEngine, DecodeCore  # noqa
from repro.serving.scheduler import Request  # noqa: E402

DEEPSEEK = ARCHS["deepseek-v2-lite"]
CONFIG_FILE = "deepseek-v2-lite-ep8"
SMALL = {"n_layers": 3, "d_model": 64, "n_heads": 4, "d_ff": 96,
         "vocab": 256,
         "mla": {"kv_lora_rank": 32, "qk_nope_head_dim": 16,
                 "qk_rope_head_dim": 8, "v_head_dim": 16},
         "moe": {"n_experts": 2, "top_k": 3, "n_shared_experts": 2,
                 "expert_d_ff": 32, "shared_d_ff": 48, "every_n_layers": 1,
                 "capacity_factor": 4.0, "router_aux_loss": 0.001,
                 "router_experts": 8, "first_expert": 2,
                 "norm_topk_prob": False}}
TOL = {"float32": 1e-3, "bfloat16": 0.2}
SEED = 2 ** 33 + 5


def small_model(dtype="float32", seed=SEED):
    bench = Bench()
    conf = bench.config(CONFIG_FILE)
    conf.update(SMALL, name="small", param_dtype=dtype, compute_dtype=dtype)
    conf["reduced"] = dict(conf["reduced"], **{
        k: "small" for k in list(SMALL) + ["param_dtype", "compute_dtype"]})
    cfg = weights.model_config(conf)
    return conf, cfg, weights.make_params(cfg, seed)


def engine_logits(cfg, params, prompt, n_new, kv_dtype, precision="int8"):
    """Prefill the prompt through the engine's batch step one token a
    step over the paged latent cache, then decode greedily; returns the
    fed tokens and every step's logits."""
    rc = RunConfig(attn_impl="naive", remat=False, kv_cache_dtype=kv_dtype)
    core = DecodeCore(cfg, rc, params, quantize=True, plan_batch=2,
                      plan_max_len=16, precision=precision)
    step = core.batch_step_for(core.plan_table)
    b, bs, nb = prompt.shape[0], 4, 4
    cache = init_paged_cache(cfg, rc, b, b * nb, bs)
    tables = np.arange(b * nb, dtype=np.int32).reshape(b, nb)
    fed, out = [], []
    tok = prompt[:, :1]
    for t in range(prompt.shape[1] + n_new):
        logits, cache = step(core.params, cache, jnp.asarray(tok),
                             np.full(b, t, np.int32), np.ones(b, bool),
                             tables)
        last = np.asarray(logits[:, -1], np.float32)
        fed.append(tok[:, 0])
        out.append(last)
        tok = (prompt[:, t + 1:t + 2] if t + 1 < prompt.shape[1]
               else last.argmax(-1)[:, None].astype(np.int32))
    return np.stack(fed, 1), np.stack(out, 1)


def _prompt(cfg, seed=1, length=8):
    return np.random.default_rng(seed).integers(
        0, cfg.vocab, (2, length)).astype(np.int32)


@pytest.fixture(scope="module")
def served_f32():
    conf, cfg, params = small_model("float32")
    fed, got = engine_logits(cfg, params, _prompt(cfg), 4, "float32")
    return conf, cfg, params, fed, got


def test_layer_groups_put_the_dense_layer_ahead_of_the_scan():
    groups = layer_groups(DEEPSEEK)
    assert [(name, [(s.mixer, s.ffn) for s in slots], n)
            for name, slots, n in groups] == [
        ("lead", [("mla", "dense")], 1), ("slots", [("mla", "moe")], 26)]
    cache = jax.eval_shape(lambda: init_paged_cache(
        DEEPSEEK, RunConfig(), 4, 8, 16))
    assert [c["latent"].shape for c in cache] == [(1, 8, 16, 640),
                                                  (26, 8, 16, 640)]
    assert latent_width(DEEPSEEK) == 640 >= DEEPSEEK.mla.latent_dim == 576


@pytest.mark.parametrize("dtype", sorted(TOL))
def test_engine_matches_reference(dtype, served_f32):
    if dtype == "float32":
        conf, cfg, params, fed, got = served_f32
    else:
        conf, cfg, params = small_model(dtype)
        fed, got = engine_logits(cfg, params, _prompt(cfg), 4, "bfloat16")
    ref = Bench().reference("moe")
    assert np.abs(got - ref.logits(conf, params, fed)).max() <= TOL[dtype]
    # the decoded (greedy) tokens sit at or near the reference's best
    targets = fed[:, 1:].copy()
    targets[:, :7] = -1
    gaps = ref.logit_gaps(conf, params, fed[:, :-1], targets)
    assert np.nanmax(gaps) <= 2 * TOL[dtype]


@pytest.mark.parametrize("departure", ["plain rope", "renormalised gates",
                                       "halves pair order", "no kv_a norm",
                                       "other share"])
def test_reference_tells_each_departure_apart(departure, served_f32):
    """Each departure from the published equations, put in the
    reference, misses the served logits by more than the bfloat16
    tolerance, let alone the float32 one."""
    conf, cfg, params, fed, got = served_f32
    ref = Bench().reference("moe")
    conf_, kw = {
        "plain rope": (dict(conf, rope_scaling=None), {}),
        "renormalised gates": (dict(conf, moe=dict(
            conf["moe"], norm_topk_prob=True)), {}),
        "halves pair order": (conf, {"interleaved": False}),
        "no kv_a norm": (conf, {"kv_norm": False}),
        "other share": (dict(conf, moe=dict(conf["moe"], first_expert=0)),
                        {}),
    }[departure]
    assert np.abs(got - ref.logits(conf_, params, fed, **kw)).max() \
        > TOL["bfloat16"]


def test_absorbed_decode_equals_the_unabsorbed_forward():
    """Token-by-token decode through the contiguous latent cache (W_UK
    in the query, W_UV on the output) gives the full-sequence forward's
    logits (keys and values projected up from the latent), float32."""
    cfg = dataclasses.replace(reduced(DEEPSEEK), param_dtype="float32",
                              compute_dtype="float32")
    rc = RunConfig(remat=False, attn_impl="naive", kv_cache_dtype="float32")
    params = init(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, cfg.vocab)
    full, _ = forward(params, toks, cfg, rc)
    cache = init_cache(cfg, rc, 2, 16)
    step = jax.jit(lambda p, c, t, pos: decode_step(p, c, t, pos, cfg, rc))
    outs = []
    for t in range(12):
        lg, cache = step(params, cache, toks[:, t:t + 1], jnp.int32(t))
        outs.append(lg[:, 0])
    # float32 throughout: the two forms differ by reassociation alone
    # (measured 6.1e-7 of max |logit| 0.76)
    np.testing.assert_allclose(np.asarray(jnp.stack(outs, 1)),
                               np.asarray(full), rtol=0, atol=1e-5)


@pytest.mark.parametrize("force_buffered", [False, True])
def test_expert_shares_sum_to_the_whole_layer(force_buffered):
    """Four shares of 2 experts each, gates unnormalised: their outputs,
    with the shared experts counted once, add up to the uncut 8-expert
    layer of the reference, on the same INT8 weights."""
    small = reduced(DEEPSEEK)
    whole = dataclasses.replace(
        small, param_dtype="float32", compute_dtype="float32",
        moe=dataclasses.replace(small.moe, n_experts=8, top_k=3,
                                capacity_factor=8 / 3))
    assert not whole.moe.norm_topk_prob
    params = quantize_model_params(
        moe_init(jax.random.PRNGKey(3), whole, jnp.float32))
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 5, whole.d_model))
    with jax.default_matmul_precision("highest"):
        want = Bench().reference("moe")._moe(
            x.reshape(-1, whole.d_model), params,
            dataclasses.asdict(whole.moe)).reshape(x.shape)
        parts = []
        for first in range(0, 8, 2):
            share = dataclasses.replace(whole, moe=dataclasses.replace(
                whole.moe, n_experts=2, router_experts=8,
                first_expert=first))
            held = dict(params, **{
                w: {k: a[first:first + 2] for k, a in params[w].items()}
                for w in ("w_gate", "w_up", "w_down")})
            y, _ = moe_apply(held, x, share, force_buffered=force_buffered)
            parts.append(y)
        no_experts = dict(params, w_down=dict(
            params["w_down"], scale=jnp.zeros_like(
                params["w_down"]["scale"])))
        shared = moe_apply(no_experts, x, whole)[0]
    got = sum(parts) - 3 * shared
    # float32, the same dequantized weights: reassociation alone
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=1e-5)
    assert np.abs(np.asarray(parts[0] - want)).max() > 1e-2


def test_yarn_frequencies_and_scale_at_published_widths():
    """d = 64, base 10000, factor 40 over 4096: the ramp runs from pair
    10 to pair 23; the score scale is 192^-1/2 (0.1 x 0.707 ln 40 + 1)^2
    = 0.114721."""
    rs = DEEPSEEK.rope_scaling
    f = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    got = np.asarray(rope_freqs(64, 10000.0, rs))
    np.testing.assert_allclose(got[:11], f[:11], rtol=1e-6)
    np.testing.assert_allclose(got[23:], f[23:] / 40, rtol=1e-6)
    ramp = (np.arange(11, 23) - 10) / 13
    np.testing.assert_allclose(got[11:23], f[11:23] / 40 * ramp
                               + f[11:23] * (1 - ramp), rtol=1e-6)
    m = 0.1 * 0.707 * math.log(40) + 1
    assert m == pytest.approx(1.26080, abs=1e-5)
    assert mla_scale(DEEPSEEK) == pytest.approx(192 ** -0.5 * m * m)
    assert mla_scale(DEEPSEEK) == pytest.approx(0.114721, abs=1e-6)


def test_interleaved_rope_is_halves_rope_of_the_deinterleaved_vector():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 3, 8))
    pos = jnp.arange(5)[None, :].repeat(2, 0)
    de = jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)
    np.testing.assert_allclose(
        np.asarray(apply_rope(x, pos, 1e4, interleaved=True)),
        np.asarray(apply_rope(de, pos, 1e4)), rtol=0, atol=1e-6)


def test_int8_latent_cache_is_refused():
    with pytest.raises(NotImplementedError, match="int8"):
        init_paged_cache(reduced(DEEPSEEK),
                         RunConfig(kv_cache_dtype="int8"), 2, 4, 4)


def test_int4_control_runs_through_the_absorbed_path():
    """The benchmark's INT4 control (its weights requantized by the
    program's own quantizer) serves through the same step: W_UK, whose
    input channels the absorbed query does not hold, unpacks to its own
    rows.  It is finite and further from the reference than INT8."""
    conf, cfg, params = small_model("float32")
    ref = Bench().reference("moe")
    q4 = weights.requantized(params, "int4")
    fed, got = engine_logits(cfg, q4, _prompt(cfg), 4, "float32",
                             precision="int4")
    assert np.isfinite(got).all()
    assert np.abs(got - ref.logits(conf, params, fed)).max() \
        > 10 * TOL["float32"]


def test_engine_reports_the_latent_footprint():
    cfg = reduced(DEEPSEEK)
    rc = RunConfig(attn_impl="naive", remat=False)
    core = DecodeCore(cfg, rc, init(jax.random.PRNGKey(0), cfg),
                      quantize=True, plan_batch=2, plan_max_len=16)
    engine = ContinuousBatchingEngine(core, n_slots=2, max_len=16,
                                      block_size=4)
    engine.run([Request(rid=0, prompt=np.arange(5, dtype=np.int32),
                        max_new_tokens=3)])
    kv = engine.telemetry()["aggregate"]["kv_blocks"]
    # 32 + 8 latent values, padded to 128, in bfloat16
    assert kv["bytes_per_token_per_layer"] == {"mla": 128 * 2}
    assert len(engine.completed[0].tokens) == 3
