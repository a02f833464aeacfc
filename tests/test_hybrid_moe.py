"""The Mamba-2 + GQA hybrid with an expert-parallel MoE in every layer
(granite-4.0-h-small) against the benchmark's plain float32 reference
(bench/reference/hybrid.py), at a small Granite-shaped size on the CPU,
on the benchmark's seeded INT8 weights.

The small model keeps every mechanism of the published one: a period of
4 layers with attention mid-period and no positional embedding, Mamba-2
with the gate before the norm and a conv bias, and an MoE whose router
scores 8 experts (top-3) of which this share holds 2, numbered from 2,
beside one shared expert.

Tolerances, as max |logit difference| (max |logit| is about 0.55 here:
the head is tied to the 0.02-scale embedding):
  * float32 model: 0.02.  Same weights, float32 throughout, except the
    conv carry, which the program keeps in bfloat16 whatever the model's
    dtype (measured 0.0046).
  * bfloat16 model as served: 0.25.  Activations round to bfloat16 in
    every layer (measured 0.10).
A wrong equation is far outside the float32 one: the gate on the other
side of the norm moves the logits by 0.55, the wrong share of experts by
0.29, the conv bias left out by more than 0.1.
"""
import dataclasses
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
from repro.configs import ARCHS, SHAPES, RunConfig, reduced  # noqa: E402
from repro.configs.base import ShapeConfig  # noqa: E402
from repro.configs.granite_4_0_h_small import MULTIPLIERS  # noqa: E402
from repro.core.llm_workloads import gemms_of_model  # noqa: E402
from repro.models.model import (fold_multipliers, init_paged_cache,  # noqa
                                period_slots)
from repro.models.moe import moe_apply, moe_init  # noqa: E402
from repro.serving import DecodeCore  # noqa: E402

GRANITE = ARCHS["granite-4.0-h-small"]
CONFIG_FILE = "granite-4.0-h-small-ep8-pp4"
SMALL = {"n_layers": 4, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
         "d_head": 16, "vocab": 256, "attn_every": 4,
         "layer_types": ["mamba", "mamba", "attention", "mamba"],
         "ssm": {"d_state": 16, "d_conv": 4, "headdim": 16, "expand": 2,
                 "chunk": 32, "n_groups": 1, "norm_before_gate": False,
                 "conv_bias": True},
         "moe": {"n_experts": 2, "top_k": 3, "n_shared_experts": 1,
                 "expert_d_ff": 32, "shared_d_ff": 48, "every_n_layers": 1,
                 "capacity_factor": 4.0, "router_aux_loss": 0.001,
                 "router_experts": 8, "first_expert": 2}}
TOL = {"float32": 0.02, "bfloat16": 0.25}
SEED = 2 ** 33 + 5


def small_model(dtype="float32", seed=SEED, **moe):
    bench = Bench()
    conf = bench.config(CONFIG_FILE)
    conf.update(SMALL, name="small", param_dtype=dtype, compute_dtype=dtype)
    conf["moe"] = dict(SMALL["moe"], **moe)
    conf["reduced"] = dict(conf["reduced"], **{
        k: "small" for k in list(SMALL) + ["param_dtype", "compute_dtype"]})
    cfg = weights.model_config(conf)
    return conf, cfg, weights.make_params(cfg, seed)


def engine_logits(cfg, params, prompt, n_new, kv_dtype):
    """Prefill the prompt through the engine's batch step one token a
    step over the paged cache, then decode greedily; returns the fed
    tokens and every step's logits."""
    rc = RunConfig(attn_impl="naive", remat=False, kv_cache_dtype=kv_dtype)
    core = DecodeCore(cfg, rc, params, quantize=True, plan_batch=2,
                      plan_max_len=16)
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


def test_period_puts_attention_mid_period_and_moe_everywhere():
    slots = period_slots(GRANITE)
    assert [s.mixer for s in slots].index("attn") == 5
    assert [s.mixer for s in slots].count("attn") == 1
    assert all(s.ffn == "moe" for s in slots)
    jamba = period_slots(ARCHS["jamba-1.5-large-398b"])
    assert [s.mixer for s in jamba].index("attn") == 4
    assert [s.ffn for s in jamba] == ["dense", "moe"] * 4


@pytest.mark.parametrize("dtype", sorted(TOL))
def test_engine_matches_reference(dtype, served_f32):
    if dtype == "float32":
        conf, cfg, params, fed, got = served_f32
    else:
        conf, cfg, params = small_model(dtype)
        fed, got = engine_logits(cfg, params, _prompt(cfg), 4, "bfloat16")
    ref = Bench().reference("hybrid")
    assert np.abs(got - ref.logits(conf, params, fed)).max() <= TOL[dtype]
    # the decoded (greedy) tokens sit at or near the reference's best
    targets = fed[:, 1:].copy()
    targets[:, :7] = -1
    gaps = ref.logit_gaps(conf, params, fed[:, :-1], targets)
    assert np.nanmax(gaps) <= 2 * TOL[dtype]


def test_reference_tells_gate_order_conv_bias_and_share_apart(served_f32):
    conf, cfg, params, fed, got = served_f32
    ref = Bench().reference("hybrid")
    no_bias = jax.tree_util.tree_map_with_path(
        lambda p, a: (jnp.zeros_like(a)
                      if str(getattr(p[-1], "key", "")).endswith("_bias")
                      else a), params)
    wrong = {
        "gate order": (dict(conf, ssm=dict(conf["ssm"],
                                           norm_before_gate=True)), params),
        "conv bias": (conf, no_bias),
        "share": (dict(conf, moe=dict(conf["moe"], first_expert=0)),
                  params),
    }
    for name, (c, p) in wrong.items():
        assert np.abs(got - ref.logits(c, p, fed)).max() > 5 * TOL[
            "float32"], name


@pytest.mark.parametrize("force_buffered", [False, True])
def test_expert_shares_sum_to_the_whole_layer(force_buffered):
    """Four shares of 2 experts each: their outputs, with the shared
    expert counted once, add up to the uncut 8-expert layer's."""
    whole = dataclasses.replace(
        reduced(GRANITE), param_dtype="float32", compute_dtype="float32",
        moe=dataclasses.replace(reduced(GRANITE).moe, n_experts=8, top_k=3,
                                capacity_factor=8 / 3))
    params = moe_init(jax.random.PRNGKey(3), whole, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 5, whole.d_model))
    with jax.default_matmul_precision("highest"):
        want, _ = moe_apply(params, x, whole, force_buffered=force_buffered)
        parts = []
        for first in range(0, 8, 2):
            share = dataclasses.replace(whole, moe=dataclasses.replace(
                whole.moe, n_experts=2, router_experts=8,
                first_expert=first))
            held = dict(params, **{
                w: params[w][first:first + 2]
                for w in ("w_gate", "w_up", "w_down")})
            y, _ = moe_apply(held, x, share, force_buffered=force_buffered)
            parts.append(y)
        shared = moe_apply(dict(params, w_down=jnp.zeros_like(
            params["w_down"])), x, whole)[0]
    got = sum(parts) - 3 * shared
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=1e-5)
    # a share alone is not the layer
    assert np.abs(np.asarray(parts[0] - want)).max() > 1e-2


def test_multiplier_fold_is_exact():
    """The published form, the four scalars explicit on unfolded
    weights, equals the folded form on folded weights: in the reference
    to float32 rounding, and through the program's engine to the
    float32 tolerance."""
    conf, cfg, params = small_model("float32", seed=7)
    folded = fold_multipliers(params, cfg, **MULTIPLIERS)
    ref = Bench().reference("hybrid")
    fed, got = engine_logits(cfg, folded, _prompt(cfg, 2), 4, "float32")
    published = ref.logits(conf, params, fed, published=True)
    scale = np.abs(published).max()
    assert np.abs(ref.logits(conf, folded, fed) - published).max() \
        <= 1e-5 * scale
    assert np.abs(got - published).max() <= TOL["float32"] * scale / 0.55
    # the scalars matter: unfolded weights without them are another model
    assert np.abs(ref.logits(conf, params, fed) - published).max() > scale


@pytest.mark.parametrize("batch", [1, 8, 64, 256])
def test_planner_expert_rows_fit_the_tokens_of_a_held_share(batch):
    """9 of 72 experts held, top-10: each held expert sees batch x 10 /
    72 rows, never more than the batch; its 9 experts' GEMMs counted."""
    cfg = dataclasses.replace(GRANITE, n_layers=10, moe=dataclasses.replace(
        GRANITE.moe, n_experts=9, router_experts=72))
    gemms = gemms_of_model(cfg, ShapeConfig("d", 1536, batch, "decode"))
    experts = [g for g in gemms if "expert-" in g.label]
    assert len(experts) == 3
    for g in experts:
        assert g.M == max(1, batch * 10 // 72) <= batch
        assert g.count == 10 * 9
    # the uncut model's rows are unchanged by the held share
    whole = gemms_of_model(GRANITE, SHAPES["decode_32k"])
    assert {g.M for g in whole if "expert-" in g.label} == {128 * 10 // 72}
