"""The hybrid configuration as the benchmark reads it: the Granite-4.0-H
file and its cut, the family's FLOP count, and the reference's state
control."""
import json
import os
from types import SimpleNamespace

import numpy as np
import pytest

from bench.lib import check, flops, weights
from bench.lib.spec import Bench, family

from conftest import REPO

NAME = "granite-4.0-h-small-ep8-pp4"


def granite() -> dict:
    with open(os.path.join(REPO, "bench", "configs", f"{NAME}.json")) as f:
        return json.load(f)


def test_model_config_loads_the_file_at_published_widths():
    from repro.configs import ARCHS
    cfg = weights.model_config(granite())
    reg = ARCHS["granite-4.0-h-small"]
    assert cfg.n_layers == 10 and reg.n_layers == 40
    assert (cfg.moe.n_experts, cfg.moe.n_routed, cfg.moe.first_expert,
            cfg.moe.top_k) == (9, 72, 0, 10)
    assert cfg.moe.n_routed == reg.moe.n_experts
    for key in ("d_model", "n_heads", "n_kv_heads", "d_head", "vocab",
                "rope_theta", "tie_embeddings", "attn_every", "ssm"):
        assert getattr(cfg, key) == getattr(reg, key), key
    for key in ("top_k", "expert_d_ff", "shared_d_ff", "n_shared_experts",
                "every_n_layers", "capacity_factor"):
        assert getattr(cfg.moe, key) == getattr(reg.moe, key), key
    assert not cfg.ssm.norm_before_gate and cfg.ssm.conv_bias


@pytest.mark.parametrize("key", ["moe", "n_layers"])
def test_model_config_rejects_a_cut_left_out_of_reduced(key):
    conf = granite()
    del conf["reduced"][key]
    with pytest.raises(ValueError, match=key):
        weights.model_config(conf)


def test_published_keys_agree_with_the_program_keys():
    """The file keeps the source's own keys beside the program's: both
    describe the model as it is run."""
    c = granite()
    s, m = c["ssm"], c["moe"]
    pairs = [
        (c["hidden_size"], c["d_model"]),
        (c["num_attention_heads"], c["n_heads"]),
        (c["num_key_value_heads"], c["n_kv_heads"]),
        (c["vocab_size"], c["vocab"]),
        (c["num_hidden_layers"], c["n_layers"]),
        (c["rms_norm_eps"], c["rmsnorm_eps"]),
        (c["tie_word_embeddings"], c["tie_embeddings"]),
        (c["num_local_experts"], m["n_experts"]),
        (c["num_experts_per_tok"], m["top_k"]),
        (c["intermediate_size"], m["expert_d_ff"]),
        (c["shared_intermediate_size"], m["shared_d_ff"]),
        (c["mamba_d_state"], s["d_state"]),
        (c["mamba_d_conv"], s["d_conv"]),
        (c["mamba_d_head"], s["headdim"]),
        (c["mamba_expand"], s["expand"]),
        (c["mamba_n_groups"], s["n_groups"]),
        (c["mamba_chunk_size"], s["chunk"]),
        (c["mamba_n_heads"], s["expand"] * c["d_model"] // s["headdim"]),
        (c["mamba_conv_bias"], s["conv_bias"]),
        (c["hidden_size"] // c["num_attention_heads"], c["d_head"]),
    ]
    for published, program in pairs:
        assert published == program
    assert c["position_embedding_type"] == "nope" and c["rope_theta"] == 0
    kinds = c["layer_types"]
    assert len(kinds) == c["n_layers"]
    assert [i for i, k in enumerate(kinds) if k == "attention"] == [
        c["attn_every"] // 2]
    assert set(c["reduced"]) >= {"n_layers", "moe", "num_hidden_layers",
                                 "num_local_experts", "layer_types",
                                 "rope_theta"}


def test_hybrid_flop_count():
    c = granite()
    fam = family("hybrid")
    d, di, nh = 4096, 8192, 128
    mamba = d * (2 * di + 2 * 128 + nh) + di * d             # 102.2 M
    attn = d * (32 + 2 * 8) * 128 + 32 * 128 * d             # 41.9 M
    moe = d * 72 + 3 * d * 1536 + 1.25 * 3 * d * 768         # 31.0 M
    want = 0.9 * mamba + 0.1 * attn + moe
    assert fam.proj_weights_per_layer(c) == pytest.approx(want, rel=1e-12)
    assert want == pytest.approx(127.17e6, rel=1e-4)
    active, live = 64, 64 * 400
    ssd = 5.0 * active * nh * 128 * 64
    attention = 4.0 * 32 * 128 * live
    assert fam.mixer_flops(c, active, live) == pytest.approx(
        0.9 * ssd + 0.1 * attention, rel=1e-12)
    step = flops.step_model_flops(c, active, live)
    assert step == pytest.approx(
        2.0 * active * (10 * want + d * 100352)
        + 10 * (0.9 * ssd + 0.1 * attention), rel=1e-12)


def test_leaves_draw_every_parameter_of_the_family():
    """The family's rules draw the router and the conv biases; a served
    tree of the small model holds no leaf without a rule."""
    conf = granite()
    small = dict(conf, n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                 d_head=16, vocab=128, attn_every=2,
                 ssm=dict(conf["ssm"], d_state=16, headdim=16),
                 moe=dict(conf["moe"], n_experts=2, router_experts=8,
                          expert_d_ff=32, shared_d_ff=32, top_k=3))
    small["reduced"] = dict(conf["reduced"], **{k: "small" for k in (
        "d_model", "n_heads", "n_kv_heads", "d_head", "vocab", "attn_every",
        "ssm")})
    cfg = weights.model_config(small)
    params = weights.make_params(cfg, seed=3)
    mamba = params["slots"][0]["mamba"]
    for name in ("conv_x_bias", "conv_B_bias", "conv_C_bias"):
        b = np.asarray(mamba[name], np.float32)
        assert 0 < np.abs(b).max() <= 0.5, name
    router = np.asarray(params["slots"][0]["moe"]["router"])
    assert router.shape == (1, 64, 8)
    assert 0.5 < router.std() * np.sqrt(64) < 1.5


def test_the_state_control_rounds_the_state():
    """The reference with its SSM state rounded to bfloat16 after every
    update, put in the program's place (read on the chip, at the cell's
    size)."""
    bench = Bench()
    conf = granite()
    conf.update(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_head=16,
                vocab=128, attn_every=2, layer_types=["mamba", "attention"],
                param_dtype="float32", compute_dtype="float32",
                ssm=dict(conf["ssm"], d_state=16, headdim=16),
                moe=dict(conf["moe"], n_experts=2, router_experts=8,
                         expert_d_ff=32, shared_d_ff=32, top_k=3))
    conf["reduced"] = dict(conf["reduced"], **{k: "small" for k in (
        "d_model", "n_heads", "n_kv_heads", "d_head", "vocab", "attn_every",
        "ssm", "param_dtype", "compute_dtype")})
    cfg = weights.model_config(conf)
    params = weights.make_params(cfg, seed=5)
    ref = bench.reference("hybrid")
    inputs = np.random.default_rng(1).integers(
        0, cfg.vocab, (2, 48)).astype(np.int32)
    exact = ref.logits(conf, params, inputs)
    rounded = ref.logits(conf, params, inputs, state_dtype="bfloat16")
    assert 0 < np.abs(exact - rounded).max() < 0.05
    picked = [SimpleNamespace(req=SimpleNamespace(prompt=row[:8],
                                                  tokens=row[8:20]))
              for row in inputs]
    gaps = check.control_gaps(ref, conf, params, picked, 24,
                              state_dtype="bfloat16")
    assert gaps.shape == (24,) and (gaps >= 0).all()
