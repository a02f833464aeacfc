"""The DeepSeek-V2-Lite configuration as the benchmark reads it: the file
and its cut, the family's FLOP count and weight rules, the cell's place
in BENCHMARK.json, and a whole run of a tiny cell of the family on the
CPU."""
import json
import os

import numpy as np
import pytest

from bench.lib import flops, weights
from bench.lib.cell import run_cell
from bench.lib.spec import Bench, family

from conftest import REPO, write_tiny_bench

NAME = "deepseek-v2-lite-ep8"
CELL = "deepseek_v2_lite_offline"
SMALL = {"n_layers": 3, "d_model": 64, "n_heads": 4, "d_ff": 96,
         "vocab": 128,
         "mla": {"kv_lora_rank": 32, "qk_nope_head_dim": 16,
                 "qk_rope_head_dim": 8, "v_head_dim": 16}}


def deepseek() -> dict:
    with open(os.path.join(REPO, "bench", "configs", f"{NAME}.json")) as f:
        return json.load(f)


def small(**extra) -> dict:
    conf = deepseek()
    conf.update(SMALL, **extra)
    conf["moe"] = dict(conf["moe"], router_experts=8, n_experts=2,
                       expert_d_ff=32, shared_d_ff=48, top_k=3)
    conf["reduced"] = dict(conf["reduced"], **{
        k: "small" for k in list(SMALL) + list(extra)})
    return conf


def test_model_config_loads_the_file_at_published_widths():
    from repro.configs import ARCHS
    cfg = weights.model_config(deepseek())
    reg = ARCHS["deepseek-v2-lite"]
    assert cfg.n_layers == reg.n_layers == 27
    assert cfg.first_k_dense_replace == 1
    assert (cfg.moe.n_experts, cfg.moe.n_routed, cfg.moe.first_expert,
            cfg.moe.top_k, cfg.moe.norm_topk_prob) == (8, 64, 0, 6, False)
    assert cfg.moe.n_routed == reg.moe.n_experts
    for key in ("d_model", "n_heads", "d_ff", "vocab", "rope_theta",
                "rmsnorm_eps", "tie_embeddings", "mla", "rope_scaling",
                "first_k_dense_replace"):
        assert getattr(cfg, key) == getattr(reg, key), key
    for key in ("top_k", "expert_d_ff", "shared_d_ff", "n_shared_experts",
                "every_n_layers", "capacity_factor", "norm_topk_prob"):
        assert getattr(cfg.moe, key) == getattr(reg.moe, key), key
    assert (cfg.mla.kv_lora_rank, cfg.mla.qk_nope_head_dim,
            cfg.mla.qk_rope_head_dim, cfg.mla.v_head_dim) == (512, 128, 64,
                                                              128)
    assert cfg.rope_scaling.factor == 40
    # the whole cut is the held experts
    assert set(deepseek()["reduced"]) == {"moe"}


def test_model_config_rejects_the_held_experts_left_out_of_reduced():
    conf = deepseek()
    del conf["reduced"]["moe"]
    with pytest.raises(ValueError, match="moe"):
        weights.model_config(conf)


def test_published_keys_agree_with_the_program_keys():
    """The file keeps the source's own keys beside the program's: both
    describe the model as it is run.  `rope_theta`, `rope_scaling` and
    `first_k_dense_replace` are one key each, the source's and the
    program's."""
    c = deepseek()
    m, a = c["moe"], c["mla"]
    pairs = [
        (c["hidden_size"], c["d_model"]),
        (c["num_attention_heads"], c["n_heads"]),
        (c["num_key_value_heads"], c["n_kv_heads"]),
        (c["vocab_size"], c["vocab"]),
        (c["num_hidden_layers"], c["n_layers"]),
        (c["rms_norm_eps"], c["rmsnorm_eps"]),
        (c["tie_word_embeddings"], c["tie_embeddings"]),
        (c["intermediate_size"], c["d_ff"]),
        (c["n_routed_experts"], m["router_experts"]),
        (c["num_experts_per_tok"], m["top_k"]),
        (c["moe_intermediate_size"], m["expert_d_ff"]),
        (c["n_shared_experts"], m["n_shared_experts"]),
        (c["n_shared_experts"] * c["moe_intermediate_size"],
         m["shared_d_ff"]),
        (c["moe_layer_freq"], m["every_n_layers"]),
        (c["norm_topk_prob"], m["norm_topk_prob"]),
        (c["kv_lora_rank"], a["kv_lora_rank"]),
        (c["qk_nope_head_dim"], a["qk_nope_head_dim"]),
        (c["qk_rope_head_dim"], a["qk_rope_head_dim"]),
        (c["v_head_dim"], a["v_head_dim"]),
        (c["attention_bias"], c["qkv_bias"]),
    ]
    for published, program in pairs:
        assert published == program
    assert c["q_lora_rank"] is None and c["scoring_func"] == "softmax"
    assert c["routed_scaling_factor"] == 1 and c["topk_method"] == "greedy"
    assert c["rope_scaling"]["type"] == "yarn"
    assert c["cache_dtypes"] == {"latent": "bfloat16"}


def test_moe_flop_count():
    """By hand at the published widths: per layer MLA 13.76 M weights
    (Wq 2048 x 3072, Wkv_a 2048 x 576, W_UK and W_UV 16 x 512 x 128,
    Wo 2048 x 2048); layer 0 a SwiGLU of 10944, layers 1-26 the router,
    the shared SwiGLU of 2816 and 6 x 8 / 64 of an expert of 1408."""
    c = deepseek()
    fam = family("moe")
    d = 2048
    mla = d * 3072 + d * 576 + 2 * 16 * 512 * 128 + 2048 * d
    assert mla == 13_762_560
    dense = 3 * d * 10944
    moe = d * 64 + 3 * d * 2816 + 0.75 * 3 * d * 1408
    want = mla + (dense + 26 * moe) / 27
    assert fam.proj_weights_per_layer(c) == pytest.approx(want, rel=1e-12)
    active, live = 128, 128 * 400
    attention = 2.0 * 16 * (512 + 64 + 512) * live
    assert fam.mixer_flops(c, active, live) == pytest.approx(attention)
    assert flops.step_model_flops(c, active, live) == pytest.approx(
        2.0 * active * (27 * want + d * 102400) + 27 * attention,
        rel=1e-12)


def test_leaves_draw_every_parameter_of_the_family():
    """The family's rules draw the router; a served tree of the small
    model holds no leaf without a rule, the leading dense layer's
    included."""
    cfg = weights.model_config(small())
    params = weights.make_params(cfg, seed=3)
    assert set(params) == {"embed", "lm_head", "final_norm", "lead",
                           "slots"}
    router = np.asarray(params["slots"][0]["moe"]["router"])
    assert router.shape == (2, 64, 8)
    assert 0.5 < router.std() * np.sqrt(64) < 1.5
    mla = params["lead"][0]["mla"]
    assert set(mla) == {"wq", "wkv_a", "kv_norm", "w_uk", "w_uv", "wo"}
    assert mla["w_uk"]["q"].shape == (1, 4, 32, 16)
    assert mla["w_uk"]["scale"].shape == (1, 4, 16)
    # the latent's norm gain: U(0.8, 1.2), rounded to bfloat16
    g = np.asarray(mla["kv_norm"]["scale"], np.float32)
    assert 0.79 <= g.min() and g.max() <= 1.21 and g.std() > 0.05


def test_the_cell_reports_the_six_metrics_and_not_the_int8_roofline():
    spec = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    cell = next(w for w in spec["workloads"] if w["name"] == CELL)
    assert cell["config"] == NAME and cell["chips"] == 1
    assert cell["traffic"] == "offline" and len(cell["why"]) <= 200
    listed = {m["name"] for m in spec["end_to_end"] + spec["per_layer"]
              if CELL in m.get("workloads", [])}
    assert listed == {"output_tokens_per_s", "peak_hbm_gib",
                      "host_ms_per_step.offline", "step_device_ms.offline",
                      "step_mfu.offline", "idle_share.offline"}
    assert Bench().cell(CELL) == {"slots": 128, "max_len": 1536,
                                  "block_size": 16, "rate_req_s": None,
                                  "limits": Bench().cell(CELL)["limits"]}


def test_a_tiny_cell_of_the_family_runs_correct(tmp_path):
    """A whole traced run of a tiny DeepSeek-shaped cell on the CPU, past
    the harness's look for a chip: it serves, compares correct against
    the family's reference and holds its latent cache at the stated
    precision."""
    bench_dir = write_tiny_bench(str(tmp_path))
    conf = small(name="tiny-moe")
    with open(os.path.join(bench_dir, "configs", "tiny-moe.json"), "w") as f:
        json.dump(conf, f)
    with open(os.path.join(bench_dir, "cells", "moe_offline.json"),
              "w") as f:
        json.dump({"slots": 4, "max_len": 32, "block_size": 4,
                   "rate_req_s": None, "limits": {"max_gap": 0.05}}, f)
    spec_path = tmp_path / "BENCHMARK.json"
    spec = json.load(open(spec_path))
    spec["workloads"].append({"name": "moe_offline", "config": "tiny-moe",
                              "traffic": "tiny_offline", "chips": 1,
                              "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"].append("moe_offline")
    json.dump(spec, open(spec_path, "w"))
    bench = Bench(root=bench_dir, spec_path=spec_path)
    r = run_cell(bench, "moe_offline", seed=2 ** 32 + 77, seconds=1.0,
                 trace=True, t_start=0.0, require_tpu=False)
    assert r["correct"] is True
    assert r["checks"]["cache_bits_short"]["value"] == 0
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["run"]["tokens_compared"] > 0
    assert r["run"]["compiles_in_window"] == 0
    assert {"step_mfu.offline", "step_device_ms.offline"} <= set(
        r["metrics"])
