"""The mixture-of-experts family with latent attention (DeepSeek-V2):
its FLOP count and the rules that draw its own parameters.  Each layer
has multi-head latent attention (MLA); the first `first_k_dense_replace`
layers a dense SwiGLU FFN, the others an expert-parallel MoE (router
over all routed experts, the experts held here, the shared experts)."""
import math

import jax
import jax.numpy as jnp


def _mla_weights(m: dict) -> int:
    """Wq, Wkv_a, Wkv_b's two halves (W_UK, W_UV) and Wo."""
    a, d, nh = m["mla"], m["d_model"], m["n_heads"]
    r = a["kv_lora_rank"]
    return (d * nh * (a["qk_nope_head_dim"] + a["qk_rope_head_dim"])
            + d * (r + a["qk_rope_head_dim"])
            + nh * r * (a["qk_nope_head_dim"] + a["v_head_dim"])
            + nh * a["v_head_dim"] * d)


def _moe_weights(m: dict) -> float:
    """Router, shared experts and the expected held-expert share of one
    token: top_k x held / routed experts (0.75 for 8 of 64, top-6)."""
    e, d = m["moe"], m["d_model"]
    routed = e.get("router_experts") or e["n_experts"]
    held = e["top_k"] * e["n_experts"] / routed
    return (d * routed + 3 * d * e["shared_d_ff"]
            + held * 3 * d * e["expert_d_ff"])


def proj_weights_per_layer(m: dict) -> float:
    """The mean over all layers, the leading dense ones included, of
    MLA's projections and the layer's FFN."""
    n, lead = m["n_layers"], m.get("first_k_dense_replace", 0)
    ffn = (lead * 3 * m["d_model"] * m["d_ff"]
           + (n - lead) * _moe_weights(m)) / n
    return _mla_weights(m) + ffn


def mixer_flops(m: dict, active: int, live_len: int) -> float:
    """One layer's absorbed MLA over the live positions (summed over the
    lanes): each head's scores over the latent row [c | k_pe] and its
    values over c, 2 x heads x (kv_lora_rank + qk_rope_head_dim +
    kv_lora_rank) per position."""
    a = m["mla"]
    r = a["kv_lora_rank"]
    return 2.0 * m["n_heads"] * (2 * r + a["qk_rope_head_dim"]) * live_len


def _router(key, sds):
    """Unit-variance logits for unit-variance inputs."""
    return (jax.random.normal(key, sds.shape, jnp.float32)
            / math.sqrt(sds.shape[-2])).astype(sds.dtype)


LEAVES = {"router": _router}
