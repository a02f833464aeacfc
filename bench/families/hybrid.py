"""The hybrid family (Mamba-2 and GQA attention layers in a repeating
period, an expert-parallel MoE FFN): its FLOP count and the rules that
draw its own parameters.  The Mamba-2 and attention parts are the ssm
and dense families' own; a period of `attn_every` layers holds one
attention layer and `attn_every - 1` Mamba-2 layers."""
import math

import jax
import jax.numpy as jnp

from bench.lib.spec import family

_SSM, _DENSE = family("ssm"), family("dense")


def _attn_share(m: dict) -> float:
    return 1.0 / m["attn_every"]


def _moe_weights(m: dict) -> float:
    """Router, shared expert and the expected held-expert share of one
    token: top_k x held / routed experts (1.25 for 9 of 72, top-10)."""
    e, d = m["moe"], m["d_model"]
    routed = e.get("router_experts") or e["n_experts"]
    held = e["top_k"] * e["n_experts"] / routed
    return (d * routed + 3 * d * e["shared_d_ff"] * e["n_shared_experts"]
            + held * 3 * d * e["expert_d_ff"])


def proj_weights_per_layer(m: dict) -> float:
    """The period's mean of the Mamba-2 in/out projections and attention's
    Q, K, V, O, plus every layer's MoE."""
    a = _attn_share(m)
    attn = _DENSE.proj_weights_per_layer(dict(m, d_ff=0))
    return ((1 - a) * _SSM.proj_weights_per_layer(m) + a * attn
            + _moe_weights(m))


def mixer_flops(m: dict, active: int, live_len: int) -> float:
    """The period's mean of the SSD state update and of attention over
    the live positions."""
    a = _attn_share(m)
    return ((1 - a) * _SSM.mixer_flops(m, active, live_len)
            + a * _DENSE.mixer_flops(m, active, live_len))


def _router(key, sds):
    """Unit-variance logits for unit-variance inputs."""
    return (jax.random.normal(key, sds.shape, jnp.float32)
            / math.sqrt(sds.shape[-2])).astype(sds.dtype)


def _conv_bias(key, sds):
    """PyTorch's Conv1d default: uniform in +-1/sqrt(fan_in), fan_in the
    4 taps of a depthwise channel."""
    return jax.random.uniform(key, sds.shape, jnp.float32, -0.5, 0.5
                              ).astype(sds.dtype)


LEAVES = dict(_SSM.LEAVES, router=_router, conv_x_bias=_conv_bias,
              conv_B_bias=_conv_bias, conv_C_bias=_conv_bias)

# the Mamba-2 layers' float32 state rounded to bfloat16: the ssm family's
# reference control, which the hybrid reference takes too
STATE_CONTROLS = _SSM.STATE_CONTROLS
