"""The Mamba-2 (SSD) family: its FLOP count and the rules that draw its
own parameters (the published initialisation: A in [1, 16], dt in
[0.001, 0.1])."""
import math

import jax
import jax.numpy as jnp


def _widths(m: dict) -> tuple[int, int]:
    s = m["ssm"]
    di = s["expand"] * m["d_model"]
    return di, di // s["headdim"]


def proj_weights_per_layer(m: dict) -> int:
    """in_proj (z, x, B, C, dt) and out_proj."""
    s, d = m["ssm"], m["d_model"]
    di, nh = _widths(m)
    gn = s["n_groups"] * s["d_state"]
    return d * (2 * di + 2 * gn + nh) + di * d


def mixer_flops(m: dict, active: int, live_len: int) -> float:
    """One layer's SSD state update: 5 x heads x d_state x headdim per
    lane (decay, input outer product, sum, and the C readout's
    multiply-add); the state does not grow with the context."""
    s = m["ssm"]
    _, nh = _widths(m)
    return 5.0 * active * nh * s["d_state"] * s["headdim"]


def _uniform(key, sds, lo, hi):
    return jax.random.uniform(key, sds.shape, jnp.float32, lo, hi
                              ).astype(sds.dtype)


def _dt_bias(key, sds):
    """softplus^-1 of dt drawn log-uniform in [0.001, 0.1]."""
    dt = jnp.exp(_uniform(key, sds, math.log(1e-3), math.log(1e-1)))
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(sds.dtype)


LEAVES = {
    # depthwise conv taps
    "conv_x": lambda key, sds: _uniform(key, sds, -0.5, 0.5),
    "conv_B": lambda key, sds: _uniform(key, sds, -0.5, 0.5),
    "conv_C": lambda key, sds: _uniform(key, sds, -0.5, 0.5),
    "A_log": lambda key, sds: jnp.log(_uniform(key, sds, 1.0, 16.0)),
    "dt_bias": _dt_bias,
    "D": lambda key, sds: _uniform(key, sds, 0.5, 1.5),
}

# the reference controls that bench/calibrate.py reads beside every
# program run: the float32 SSM state rounded to bfloat16 after every
# update, the step below the precision the configuration states
STATE_CONTROLS = {"ssm_state_bf16": {"state_dtype": "bfloat16"}}
