"""The dense decoder family (GQA attention, gated MLP): its FLOP count
and the rules that draw its own parameters."""
import jax
import jax.numpy as jnp


def _d_head(m: dict) -> int:
    return m["d_head"] or m["d_model"] // m["n_heads"]


def proj_weights_per_layer(m: dict) -> int:
    """Q, K, V, O and the three MLP matrices."""
    d, dh = m["d_model"], _d_head(m)
    return (d * (m["n_heads"] + 2 * m["n_kv_heads"]) * dh
            + m["n_heads"] * dh * d + 3 * d * m["d_ff"])


def mixer_flops(m: dict, active: int, live_len: int) -> float:
    """One layer's attention: QK^T and PV over the live positions, 4 x
    heads x head_dim per position (summed over the lanes)."""
    return 4.0 * m["n_heads"] * _d_head(m) * live_len


def _bias(key, sds):
    return (0.02 * jax.random.normal(key, sds.shape, jnp.float32)
            ).astype(sds.dtype)


LEAVES = {"bq": _bias, "bk": _bias, "bv": _bias}
