"""Low-bit weight formats for the widened What axis: packed INT4 and
scaled FP8 alongside the paper's INT8 evaluation precision.

Formats (pytree sub-trees; the dict *key* is the jit-static format
discriminator `models.layers.linear` dispatches on):

  {"q":  int8 (K, N),              "scale": f32 (N,)}   INT8 (quant.int8)
  {"q4": int8 (ceil(K/2), N),      "scale": f32 (N,)}   packed INT4
  {"qf8": float8_e4m3fn (K, N),    "scale": f32 (N,)}   scaled FP8

INT4 packs two signed nibbles per int8 byte along K (even K-rows in the
low nibble, odd rows in the high nibble) with a per-output-channel /7
symmetric scale; unpacking recovers the signed nibbles with arithmetic
shifts.  FP8 stores e4m3 elements with a per-output-channel scale that
maps each column's max-abs onto the e4m3 dynamic range.  Both formats
reuse the INT8 epilogue-fused contraction structure: contract against
the raw low-bit weight in x.dtype, scale the O(batch·d_out) output.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .int8 import scaled_contract

FP8_DTYPE = jnp.float8_e4m3fn
FP8_MAX = 448.0          # e4m3 finite max


# --- INT4: pack / unpack ----------------------------------------------------

def quantize_weight_int4(w):
    """(K, N) -> (packed int8 (ceil(K/2), N), f32 (N,)) per-channel /7."""
    w = w.astype(jnp.float32)
    scale = jnp.max(jnp.abs(w), axis=0) / 7.0 + 1e-12
    q = jnp.clip(jnp.round(w / scale[None, :]), -7, 7).astype(jnp.int8)
    return pack_int4(q), scale.astype(jnp.float32)


def pack_int4(q):
    """Pack int8 values in [-8, 7] two-per-byte along axis -2 (K).

    Handles stacked leading axes: (..., K, N) -> (..., ceil(K/2), N)."""
    k = q.shape[-2]
    if k % 2:
        q = jnp.concatenate([q, jnp.zeros_like(q[..., :1, :])], axis=-2)
    lo = q[..., 0::2, :] & jnp.int8(0x0F)
    hi = jnp.left_shift(q[..., 1::2, :], 4)
    return (lo | hi).astype(jnp.int8)


def unpack_int4(packed, k: int):
    """Inverse of pack_int4: (..., ceil(K/2), N) int8 -> (..., K, N) int8.

    Arithmetic shifts sign-extend each nibble (int8 >> is arithmetic)."""
    lo = jnp.right_shift(jnp.left_shift(packed, 4), 4)
    hi = jnp.right_shift(packed, 4)
    full = jnp.stack([lo, hi], axis=-2)             # (..., Kp, 2, N)
    full = full.reshape(*packed.shape[:-2], 2 * packed.shape[-2],
                        packed.shape[-1])
    return full[..., :k, :]


def dequantize_weight_int4(packed, scale, k: int, dtype=jnp.float32):
    """Canonical reference expression for the packed-INT4 format."""
    return unpack_int4(packed, k).astype(dtype) * scale.astype(dtype)[None, :]


# --- FP8 --------------------------------------------------------------------

def quantize_weight_fp8(w):
    """(K, N) -> (float8_e4m3fn (K, N), f32 (N,)) per-output-channel.

    Scale maps each column's max-abs onto the e4m3 finite range so small-
    magnitude columns keep mantissa resolution."""
    w = w.astype(jnp.float32)
    scale = jnp.max(jnp.abs(w), axis=0) / FP8_MAX + 1e-12
    qf = (w / scale[None, :]).astype(FP8_DTYPE)
    return qf, scale.astype(jnp.float32)


def dequantize_weight_fp8(qf, scale, dtype=jnp.float32):
    """Canonical reference expression for the FP8 format."""
    return qf.astype(dtype) * scale.astype(dtype)[None, :]


# --- epilogue-fused contractions (mirror quant.int8.dequant_contract) -------

def dequant_contract_int4(x, packed, scale, spec: str | None = None):
    """x · dequant(int4) with the scale fused into the output epilogue.

    Unpacks the nibbles (O(K·N) int8, transient) and contracts in x.dtype
    — exact for int4 magnitudes in every float dtype in use."""
    q = unpack_int4(packed, x.shape[-1]).astype(x.dtype)
    return scaled_contract(x, q, scale, spec)


def dequant_contract_fp8(x, qf, scale, spec: str | None = None):
    """x · dequant(fp8) with the scale fused into the output epilogue."""
    return scaled_contract(x, qf.astype(x.dtype), scale, spec)


# --- Pallas GEMM routes -----------------------------------------------------

def planned_linear_int4(x, packed, scale, interpret: bool | None = None):
    """Weight-stationary Pallas route for packed INT4: unpack to int8
    (values in [-7, 7] are exact int8) and reuse the INT8 kernel with the
    /7 scale — same grid, same epilogue fusion."""
    from ..kernels import ops
    b_shape = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    w_q = unpack_int4(packed, x.shape[-1])
    y = ops.int8_matmul(x2, w_q, scale, interpret=interpret)
    return y.reshape(*b_shape, w_q.shape[1]).astype(x.dtype)


def planned_linear_fp8(x, qf, scale, interpret: bool | None = None):
    """Weight-stationary Pallas route for FP8: the kernel upcasts the
    weight tile to f32 in-register, so the e4m3 operand feeds the same
    weight-stationary grid as int8."""
    from ..kernels import ops
    b_shape = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    y = ops.int8_matmul(x2, qf, scale, interpret=interpret)
    return y.reshape(*b_shape, qf.shape[1]).astype(x.dtype)


# --- format dispatch --------------------------------------------------------

def weight_format(w) -> str | None:
    """Precision token of a quantized weight sub-tree, else None."""
    if not isinstance(w, dict):
        return None
    if "q4" in w:
        return "int4"
    if "qf8" in w:
        return "fp8"
    if "q" in w:
        return "int8"
    return None


def quantize_model_params_lowbit(params, precision: str = "int8"):
    """Name-walked projection quantization at a chosen precision.

    precision "int8" delegates to quant.int8.quantize_model_params;
    "int4"/"fp8" produce {"q4"|"qf8", "scale"} sub-trees with the same
    stacked-leading-axis vmap treatment (per-(layer, channel) scales
    survive unstack_tree inside the decode scan)."""
    from jax.tree_util import DictKey, tree_map_with_path

    from .int8 import PROJECTION_WEIGHT_NAMES, quantize_model_params
    if precision == "int8":
        return quantize_model_params(params)
    if precision == "int4":
        base, key = quantize_weight_int4, "q4"
    elif precision == "fp8":
        base, key = quantize_weight_fp8, "qf8"
    else:
        raise ValueError(f"unknown precision {precision!r} "
                         "(expected int8/int4/fp8)")

    def q(path, leaf):
        name = next((p.key for p in reversed(path)
                     if isinstance(p, DictKey)), None)
        if name not in PROJECTION_WEIGHT_NAMES or getattr(
                leaf, "ndim", 0) < 2:
            return leaf
        fn = base
        for _ in range(leaf.ndim - 2):      # (layers, [experts,] K, N)
            fn = jax.vmap(fn)
        qw, scale = fn(leaf)
        return {key: qw, "scale": scale}

    return tree_map_with_path(q, params)
