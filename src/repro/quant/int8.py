"""INT8 post-training quantization (the paper's fixed evaluation precision)
+ the CiM-planner-gated quantized linear layer.

`quantize_params` converts the weight matrices of a model to int8 with
per-output-channel scales; `planned_linear` consults the WWW planner
decision to route large-M GEMMs through the weight-stationary Pallas
kernel and keep small-M (decode) GEMMs on the standard path — the paper's
"when to CiM" answer, enforced at runtime.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def quantize_weight(w):
    """(K, N) -> (int8 (K, N), f32 (N,)) per-output-channel symmetric."""
    scale = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=0) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(w.astype(jnp.float32) / scale[None, :]),
                 -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def dequantize_weight(q, scale, dtype=jnp.float32):
    """Per-output-channel dequant in `dtype` (the canonical expression —
    the numerical *reference* every fused contraction is tested against).
    Supports stacked leading axes: q (..., K, N) with scale (..., N).

    The serving hot path no longer calls this per step: `dequant_contract`
    contracts against the raw int8 weight and applies the scale as an
    O(batch·d_out) epilogue instead of materializing this O(K·N) array."""
    return q.astype(dtype) * scale.astype(dtype)[..., None, :]


def _scale_for(spec: str, scale, side: str):
    """Reshape/transpose a per-output-channel `scale` so it broadcasts
    against the *output* (side "out") or the *input* x (side "x") of
    `einsum(spec, x, q)`.

    The weight operand's second-to-last letter is its input channel K
    (the repo-wide (..., K, N) weight convention); every other weight
    letter carries a scale axis.  Returns None when a scale axis is not
    among that side's letters."""
    ins, out = spec.replace(" ", "").split("->")
    x_spec, w_spec = ins.split(",")
    target = out if side == "out" else x_spec
    k = w_spec[-2]
    s_letters = [c for c in w_spec if c != k]      # scale axis order
    if any(c not in target for c in s_letters):
        return None
    s = jnp.transpose(scale, [s_letters.index(c)
                              for c in target if c in s_letters])
    dims = iter(s.shape)
    return s.reshape([next(dims) if c in s_letters else 1 for c in target])


def _epilogue_scale(spec: str, scale):
    """`scale` shaped for the output of `einsum(spec, x, q)`, or None
    when a scale axis does not survive into it."""
    return _scale_for(spec, scale, "out")


def dequant_contract(x, q, scale, spec: str | None = None, *,
                     materialize: bool = False):
    """x · dequant(q, scale) with the per-output-channel scale fused into
    the matmul *epilogue*: contract against the raw int8 weight (cast to
    x.dtype — exact for int8 values) and scale the O(batch·d_out) output,
    instead of materializing the O(K·N) dequantized weight every call.
    Mathematically identical to the canonical expression up to float
    reassociation: sum_k x_k·(q_kj·s_j) == (sum_k x_k·q_kj)·s_j.

    A plain matmul accumulates and is scaled in f32 and rounds to
    x.dtype once, as the Pallas route does (`planned_linear`): rounding
    the bf16 product and then the bf16 scaled product again put 0.18 of
    logit difference between the two routes of full-width mamba2-780m
    on a TPU v5e.

    `materialize=True` keeps the canonical `dequantize_weight` expression
    — the parity reference the fused path is tested against."""
    if not materialize:
        return scaled_contract(x, q.astype(x.dtype), scale, spec)
    w = dequantize_weight(q, scale, x.dtype)
    return jnp.einsum(spec, x, w) if spec else x @ w


def scaled_contract(x, q, scale, spec: str | None = None):
    """(x · q) · scale for a weight `q` already cast to x.dtype (exact
    for the int8/int4/fp8 values it carries).  A plain matmul accumulates
    and scales in f32 and rounds to x.dtype once, like the Pallas route
    it is compared with; a batched `spec` contraction (no Pallas
    counterpart, and XLA:CPU has no bf16 x bf16 -> f32 batched dot)
    scales its x.dtype product.  A spec that contracts the scaled
    channels themselves (a weight applied transposed, as MLA's absorbed
    W_UK is) scales x's channels in float32 before the contraction; any
    other spec whose scale axis is summed out of the output materializes
    q · scale instead."""
    if spec is None:
        f32 = jnp.float32
        y = jnp.matmul(x, q, preferred_element_type=f32)
        s = scale.astype(f32)
        return (y * (s if q.ndim == 2 else s[..., None, :])
                ).astype(x.dtype)
    se = _epilogue_scale(spec, scale)
    if se is not None:
        return jnp.einsum(spec, x, q) * se.astype(x.dtype)
    sx = _scale_for(spec, scale, "x")
    if sx is not None:
        xs = (x.astype(jnp.float32) * sx.astype(jnp.float32)).astype(x.dtype)
        return jnp.einsum(spec, xs, q)
    return jnp.einsum(spec, x, q * scale.astype(x.dtype)[..., None, :])


def quantize_tree(params, min_size: int = 1 << 16):
    """Quantize every >=2D weight leaf above `min_size` elements.

    Returns a tree of {"q": int8, "scale": f32} replacing those leaves."""
    def q(p):
        if hasattr(p, "ndim") and p.ndim == 2 and p.size >= min_size:
            qw, s = quantize_weight(p)
            return {"q": qw, "scale": s}
        return p
    return jax.tree.map(q, params)


def planned_linear(x, w_q, w_scale, use_cim_path: bool,
                   interpret: bool | None = None):
    """y = x @ dequant(w) — routed per the planner decision.

    use_cim_path=True  -> weight-stationary INT8 Pallas kernel
    use_cim_path=False -> plain XLA matmul on the dequantized weights
    (the paper: never deploy CiM for M=1 / low-reuse GEMMs).

    Both branches respect x.dtype: bfloat16 decode activations contract
    against the int8 weight in bfloat16 (no float32 weight
    materialization) and return bfloat16; the Pallas kernel accumulates
    in f32 internally and casts its output back.  The XLA branch fuses
    the per-output-channel scale into the matmul epilogue
    (`dequant_contract`) rather than dequantizing the full weight.
    """
    if use_cim_path:
        from ..kernels import ops
        b_shape = x.shape[:-1]
        x2 = x.reshape(-1, x.shape[-1])
        y = ops.int8_matmul(x2, w_q, w_scale, interpret=interpret)
        return y.reshape(*b_shape, w_q.shape[1]).astype(x.dtype)
    return dequant_contract(x, w_q, w_scale)


# weight-leaf names the runtime gate can quantize: every projection that
# `core.llm_workloads.gemms_of_model` emits a label for.  Norm scales,
# biases, convs, router (kept f32 for routing stability) and the embedding
# gather stay in float.
PROJECTION_WEIGHT_NAMES = frozenset({
    "wq", "wk", "wv", "wo",                      # attention projections
    "wkv_a", "w_uk", "w_uv",                     # latent attention (MLA)
    "w_gate", "w_up", "w_down",                  # dense MLP / MoE experts
    "w_z", "w_x", "w_B", "w_C", "w_dt",          # mamba in-projections
    "out_proj",                                  # mamba out-projection
    "lm_head",
})


def quantize_model_params(params):
    """INT8-quantize every projection weight of a model param tree.

    Unlike size-threshold `quantize_tree`, this walks by *name*: the leaf
    names in PROJECTION_WEIGHT_NAMES are exactly the weights the planner
    has verdicts for.  Stacked (scanned) leaves keep their leading layer /
    expert axes — quantization vmaps over them, so per-(layer, channel)
    scales survive `unstack_tree` inside the decode scan.  Each quantized
    leaf becomes a {"q": int8, "scale": f32} sub-tree (pytree-transparent:
    scan/unstack slice q and scale together).
    """
    from jax.tree_util import DictKey, tree_map_with_path

    def q(path, leaf):
        name = next((p.key for p in reversed(path)
                     if isinstance(p, DictKey)), None)
        if name not in PROJECTION_WEIGHT_NAMES or getattr(
                leaf, "ndim", 0) < 2:
            return leaf
        fn = quantize_weight
        for _ in range(leaf.ndim - 2):      # (layers, [experts,] K, N)
            fn = jax.vmap(fn)
        qw, scale = fn(leaf)
        return {"q": qw, "scale": scale}

    return tree_map_with_path(q, params)


def quantization_error(w, rtol_target: float = 0.02) -> float:
    q, s = quantize_weight(w)
    back = dequantize_weight(q, s)
    num = jnp.linalg.norm(back - w.astype(jnp.float32))
    den = jnp.linalg.norm(w.astype(jnp.float32)) + 1e-12
    return float(num / den)
