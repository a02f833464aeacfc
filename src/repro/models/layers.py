"""Shared model layers, pure-functional JAX (no flax dependency).

Every layer is an (init, apply) pair over plain dict pytrees so that
sharding rules can match on parameter path names.

`linear` is the single pluggable projection execution layer: every dense
projection matmul in the model stack routes through it with a GEMM label,
and a jit-static `KernelPlanTable` (repro.quant.plan_table) decides per
label whether the projection lowers to the weight-stationary INT8 Pallas
kernel or the standard XLA matmul — the What/When/Where verdicts applied
as the deployed dataflow, not just telemetry.
"""
from __future__ import annotations

import contextlib
import math
import os
import sys
import threading
from functools import partial

import jax
import jax.numpy as jnp

from ..quant.int8 import dequant_contract, planned_linear
from ..quant.lowbit import (dequant_contract_fp8, dequant_contract_int4,
                            planned_linear_fp8, planned_linear_int4)


def dtype_of(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
            "float16": jnp.float16}[name]


# --- the planner-gated projection execution layer ---------------------------

_ROUTE_TRACE = threading.local()    # .records, per-thread: concurrent
                                    # sessions may trace simultaneously

# route strings linear() records (serving/dryrun/bench key off these)
CIM_ROUTE = "cim-int8-pallas"
DEQUANT_ROUTE = "int8-dequant-xla"
CIM_INT4_ROUTE = "cim-int4-pallas"
DEQUANT_INT4_ROUTE = "int4-dequant-xla"
CIM_FP8_ROUTE = "cim-fp8-pallas"
DEQUANT_FP8_ROUTE = "fp8-dequant-xla"
FLOAT_ROUTE = "xla"

# Device-side scopes (`jax.named_scope`) of the decode step's sublayers.
# Each op of the compiled step carries the innermost one in its op_name
# metadata, so a profiler trace attributes device time to sublayers:
#   embed       token embedding lookup
#   norm        RMSNorm (pre-mixer, pre-FFN, final)
#   ffn_act     SwiGLU's elementwise silu(gate) * up
#   attn_core   RoPE, the paged KV write and gather, decode attention
#   ssd         Mamba-2's causal conv, SSD state update, gated norm
#   cache_mask  per-slot select of the updated cache rows
#   lm_head     the LM head (its matmul is a projection, below)
#   layer_scan  the scan over layers itself: slicing each layer's
#               weights out of the stacked arrays, reading and writing
#               its index of the carried cache, the residual adds
# and every projection, inside `linear`, "proj/<route>/<label>" with the
# route `route_trace` records.
SCOPES = ("embed", "norm", "ffn_act", "attn_core", "ssd", "cache_mask",
          "lm_head", "layer_scan")
PROJ_SCOPE = "proj"
# Scopes inside the expert layer (models/moe.py), apart from SCOPES, the
# list that the benchmark's trace reduction (bench/lib/scopes.py) mirrors:
#   moe_router   router logits, softmax, top-k, the held-expert mask
#   moe_experts  the held experts (their projections in "proj/...") and
#                the gated sum of their outputs
#   moe_shared   the shared expert (its projections and ffn_act inside)
MOE_SCOPES = ("moe_router", "moe_experts", "moe_shared")


@contextlib.contextmanager
def route_trace():
    """Collect every `linear` routing decision made while tracing.

    `linear` runs at Python trace time, so wrapping `jax.eval_shape` (or
    any jit trace) of a model function yields the *executed* route per
    projection label without any compute — this backs
    `ServeSession.route_report`, the dry-run routing block, and the
    label-coverage test.  Yields a list of
    {"label", "route", "shape", "callsite"} records; "shape" is the
    executed (M, N, K) of a plain 2-D projection (M = all leading rows of
    x), None for a batched `spec` contraction.
    """
    prev = getattr(_ROUTE_TRACE, "records", None)
    _ROUTE_TRACE.records = []
    try:
        yield _ROUTE_TRACE.records
    finally:
        _ROUTE_TRACE.records = prev


def _record_route(label: str, route: str, mnk) -> None:
    records = getattr(_ROUTE_TRACE, "records", None)
    if records is not None:
        f = sys._getframe(2)        # the frame that called linear()
        records.append({
            "label": label, "route": route, "shape": mnk,
            "callsite": f"{os.path.basename(f.f_code.co_filename)}"
                        f":{f.f_lineno}"})


def linear(w, x, label: str, plan=None, spec: str | None = None):
    """y = x @ w — THE projection entry point, routed by the kernel plan.

    w is either a float weight array or a quantized {"q", "scale"} leaf
    (repro.quant.quantize_model_params).  With a KernelPlanTable `plan`,
    a quantized 2-D projection whose label gates on lowers to the
    weight-stationary INT8 Pallas kernel (planned_linear); everything
    else contracts against the raw int8 weight in x.dtype with the
    per-output-channel scale fused into the output epilogue
    (dequant_contract) — no per-step weight materialization.
    `spec` is an optional einsum spec for batched weights (MoE experts
    `"ecd,edf->ecf"`, audio lm_head `"bld,ndv->blnv"`); the Pallas path
    only applies to plain 2-D matmuls.

    The plan lookup happens at trace time (plan is jit-static), so the
    lowered program contains exactly one implementation per label — no
    runtime branch, no retrace.  Unknown labels raise KeyError from the
    plan table: model-side label drift must not silently disable gating.
    The projection's ops carry the scope "proj/<route>/<label>".
    """
    quantized = isinstance(w, dict)
    use_cim = bool(plan is not None and quantized and plan.use_cim(label))
    # the present key is the jit-static format discriminator
    # (quant.lowbit): "q" int8 / "q4" packed int4 / "qf8" scaled fp8
    key = next(k for k in ("q", "q4", "qf8") if k in w) if quantized else None
    wt = w[key] if quantized else w
    mnk = ((math.prod(x.shape[:-1]), wt.shape[-1], x.shape[-1])
           if spec is None and wt.ndim == 2 else None)
    cim = use_cim and spec is None and wt.ndim == 2
    if quantized:
        (cim_route, kernel), (xla_route, contract) = _FORMATS[key]
        route = cim_route if cim else xla_route
    else:
        route = FLOAT_ROUTE
    _record_route(label, route, mnk)
    with jax.named_scope(f"{PROJ_SCOPE}/{route}/{label}"):
        if cim:
            return kernel(x, wt, w["scale"])
        if quantized:
            return contract(x, wt, w["scale"], spec)
        if w.dtype != x.dtype:
            w = w.astype(x.dtype)
        return jnp.einsum(spec, x, w) if spec else x @ w


# weight key -> (Pallas route, kernel), (XLA route, epilogue contraction)
_FORMATS = {
    "q": ((CIM_ROUTE, partial(planned_linear, use_cim_path=True)),
          (DEQUANT_ROUTE, dequant_contract)),
    "q4": ((CIM_INT4_ROUTE, planned_linear_int4),
           (DEQUANT_INT4_ROUTE, dequant_contract_int4)),
    "qf8": ((CIM_FP8_ROUTE, planned_linear_fp8),
            (DEQUANT_FP8_ROUTE, dequant_contract_fp8)),
}


# --- initializers -----------------------------------------------------------

def dense_init(key, d_in: int, d_out: int, dtype, scale: float | None = None):
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out), jnp.float32)
            * scale).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype):
    return (jax.random.normal(key, (vocab, d), jnp.float32) * 0.02
            ).astype(dtype)


# --- norms -------------------------------------------------------------------

def rmsnorm_init(d: int, dtype):
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(params, x, eps: float = 1e-5):
    with jax.named_scope("norm"):
        dt = x.dtype
        x = x.astype(jnp.float32)
        var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        x = x * jax.lax.rsqrt(var + eps)
        return (x * params["scale"].astype(jnp.float32)).astype(dt)


# --- rotary embeddings --------------------------------------------------------

def rope_freqs(d_head: int, theta: float, scaling=None):
    """Inverse frequencies of the d_head / 2 rotated pairs; with a YaRN
    `scaling` (configs.base.RopeScaling) those of the pairs that turn
    fewer than `beta_slow` times over the original context are divided
    by `factor`, those that turn more than `beta_fast` times are kept,
    and a linear ramp over the pair index blends the two between."""
    freqs = 1.0 / (theta ** (jnp.arange(0, d_head, 2, dtype=jnp.float32)
                             / d_head))
    if scaling is None:
        return freqs

    def corr(rotations):      # the pair index that turns `rotations` times
        return (d_head * math.log(scaling.original_max_position_embeddings
                                  / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    lo = max(math.floor(corr(scaling.beta_fast)), 0)
    hi = min(math.ceil(corr(scaling.beta_slow)), d_head - 1)
    ramp = jnp.clip((jnp.arange(d_head // 2, dtype=jnp.float32) - lo)
                    / max(hi - lo, 1e-3), 0.0, 1.0)
    return freqs / scaling.factor * ramp + freqs * (1.0 - ramp)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's magnitude term 0.1 mscale ln(factor) + 1 (1 for no
    extension)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def apply_rope(x, positions, theta: float = 1e6, scaling=None,
               interleaved: bool = False):
    """x: (..., seq, heads, d_head); positions: (..., seq).

    Rotates channel i with channel i + d_head / 2 by the pair's angle.
    `interleaved` (DeepSeek's published order) pairs channels 2i and
    2i + 1 instead and returns the rotated pairs in halves, first
    members then second, as its modelling code does.  `scaling`: YaRN
    (rope_freqs); cos and sin are multiplied by the ratio of its mscale
    and mscale_all_dim terms."""
    d_head = x.shape[-1]
    freqs = rope_freqs(d_head, theta, scaling)              # (d_head/2,)
    ang = positions[..., :, None].astype(jnp.float32) * freqs  # (.., s, d/2)
    cos = jnp.cos(ang)[..., :, None, :]                     # (.., s, 1, d/2)
    sin = jnp.sin(ang)[..., :, None, :]
    if scaling is not None:
        m = (yarn_mscale(scaling.factor, scaling.mscale)
             / yarn_mscale(scaling.factor, scaling.mscale_all_dim))
        cos, sin = cos * m, sin * m
    xf = x.astype(jnp.float32)
    if interleaved:
        x1, x2 = xf[..., 0::2], xf[..., 1::2]
    else:
        x1, x2 = jnp.split(xf, 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                          axis=-1)
    return out.astype(x.dtype)


# --- MLPs ----------------------------------------------------------------------

def swiglu_init(key, d: int, d_ff: int, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    return {"w_gate": dense_init(k1, d, d_ff, dtype),
            "w_up": dense_init(k2, d, d_ff, dtype),
            "w_down": dense_init(k3, d_ff, d, dtype)}


def swiglu(params, x, plan=None, label_prefix: str = "mlp"):
    """Gated MLP; label_prefix distinguishes dense "mlp-*" from the MoE
    "shared-*" expert (matching gemms_of_model labels)."""
    g = linear(params["w_gate"], x, f"{label_prefix}-gate", plan)
    u = linear(params["w_up"], x, f"{label_prefix}-up", plan)
    with jax.named_scope("ffn_act"):
        a = jax.nn.silu(g) * u
    return linear(params["w_down"], a, f"{label_prefix}-down", plan)


# --- attention projections ------------------------------------------------------

def attn_init(key, d: int, n_heads: int, n_kv: int, d_head: int, dtype,
              qkv_bias: bool):
    ks = jax.random.split(key, 4)
    p = {"wq": dense_init(ks[0], d, n_heads * d_head, dtype),
         "wk": dense_init(ks[1], d, n_kv * d_head, dtype),
         "wv": dense_init(ks[2], d, n_kv * d_head, dtype),
         "wo": dense_init(ks[3], n_heads * d_head, d, dtype,
                          scale=1.0 / math.sqrt(n_heads * d_head))}
    if qkv_bias:
        p["bq"] = jnp.zeros((n_heads * d_head,), dtype)
        p["bk"] = jnp.zeros((n_kv * d_head,), dtype)
        p["bv"] = jnp.zeros((n_kv * d_head,), dtype)
    return p


def qkv_proj(params, x, n_heads: int, n_kv: int, d_head: int, plan=None):
    b, s, _ = x.shape
    q, k, v = (linear(params[w], x, lab, plan)
               for w, lab in (("wq", "Wq"), ("wk", "Wk"), ("wv", "Wv")))
    if "bq" in params:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    return (q.reshape(b, s, n_heads, d_head),
            k.reshape(b, s, n_kv, d_head),
            v.reshape(b, s, n_kv, d_head))


def attn_out_proj(params, o, plan=None, label: str = "Wo"):
    """Attention output projection (self-attn "Wo" / cross "xattn-out"),
    shared by the full-sequence forward and the decode step so each label
    has exactly one linear call site."""
    return linear(params["wo"], o, label, plan)


# --- misc -----------------------------------------------------------------------

def unstack_tree(tree, i):
    """Select layer i from a stacked (scanned) parameter tree."""
    return jax.tree.map(lambda x: x[i], tree)


def stack_trees(trees):
    """Stack per-layer param trees into scan-ready arrays."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def count_params(tree) -> int:
    return sum(x.size for x in jax.tree.leaves(tree))


def param_bytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def init_stacked(key, n: int, init_fn):
    """vmap an init function over layer indices (fast stacked init)."""
    keys = jax.random.split(key, n)
    return jax.vmap(init_fn)(keys)
