"""Mixture-of-Experts FFN with scatter/gather dispatch (no dense one-hot
einsum — keeps HLO FLOPs close to useful FLOPs, which matters for the
roofline's MODEL_FLOPS / HLO_FLOPS ratio).

Dispatch: top-k routing -> position-in-expert via cumsum -> scatter tokens
into an (E, C, d) buffer -> batched expert matmuls -> weighted gather-back.
Tokens beyond expert capacity are dropped (standard capacity-factor MoE).
Under EP the (E, C, d) buffer is sharded on E over the model axis and the
scatter/gather lower to all-to-alls.

Decode exception: when the token count fits expert capacity (T <= C: a
small decode batch, or any batch once capacity_factor >= n_routed /
top_k, which drops no token) capacity dropping is impossible,
so `moe_apply` skips the dispatch machinery and runs every expert over
every token with a plain batched einsum, then selects each token's
top-k outputs.  Same math up to float reassociation (the two paths
reduce in different orders, so they agree to f32 rounding, not bit for
bit; the fast-path FLOP count E*T rows is <= the buffer's E*C), far
fewer ops on the hot path — the scatter/cumsum/
segment-sum chain is the dominant per-step cost at decode shapes.

Expert parallelism: the layer holds `n_experts` experts, numbered from
`first_expert`, of the `n_routed` the router scores.  It routes every
token over all of them (softmax over the router's logits, top-k,
renormalised unless `norm_topk_prob` is off) and adds the gated
outputs of those of a token's choices that it holds; the shared expert
runs in every share.  Summed over the
shares, with the shared expert counted once, the parts give the whole
layer.  Capacity is reckoned from the routed count.  On one chip the
layer runs alone: there is no exchange.

Scopes (`jax.named_scope`, `layers.MOE_SCOPES`): "moe_router" (router,
top-k, held mask), "moe_experts" (the held experts and their gated sum),
"moe_shared" (the shared expert).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from .layers import dense_init, linear, swiglu, swiglu_init


def moe_init(key, cfg: ModelConfig, dtype):
    m = cfg.moe
    d = cfg.d_model
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], d, m.n_routed, jnp.float32),
        "w_gate": (jax.random.normal(ks[1], (m.n_experts, d, m.expert_d_ff),
                                     jnp.float32) / d ** 0.5).astype(dtype),
        "w_up": (jax.random.normal(ks[2], (m.n_experts, d, m.expert_d_ff),
                                   jnp.float32) / d ** 0.5).astype(dtype),
        "w_down": (jax.random.normal(ks[3], (m.n_experts, m.expert_d_ff, d),
                                     jnp.float32)
                   / m.expert_d_ff ** 0.5).astype(dtype),
    }
    if m.n_shared_experts:
        p["shared"] = swiglu_init(ks[4], d, m.shared_d_ff, dtype)
    return p


def capacity(cfg: ModelConfig, n_tokens: int) -> int:
    m = cfg.moe
    c = int(n_tokens * m.top_k * m.capacity_factor / m.n_routed)
    return max(8, -(-c // 8) * 8)      # round up to 8


def moe_apply(params, x, cfg: ModelConfig, plan=None, *,
              force_buffered: bool = False):
    """x: (b, l, d) -> (y, aux_loss).

    `force_buffered` disables the T <= C decode fast path so the parity
    test can pin both dispatch forms to the same output."""
    m = cfg.moe
    b, l, d = x.shape
    T = b * l
    xt = x.reshape(T, d)
    C = capacity(cfg, T)

    # router stays an f32 ungated matmul: it is not in the GEMM taxonomy
    # (tiny, and routing stability dominates any kernel choice)
    with jax.named_scope("moe_router"):
        logits = (xt @ params["router"]).astype(jnp.float32)  # (T, R)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_ids = jax.lax.top_k(probs, m.top_k)  # (T, k)
        if m.norm_topk_prob:
            gate_vals = gate_vals / jnp.maximum(
                gate_vals.sum(-1, keepdims=True), 1e-9)
        # each choice's index among the experts held here; the choices
        # held elsewhere add nothing in this share
        local = expert_ids - m.first_expert
        held = (local >= 0) & (local < m.n_experts)
        local = jnp.where(held, local, 0)

    with jax.named_scope("moe_experts"):
        if T <= C and not force_buffered:
            yt = _every_held_expert(params, xt, local, held, gate_vals,
                                    plan)
        else:
            yt = _buffered(params, xt, local, held, gate_vals, C, m, plan)
    y = yt.reshape(b, l, d)

    if m.n_shared_experts:
        with jax.named_scope("moe_shared"):
            y = y + swiglu(params["shared"], x, plan, label_prefix="shared")

    # load-balancing aux loss (Switch-style)
    frac_tokens = jnp.mean(
        jax.nn.one_hot(expert_ids[:, 0], m.n_routed, dtype=jnp.float32),
        axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    aux = m.n_routed * jnp.sum(frac_tokens * frac_probs) \
        * m.router_aux_loss
    return y, aux


def _every_held_expert(params, xt, local, held, gate_vals, plan):
    """Decode / micro-batch fast path: an expert can receive at most
    T <= C assignments (a token's top-k experts are distinct), so
    capacity dropping is IMPOSSIBLE and the scatter/gather dispatch
    machinery is pure overhead — at decode shapes it costs more
    host+device dispatch than the compute it avoids.  Run every held
    expert over every token outright (E*T rows vs the buffer's E*C,
    T <= C) and select each token's top-k outputs, the choices held
    elsewhere weighted by zero.  The per-(expert, token) dot products
    and the k-weighted sum are the buffered path's contractions, reduced
    in a different order (`etf` einsums + sum over k here, `ecf` einsums
    + segment_sum there): the same semantics to f32 rounding, fewer
    ops."""
    g = jax.nn.silu(linear(params["w_gate"], xt, "expert-gate", plan,
                           spec="td,edf->etf"))
    u = linear(params["w_up"], xt, "expert-up", plan, spec="td,edf->etf")
    eout = linear(params["w_down"], g * u, "expert-down", plan,
                  spec="etf,efd->etd")                  # (E, T, d)
    sel = jnp.take_along_axis(eout.transpose(1, 0, 2), local[:, :, None],
                              axis=1)                   # (T, k, d)
    w = jnp.where(held, gate_vals, 0.0).astype(xt.dtype)
    return (sel * w[:, :, None]).sum(axis=1)


def _buffered(params, xt, local, held, gate_vals, C: int, m, plan):
    """Capacity-factor dispatch: scatter each held (token, k) assignment
    into an (E, C, d) buffer, run the experts batched, gather back
    weighted.  Assignments past an expert's capacity are dropped."""
    T, d = xt.shape
    flat = local.reshape(-1)                                 # (T*k,)
    flat_held = held.reshape(-1)
    # position of each held assignment within its expert
    onehot = jax.nn.one_hot(flat, m.n_experts, dtype=jnp.int32) \
        * flat_held[:, None]
    pos = jnp.cumsum(onehot, axis=0) - 1                     # (T*k, E)
    pos_in_expert = jnp.take_along_axis(pos, flat[:, None], axis=1)[:, 0]
    keep = flat_held & (pos_in_expert < C)

    # scatter tokens into (E, C, d)
    tok_idx = jnp.repeat(jnp.arange(T), m.top_k)
    buf = jnp.zeros((m.n_experts, C, d), xt.dtype)
    safe_pos = jnp.where(keep, pos_in_expert, C - 1)
    contrib = jnp.where(keep[:, None], xt[tok_idx], 0)
    buf = buf.at[flat, safe_pos].add(contrib)

    # batched expert SwiGLU.  Expert weights are (E, d, f): the planner's
    # verdict gates dequantization routing, but the batched-expert einsum
    # has no 2-D weight-stationary form, so a gated expert label executes
    # as an int8-dequant XLA contraction (recorded as such by route_trace)
    g = jax.nn.silu(linear(params["w_gate"], buf, "expert-gate", plan,
                           spec="ecd,edf->ecf"))
    u = linear(params["w_up"], buf, "expert-up", plan, spec="ecd,edf->ecf")
    eout = linear(params["w_down"], g * u, "expert-down", plan,
                  spec="ecf,efd->ecd")

    # gather back with routing weights
    back = eout[flat, safe_pos]                              # (T*k, d)
    w = (gate_vals.reshape(-1) * keep).astype(xt.dtype)
    return jax.ops.segment_sum(back * w[:, None], tok_idx, num_segments=T)
