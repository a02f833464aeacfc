"""Plain float32 hybrid language model: Mamba-2 and grouped-query
attention layers with a mixture-of-experts FFN in every layer (IBM
Granite-4.0-H, `GraniteMoeHybrid`: its config.json and the modelling
code it follows).

In `jax.numpy`, every matmul at "highest" precision, one whole sequence
at a time, layer by layer; it imports nothing of the program under test
and reads the benchmark's weight tree (int8 projections with one f32
scale per output channel, dequantized here; float32 router; bfloat16
embedding).  Layer i is `layer_types[i]` of the config file; its weights
are slot i % P of period i // P of the tree (P = attn_every).

One layer, for x (L, d_model):
  h = RMSNorm(x) * g1
  Mamba-2 layer:
    z = h Wz, x' = h Wx, B = h WB, C = h WC, dt = h Wdt
    x', B, C = SiLU(causal depthwise conv_4(.) + bias)   (per channel)
    dt = softplus(dt + dt_bias),  A = -exp(A_log)        (one per head)
    S_t = exp(A dt_t) S_{t-1} + dt_t B_t x'_t^T          (per head: n x p)
    y_t = C_t S_t + D x'_t
    out = RMSNorm(y * SiLU(z)) * g Wout                  (gate, then norm)
  attention layer (no positional embedding: position_embedding_type
  "nope"): q = h Wq, k = h Wk, v = h Wv in heads of head_dim; query head
  j attends with key/value head j // (n_heads / n_kv_heads);
  out = causal softmax(q k^T * a) v Wo
  x <- x + r * out
  h = RMSNorm(x) * g2
  router: p = softmax(h Wr) over all routed experts; the top_k of p,
  divided by their sum, are the gates (= softmax over the top_k logits)
  x <- x + r * (sum over chosen experts e of gate_e SwiGLU_e(h)
                + SwiGLU_shared(h))
then logits = (RMSNorm(x) * g_f) E^T / s, the head tied to the table E
of the input embedding x_0 = m E[t].  Published scalars: m =
embedding_multiplier 12, r = residual_multiplier 0.22, a =
attention_multiplier 1/128, s = logits_scaling 16.

Departures, all stated in the config file:
  * the fold (`assumed`): the weights are the published ones with the
    four scalars folded in, as the program serves them: E <- m E, wo,
    out_proj and every w_down <- r W, wq <- a sqrt(head_dim) wq, g_f <-
    g_f / (s m).  So this reference runs m = r = 1, a = 1/sqrt(head_dim),
    s = 1 on the drawn weights; `published=True` runs the scalars
    explicitly (on unfolded weights), which the tests use to prove the
    fold exact;
  * held experts (`reduced`: moe): the router scores all
    `moe.router_experts` experts and picks top_k of them, but only the
    `moe.n_experts` held here, numbered from `moe.first_expert`, add
    their part; the shared expert always does;
  * depth (`reduced`: n_layers): one pipeline stage of the model's
    layers, with the embedding and the tied head beside it.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _deq(w):
    return w["q"].astype(F32) * w["scale"].astype(F32)[..., None, :]


def _rmsnorm(x, g, eps):
    return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
            * g.astype(F32))


def _causal_conv(x, w, bias):
    """x (L, c), taps w (k, c): out_t = sum_i w_i x_{t-k+1+i} + bias."""
    k, length = w.shape[0], x.shape[0]
    xp = jnp.pad(x, ((k - 1, 0), (0, 0)))
    out = sum(xp[i:i + length] * w[i].astype(F32) for i in range(k))
    return out if bias is None else out + bias.astype(F32)


def _mamba(h, mw, m, state_dtype):
    s, eps = m["ssm"], m["rmsnorm_eps"]
    length, d = h.shape
    di = s["expand"] * d
    nh, p = di // s["headdim"], s["headdim"]
    g, n = s["n_groups"], s["d_state"]

    def conv(name, v):
        return jax.nn.silu(_causal_conv(v, mw[f"conv_{name}"],
                                        mw.get(f"conv_{name}_bias")))
    z = h @ _deq(mw["w_z"])
    xs = conv("x", h @ _deq(mw["w_x"]))
    B = conv("B", h @ _deq(mw["w_B"]))
    C = conv("C", h @ _deq(mw["w_C"]))
    dt = jax.nn.softplus(h @ _deq(mw["w_dt"]) + mw["dt_bias"].astype(F32))
    A = -jnp.exp(mw["A_log"].astype(F32))
    xh = xs.reshape(length, nh, p)
    group = jnp.arange(nh) // (nh // g)               # head -> B/C group
    Bh = B.reshape(length, g, n)[:, group]            # (L, nh, n)
    Ch = C.reshape(length, g, n)[:, group]

    def step(state, inp):
        x_t, dt_t, b_t, c_t = inp
        state = (state * jnp.exp(A * dt_t)[:, None, None]
                 + jnp.einsum("hn,hp->hnp", b_t, x_t * dt_t[:, None]))
        if state_dtype is not None:                   # the control
            state = state.astype(state_dtype).astype(F32)
        return state, jnp.einsum("hn,hnp->hp", c_t, state)

    _, ys = jax.lax.scan(step, jnp.zeros((nh, n, p), F32), (xh, dt, Bh, Ch))
    y = (ys + xh * mw["D"].astype(F32)[:, None]).reshape(length, di)
    gate = jax.nn.silu(z)
    if s.get("norm_before_gate", True):
        y = _rmsnorm(y, mw["norm_scale"], eps) * gate
    else:
        y = _rmsnorm(y * gate, mw["norm_scale"], eps)
    return y @ _deq(mw["out_proj"])


def _attention(h, a, m, scale):
    length = h.shape[0]
    nh, kv = m["n_heads"], m["n_kv_heads"]
    dh = m["d_head"] or m["d_model"] // nh
    q = (h @ _deq(a["wq"])).reshape(length, nh, dh)
    k = (h @ _deq(a["wk"])).reshape(length, kv, dh)
    v = (h @ _deq(a["wv"])).reshape(length, kv, dh)
    kv_of = jnp.arange(nh) // (nh // kv)              # query -> kv head
    s = jnp.einsum("qhd,khd->hqk", q, k[:, kv_of]) * scale
    causal = jnp.tril(jnp.ones((length, length), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), -1)
    o = jnp.einsum("hqk,khd->qhd", p, v[:, kv_of]).reshape(length, nh * dh)
    return o @ _deq(a["wo"])


def _swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


def _moe(h, e, m):
    c = m["moe"]
    routed = c.get("router_experts") or c["n_experts"]
    first, held = c.get("first_expert", 0), c["n_experts"]
    probs = jax.nn.softmax(h @ e["router"].astype(F32), -1)  # (L, routed)
    top, ids = jax.lax.top_k(probs, c["top_k"])
    top = top / top.sum(-1, keepdims=True)
    # gate of each held expert for each token (0 where not chosen)
    gates = jnp.einsum("lk,lke->le", top, (ids[..., None] == first
                                           + jnp.arange(held)).astype(F32))
    assert e["router"].shape[-1] == routed
    wg, wu, wd = _deq(e["w_gate"]), _deq(e["w_up"]), _deq(e["w_down"])
    y = sum(gates[:, i:i + 1] * _swiglu(h, wg[i], wu[i], wd[i])
            for i in range(held))
    if c["n_shared_experts"]:
        sh = e["shared"]
        y = y + _swiglu(h, _deq(sh["w_gate"]), _deq(sh["w_up"]),
                        _deq(sh["w_down"]))
    return y


def _scalars(m, published: bool) -> dict:
    dh = m["d_head"] or m["d_model"] // m["n_heads"]
    if not published:
        return {"embedding": 1.0, "residual": 1.0,
                "attention": 1.0 / math.sqrt(dh), "logits": 1.0}
    return {"embedding": m["embedding_multiplier"],
            "residual": m["residual_multiplier"],
            "attention": m["attention_multiplier"],
            "logits": m["logits_scaling"]}


def _layer(x, w, kind, m, k, state_dtype):
    eps = m["rmsnorm_eps"]
    h = _rmsnorm(x, w["norm1"]["scale"], eps)
    out = (_attention(h, w["attn"], m, k["attention"]) if kind == "attention"
           else _mamba(h, w["mamba"], m, state_dtype))
    x = x + k["residual"] * out
    h = _rmsnorm(x, w["norm2"]["scale"], eps)
    return x + k["residual"] * _moe(h, w["moe"], m)


def _logits(x, params, m, k):
    h = _rmsnorm(x, params["final_norm"]["scale"], m["rmsnorm_eps"])
    return h @ params["embed"].astype(F32).T / k["logits"]


def _gaps(x, params, targets, m, k):
    logits = _logits(x, params, m, k)
    best = jnp.max(logits, -1)
    got = jnp.take_along_axis(logits, jnp.maximum(targets, 0)[:, None],
                              -1)[:, 0]
    return jnp.where(targets >= 0, best - got, jnp.nan)


def _per_sequence(conf, params, inputs, head, extra, state_dtype=None,
                  published=False):
    """Run each sequence through the layers, then `head(x, params, *e)`
    for its row of each array in `extra`."""
    k = _scalars(conf, published)
    period = len(params["slots"])
    layer = {kind: jax.jit(lambda x, ws, i, kind=kind: _layer(
        x, jax.tree.map(lambda a: a[i], ws), kind, conf, k, state_dtype))
        for kind in ("mamba", "attention")}
    head = jax.jit(head)
    out = []
    for b, seq in enumerate(inputs):
        x = k["embedding"] * params["embed"][jnp.asarray(seq)].astype(F32)
        for i, kind in enumerate(conf["layer_types"][:conf["n_layers"]]):
            x = layer[kind](x, params["slots"][i % period], i // period)
        out.append(np.asarray(head(x, params,
                                   *(jnp.asarray(e[b]) for e in extra))))
    return np.stack(out)


def logits(conf: dict, params, inputs: np.ndarray, state_dtype=None,
           published: bool = False) -> np.ndarray:
    """inputs (b, L) int32 -> logits (b, L, vocab) f32.  `state_dtype`
    (the control) rounds the SSM state to it after every update;
    `published` applies the four scalars explicitly instead of reading
    them folded into the weights."""
    k = _scalars(conf, published)
    with jax.default_matmul_precision("highest"):
        return _per_sequence(
            conf, params, inputs, lambda x, p: _logits(x, p, conf, k), (),
            None if state_dtype is None else jnp.dtype(state_dtype),
            published)


def logit_gaps(conf: dict, params, inputs: np.ndarray,
               targets: np.ndarray) -> np.ndarray:
    """inputs, targets (b, L) int32 -> (b, L) f32: the reference's best
    logit minus the target's logit at each position (NaN where the
    target is -1)."""
    k = _scalars(conf, False)
    with jax.default_matmul_precision("highest"):
        return _per_sequence(conf, params, inputs,
                             lambda x, p, t: _gaps(x, p, t, conf, k),
                             (targets,))
