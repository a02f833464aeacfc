"""Plain float32 dense decoder with grouped-query attention (the Mistral
family: Mistral-NeMo-Base-2407's config.json and the Llama/Mistral
modelling code it follows).

In `jax.numpy`, every matmul at "highest" precision, one whole sequence
at a time, layer by layer; it imports nothing of the program under test
and reads the benchmark's weight tree (int8 projections with one f32
scale per output channel, dequantized here).

One block, for x (L, d_model), with positions t = 0..L-1:
  h = RMSNorm(x) * g1
  q = h Wq, k = h Wk, v = h Wv   split into heads of head_dim
  q, k = RoPE(q, t), RoPE(k, t)  rotate-half, frequencies theta^(-2i/dh)
  query head j attends with key/value head j // (n_heads / n_kv_heads),
  causal softmax(q k^T / sqrt(head_dim)) v
  x <- x + attn Wo
  h = RMSNorm(x) * g2,  x <- x + (SiLU(h Wgate) * (h Wup)) Wdown
then logits = RMSNorm(x) * g_f W_head.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _deq(w):
    return w["q"].astype(F32) * w["scale"].astype(F32)[..., None, :]


def _rmsnorm(x, g, eps):
    return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
            * g.astype(F32))


def _rope(x, theta):
    """x (L, heads, dh): rotate-half RoPE at positions 0..L-1."""
    length, dh = x.shape[0], x.shape[-1]
    freqs = theta ** (-jnp.arange(0, dh, 2, dtype=F32) / dh)
    ang = jnp.arange(length, dtype=F32)[:, None] * freqs     # (L, dh/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _block(x, w, m):
    length, eps = x.shape[0], m["rmsnorm_eps"]
    nh, kv = m["n_heads"], m["n_kv_heads"]
    dh = m["d_head"] or m["d_model"] // nh
    a = w["attn"]
    h = _rmsnorm(x, w["norm1"]["scale"], eps)
    q = _rope((h @ _deq(a["wq"])).reshape(length, nh, dh), m["rope_theta"])
    k = _rope((h @ _deq(a["wk"])).reshape(length, kv, dh), m["rope_theta"])
    v = (h @ _deq(a["wv"])).reshape(length, kv, dh)
    kv_of = jnp.arange(nh) // (nh // kv)              # query -> kv head
    s = jnp.einsum("qhd,khd->hqk", q, k[:, kv_of]) / jnp.sqrt(F32(dh))
    causal = jnp.tril(jnp.ones((length, length), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), -1)
    o = jnp.einsum("hqk,khd->qhd", p, v[:, kv_of]).reshape(length, nh * dh)
    x = x + o @ _deq(a["wo"])
    h = _rmsnorm(x, w["norm2"]["scale"], eps)
    mlp = w["mlp"]
    up = jax.nn.silu(h @ _deq(mlp["w_gate"])) * (h @ _deq(mlp["w_up"]))
    return x + up @ _deq(mlp["w_down"])


def _logits(x, params, m):
    h = _rmsnorm(x, params["final_norm"]["scale"], m["rmsnorm_eps"])
    head = (params["embed"].astype(F32).T if m["tie_embeddings"]
            else _deq(params["lm_head"]))
    return h @ head


def _gaps(x, params, targets, m):
    logits = _logits(x, params, m)
    best = jnp.max(logits, -1)
    got = jnp.take_along_axis(logits, jnp.maximum(targets, 0)[:, None],
                              -1)[:, 0]
    return jnp.where(targets >= 0, best - got, jnp.nan)


def _per_sequence(conf, params, inputs, head, extra):
    """Run each sequence through the blocks, then `head(x, params, *e)`
    for its row of each array in `extra`."""
    stacked = params["slots"][0]
    block = jax.jit(lambda x, ws, i: _block(
        x, jax.tree.map(lambda a: a[i], ws), conf))
    head = jax.jit(head)
    out = []
    for b, seq in enumerate(inputs):
        x = params["embed"][jnp.asarray(seq)].astype(F32)
        for i in range(conf["n_layers"]):
            x = block(x, stacked, i)
        out.append(np.asarray(head(x, params,
                                   *(jnp.asarray(e[b]) for e in extra))))
    return np.stack(out)


def logits(conf: dict, params, inputs: np.ndarray) -> np.ndarray:
    """inputs (b, L) int32 -> logits (b, L, vocab) f32."""
    with jax.default_matmul_precision("highest"):
        return _per_sequence(conf, params, inputs,
                             lambda x, p: _logits(x, p, conf), ())


def logit_gaps(conf: dict, params, inputs: np.ndarray,
               targets: np.ndarray) -> np.ndarray:
    """inputs, targets (b, L) int32 -> (b, L) f32: the reference's best
    logit minus the target's logit at each position (NaN where the
    target is -1)."""
    with jax.default_matmul_precision("highest"):
        return _per_sequence(conf, params, inputs,
                             lambda x, p, t: _gaps(x, p, t, conf),
                             (targets,))
