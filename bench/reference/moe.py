"""Plain float32 DeepSeek-V2 language model: multi-head latent attention
(MLA) with YaRN rotary embeddings, leading dense layers, then a
mixture-of-experts FFN with shared experts in every other layer
(DeepSeek-V2-Lite, `DeepseekV2ForCausalLM`: its config.json and the
modelling code it follows).

In `jax.numpy`, every matmul at "highest" precision, one whole sequence
at a time, layer by layer, attention unabsorbed; it imports nothing of
the program under test and reads the benchmark's weight tree (int8
projections with one f32 scale per output channel, dequantized here;
float32 router; bfloat16 embedding).  Layer i < k =
first_k_dense_replace takes its weights from index i of the "lead"
group, layer i >= k from index i - k of the periods ("slots").

One layer, for x (L, d_model), every norm an RMSNorm of rmsnorm_eps:
  h = RMSNorm(x) g1
  q = h Wq                          per head: q_nope | q_pe
  [c | k_pe] = h Wkv_a;  c = RMSNorm(c) g_kv
  k_nope_j = c W_UK,j, v_j = c W_UV,j     (Wkv_b: per head j, c -> k_nope
                                          | v, its halves in the tree)
  q_pe, k_pe <- RoPE(., pos): the published pair order views each
    rotated vector as interleaved pairs (2i, 2i+1), moves their first
    and second members to halves and rotates halves by the pair's
    angle; k_pe is one vector that every head shares
  s_j = [q_nope_j | q_pe_j] . [k_nope_j | k_pe] * a, causal softmax
  x <- x + concat_j(softmax(s_j) v_j) Wo
  h = RMSNorm(x) g2
  layer i < k:  x <- x + SwiGLU_d_ff(h)
  layer i >= k: p = softmax(h Wr) over all routed experts (float32);
                the top_k of p gate their experts as they stand
                (norm_topk_prob false; true divides them by their sum)
                x <- x + sum_e p_e SwiGLU_e(h) + SwiGLU_shared(h)
then logits = (RMSNorm(x) g_f) W_head (untied).
YaRN (`rope_scaling`), rotated width d, base theta: f_i = theta^(-2i/d);
corr(n) = d ln(orig / (2 pi n)) / (2 ln theta), lo = floor(corr(beta_fast)),
hi = ceil(corr(beta_slow)); ramp_i = clip((i - lo) / (hi - lo), 0, 1);
inv_freq_i = f_i / factor ramp_i + f_i (1 - ramp_i); cos and sin times
mscale(mscale) / mscale(mscale_all_dim), with mscale(s) = 0.1 s ln(factor)
+ 1; the score scale a = qk_head_dim^-1/2 mscale(mscale_all_dim)^2.

Departures, all stated in the config file:
  * held experts (`reduced`: moe): the router scores all
    `moe.router_experts` experts and picks top_k of them, but only the
    `moe.n_experts` held here, numbered from `moe.first_expert`, add
    their part; the shared experts always do;
  * the two shared experts are one SwiGLU of their summed width, as the
    published code builds them.
`interleaved=False` and `kv_norm=False` are the tests' departures: the
rotated halves paired as (i, i + d/2), and the latent's norm left out.
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


def _yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _inv_freq(d: int, theta: float, rs) -> np.ndarray:
    f = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    if rs is None:
        return f.astype(np.float32)

    def corr(n):
        return (d * math.log(rs["original_max_position_embeddings"]
                             / (n * 2 * math.pi)) / (2 * math.log(theta)))
    lo = max(math.floor(corr(rs["beta_fast"])), 0)
    hi = min(math.ceil(corr(rs["beta_slow"])), d - 1)
    if lo == hi:
        hi += 0.001
    ramp = np.clip((np.arange(d // 2) - lo) / (hi - lo), 0, 1)
    return (f / rs["factor"] * ramp + f * (1 - ramp)).astype(np.float32)


def _rope(x, conf, interleaved):
    """x (L, heads, d) at positions 0..L-1, in the published pair order
    (`interleaved`) or halves paired as (i, i + d/2)."""
    length, d = x.shape[0], x.shape[-1]
    rs = conf.get("rope_scaling")
    ang = jnp.arange(length, dtype=F32)[:, None] * jnp.asarray(
        _inv_freq(d, conf["rope_theta"], rs))
    emb = jnp.concatenate([ang, ang], -1)[:, None]          # (L, 1, d)
    m = 1.0 if rs is None else (_yarn_mscale(rs["factor"], rs["mscale"])
                                / _yarn_mscale(rs["factor"],
                                               rs["mscale_all_dim"]))
    if interleaved:
        x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)
    half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * (jnp.cos(emb) * m) + half * (jnp.sin(emb) * m)


def _score_scale(conf) -> float:
    a, rs = conf["mla"], conf.get("rope_scaling")
    s = (a["qk_nope_head_dim"] + a["qk_rope_head_dim"]) ** -0.5
    if rs is not None:
        s *= _yarn_mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
    return s


def _mla(h, w, conf, opts):
    a, nh = conf["mla"], conf["n_heads"]
    nope, rope, r = (a["qk_nope_head_dim"], a["qk_rope_head_dim"],
                     a["kv_lora_rank"])
    length = h.shape[0]
    q = (h @ _deq(w["wq"])).reshape(length, nh, nope + rope)
    kv = h @ _deq(w["wkv_a"])
    c, k_pe = kv[:, :r], kv[:, r:]
    if opts["kv_norm"]:
        c = _rmsnorm(c, w["kv_norm"]["scale"], conf["rmsnorm_eps"])
    k_nope = jnp.einsum("lc,hcn->lhn", c, _deq(w["w_uk"]))
    v = jnp.einsum("lc,hcn->lhn", c, _deq(w["w_uv"]))
    q = jnp.concatenate([q[..., :nope],
                         _rope(q[..., nope:], conf, opts["interleaved"])],
                        -1)
    k_pe = _rope(k_pe[:, None], conf, opts["interleaved"])
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_pe, (length, nh, rope))], -1)
    s = jnp.einsum("qhd,khd->hqk", q, k) * _score_scale(conf)
    causal = jnp.tril(jnp.ones((length, length), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), -1)
    o = jnp.einsum("hqk,khd->qhd", p, v).reshape(length, -1)
    return o @ _deq(w["wo"])


def _swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


def _moe(h, e, c):
    routed = c.get("router_experts") or c["n_experts"]
    first, held = c.get("first_expert", 0), c["n_experts"]
    assert e["router"].shape[-1] == routed
    probs = jax.nn.softmax(h @ e["router"].astype(F32), -1)  # (L, routed)
    top, ids = jax.lax.top_k(probs, c["top_k"])
    if c.get("norm_topk_prob", True):
        top = top / top.sum(-1, keepdims=True)
    # gate of each held expert for each token (0 where not chosen)
    gates = jnp.einsum("lk,lke->le", top, (ids[..., None] == first
                                           + jnp.arange(held)).astype(F32))
    wg, wu, wd = _deq(e["w_gate"]), _deq(e["w_up"]), _deq(e["w_down"])
    y = sum(gates[:, i:i + 1] * _swiglu(h, wg[i], wu[i], wd[i])
            for i in range(held))
    if c["n_shared_experts"]:
        sh = e["shared"]
        y = y + _swiglu(h, _deq(sh["w_gate"]), _deq(sh["w_up"]),
                        _deq(sh["w_down"]))
    return y


def _layer(x, w, kind, conf, opts):
    eps = conf["rmsnorm_eps"]
    x = x + _mla(_rmsnorm(x, w["norm1"]["scale"], eps), w["mla"], conf,
                 opts)
    h = _rmsnorm(x, w["norm2"]["scale"], eps)
    if kind == "dense":
        return x + _swiglu(h, _deq(w["mlp"]["w_gate"]),
                           _deq(w["mlp"]["w_up"]),
                           _deq(w["mlp"]["w_down"]))
    return x + _moe(h, w["moe"], conf["moe"])


def _logits(x, params, conf):
    h = _rmsnorm(x, params["final_norm"]["scale"], conf["rmsnorm_eps"])
    return h @ _deq(params["lm_head"])


def _gaps(x, params, targets, conf):
    logits = _logits(x, params, conf)
    best = jnp.max(logits, -1)
    got = jnp.take_along_axis(logits, jnp.maximum(targets, 0)[:, None],
                              -1)[:, 0]
    return jnp.where(targets >= 0, best - got, jnp.nan)


def _per_sequence(conf, params, inputs, head, extra, opts):
    """Run each sequence through the layers, then `head(x, params, *e)`
    for its row of each array in `extra`."""
    lead = conf.get("first_k_dense_replace", 0)
    layer = {kind: jax.jit(lambda x, ws, i, kind=kind: _layer(
        x, jax.tree.map(lambda a: a[i], ws), kind, conf, opts))
        for kind in ("dense", "moe")}
    head = jax.jit(head)
    out = []
    for b, seq in enumerate(inputs):
        x = params["embed"][jnp.asarray(seq)].astype(F32)
        for i in range(conf["n_layers"]):
            if i < lead:
                x = layer["dense"](x, params["lead"][0], i)
            else:
                x = layer["moe"](x, params["slots"][0], i - lead)
        out.append(np.asarray(head(x, params,
                                   *(jnp.asarray(e[b]) for e in extra))))
    return np.stack(out)


def logits(conf: dict, params, inputs: np.ndarray, interleaved: bool = True,
           kv_norm: bool = True) -> np.ndarray:
    """inputs (b, L) int32 -> logits (b, L, vocab) f32."""
    opts = {"interleaved": interleaved, "kv_norm": kv_norm}
    with jax.default_matmul_precision("highest"):
        return _per_sequence(conf, params, inputs,
                             lambda x, p: _logits(x, p, conf), (), opts)


def logit_gaps(conf: dict, params, inputs: np.ndarray,
               targets: np.ndarray) -> np.ndarray:
    """inputs, targets (b, L) int32 -> (b, L) f32: the reference's best
    logit minus the target's logit at each position (NaN where the
    target is -1)."""
    opts = {"interleaved": True, "kv_norm": True}
    with jax.default_matmul_precision("highest"):
        return _per_sequence(conf, params, inputs,
                             lambda x, p, t: _gaps(x, p, t, conf),
                             (targets,), opts)
