"""Plain float32 Mamba-2 language model (Dao & Gu, arXiv:2405.21060).

Written from the paper and the published reference implementation
(state-spaces/mamba, `Mamba2` with `ngroups` B/C groups), in
`jax.numpy` with every matmul at "highest" precision, whole sequences at
a time, layer by layer.  It imports nothing of the program under test.
It reads the benchmark's weight tree: int8 projections with one f32 scale
per output channel, dequantized here.

One block, for input x (L, d_model), with h = RMSNorm(x) * g1:
  z = h Wz, x' = h Wx, B = h WB, C = h WC, dt = h Wdt
  x', B, C = SiLU(causal depthwise conv_4(.))      (per channel)
  dt = softplus(dt + dt_bias),  A = -exp(A_log)    (one per head)
  S_t = exp(A dt_t) S_{t-1} + dt_t B_t x'_t^T       (per head: n x p)
  y_t = C_t S_t + D x'_t
  out = gated RMSNorm(y, z) Wout,   x <- x + out
then logits = RMSNorm(x) * g_f W_head.

Departures the configuration states and this reference follows
(keys of the config file): the published model gates before the
norm, RMSNorm(y * SiLU(z)) * g, while `norm_before_gate` runs
RMSNorm(y) * g * SiLU(z); the published conv has a bias, which
`conv_bias: false` leaves out; the published head is tied to the
embedding, which `tie_embeddings: false` unties.
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


def _causal_conv(x, w):
    """x (b, L, c), taps w (k, c): out_t = sum_i w_i x_{t-k+1+i}."""
    k, length = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(xp[:, i:i + length] * w[i].astype(F32) for i in range(k))


def _block(x, w, conf, state_dtype=None):
    s, eps = conf["ssm"], conf["rmsnorm_eps"]
    b, length, d = x.shape
    di = s["expand"] * d
    nh, p = di // s["headdim"], s["headdim"]
    g, n = s["n_groups"], s["d_state"]
    mw = w["mamba"]
    h = _rmsnorm(x, w["norm1"]["scale"], eps)
    z = h @ _deq(mw["w_z"])
    xs = jax.nn.silu(_causal_conv(h @ _deq(mw["w_x"]), mw["conv_x"]))
    B = jax.nn.silu(_causal_conv(h @ _deq(mw["w_B"]), mw["conv_B"]))
    C = jax.nn.silu(_causal_conv(h @ _deq(mw["w_C"]), mw["conv_C"]))
    dt = jax.nn.softplus(h @ _deq(mw["w_dt"]) + mw["dt_bias"].astype(F32))
    A = -jnp.exp(mw["A_log"].astype(F32))
    xh = xs.reshape(b, length, nh, p)
    group = jnp.arange(nh) // (nh // g)               # head -> B/C group
    Bh = B.reshape(b, length, g, n)[:, :, group]      # (b, L, nh, n)
    Ch = C.reshape(b, length, g, n)[:, :, group]

    def step(state, inp):
        x_t, dt_t, b_t, c_t = inp
        state = (state * jnp.exp(A * dt_t)[..., None, None]
                 + jnp.einsum("bhn,bhp->bhnp", b_t, x_t * dt_t[..., None]))
        if state_dtype is not None:                   # the control
            state = state.astype(state_dtype).astype(F32)
        return state, jnp.einsum("bhn,bhnp->bhp", c_t, state)

    tm = lambda t: jnp.swapaxes(t, 0, 1)              # noqa: E731
    _, ys = jax.lax.scan(step, jnp.zeros((b, nh, n, p), F32),
                         (tm(xh), tm(dt), tm(Bh), tm(Ch)))
    y = tm(ys) + xh * mw["D"].astype(F32)[:, None]
    y = y.reshape(b, length, di)
    gate = jax.nn.silu(z)
    if conf["norm_before_gate"]:
        y = _rmsnorm(y, mw["norm_scale"], eps) * gate
    else:
        y = _rmsnorm(y * gate, mw["norm_scale"], eps)
    return x + y @ _deq(mw["out_proj"])


def _logits(x, params, m):
    h = _rmsnorm(x, params["final_norm"]["scale"], m["rmsnorm_eps"])
    head = (params["embed"].astype(F32).T if m["tie_embeddings"]
            else _deq(params["lm_head"]))
    return h @ head


def _gaps(x, params, targets, m):
    logits = _logits(x, params, m)
    best = jnp.max(logits, -1)
    got = jnp.take_along_axis(logits, jnp.maximum(targets, 0)[..., None],
                              -1)[..., 0]
    return jnp.where(targets >= 0, best - got, jnp.nan)


def _hidden(conf, params, inputs, state_dtype=None):
    if conf["conv_bias"]:
        raise NotImplementedError("the weight tree carries no conv bias")
    stacked = params["slots"][0]
    block = jax.jit(lambda x, ws, i: _block(
        x, jax.tree.map(lambda a: a[i], ws), conf, state_dtype))
    x = params["embed"][jnp.asarray(inputs)].astype(F32)
    for i in range(conf["n_layers"]):
        x = block(x, stacked, i)
    return x


def logits(conf: dict, params, inputs: np.ndarray, state_dtype=None
           ) -> np.ndarray:
    """inputs (b, L) int32 -> logits (b, L, vocab) f32.  `state_dtype`
    (the control) rounds the SSM state to it after every update."""
    with jax.default_matmul_precision("highest"):
        x = _hidden(conf, params, inputs,
                    None if state_dtype is None else jnp.dtype(state_dtype))
        return np.asarray(jax.jit(lambda x, p: _logits(x, p, conf))(
            x, params))


def logit_gaps(conf: dict, params, inputs: np.ndarray,
               targets: np.ndarray) -> np.ndarray:
    """inputs, targets (b, L) int32 -> (b, L) f32: the reference's best
    logit minus the target's logit at each position (NaN where the
    target is -1)."""
    with jax.default_matmul_precision("highest"):
        x = _hidden(conf, params, inputs)
        gaps = jax.jit(lambda x, p, t: _gaps(x, p, t, conf))(
            x, params, jnp.asarray(targets))
    return np.asarray(gaps)
