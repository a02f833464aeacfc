"""Mamba2 SSD (state-space duality) mixer in pure JAX (arXiv:2405.21060).

Chunked SSD algorithm: within a chunk the recurrence is computed in its
"dual" quadratic-attention form; across chunks a lax.scan carries the
(heads, headdim, d_state) recurrent state.  Decode is the O(1) recurrent
update — this is what makes long_500k serving linear for SSM archs.

Layout conventions:
  x     : (b, l, h, p)      p = headdim
  dt, A : (b, l, h)         per-head scalar decay (A negative)
  B, C  : (b, l, g, n)      n = d_state, g = groups (broadcast over heads)
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig, SSMConfig
from .layers import dense_init, linear


def segsum(x):
    """Stable 'segment sum' producing the lower-triangular decay matrix:
    out[i, j] = sum_{k=j+1..i} x[k] for i >= j, -inf otherwise."""
    l = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    out = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((l, l), bool), k=0)
    return jnp.where(mask, out, -jnp.inf)


def ssd_chunked(x, dt, A, B, C, chunk: int, init_state=None):
    """Chunked SSD scan.  Returns (y, final_state).

    x: (b, l, h, p); dt: (b, l, h) (softplus-ed); A: (h,) negative;
    B, C: (b, l, g, n) with h % g == 0.
    """
    b, l, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    nc = l // chunk
    assert nc * chunk == l, (l, chunk)
    rep = h // g

    # fold dt into x and A (discretization)
    a = A[None, None, :] * dt                     # (b, l, h)  log-decay
    xb = x * dt[..., None]                        # input scaled by dt

    # chunk everything: (b, nc, cl, ...)
    def ch(t):
        return t.reshape(b, nc, chunk, *t.shape[2:])
    xc, ac, Bc, Cc = ch(xb), ch(a), ch(B), ch(C)
    Bh = jnp.repeat(Bc, rep, axis=3)              # (b, nc, cl, h, n)
    Ch = jnp.repeat(Cc, rep, axis=3)

    a_cum = jnp.cumsum(ac, axis=2)                # (b, nc, cl, h)
    # --- intra-chunk (dual quadratic form) ---
    L = jnp.exp(segsum(ac.transpose(0, 1, 3, 2)))     # (b, nc, h, cl, cl)
    scores = jnp.einsum("bcihn,bcjhn->bchij", Ch, Bh)  # (b,nc,h,cl,cl)
    y_diag = jnp.einsum("bchij,bcjhp->bcihp", scores * L, xc)

    # --- chunk states ---
    decay_to_end = jnp.exp(a_cum[:, :, -1:, :] - a_cum)    # (b,nc,cl,h)
    states = jnp.einsum("bcihn,bcih,bcihp->bchnp",
                        Bh, decay_to_end, xc)               # (b,nc,h,n,p)

    # --- inter-chunk recurrence over nc ---
    chunk_decay = jnp.exp(a_cum[:, :, -1, :])               # (b, nc, h)

    def step(carry, inp):
        st, dec = inp                                        # (b,h,n,p),(b,h)
        new = carry * dec[..., None, None] + st
        return new, carry                                    # emit incoming

    s0 = (init_state.astype(jnp.float32) if init_state is not None
          else jnp.zeros((b, h, n, p), jnp.float32))
    final, prev_states = jax.lax.scan(
        step, s0, (states.swapaxes(0, 1), chunk_decay.swapaxes(0, 1)))
    prev_states = prev_states.swapaxes(0, 1)                 # (b,nc,h,n,p)

    # --- contribution of carried state to each position ---
    state_decay = jnp.exp(a_cum)                             # (b,nc,cl,h)
    y_off = jnp.einsum("bcihn,bchnp,bcih->bcihp",
                       Ch, prev_states, state_decay)
    y = (y_diag + y_off).astype(jnp.float32).reshape(b, l, h, p)
    return y, final


def ssd_decode_step(state, x_t, dt_t, A, B_t, C_t):
    """O(1) recurrent update for one token.

    state: (b, h, n, p); x_t: (b, h, p); dt_t: (b, h);
    B_t, C_t: (b, g, n).  Returns (y_t, new_state)."""
    h = x_t.shape[1]
    rep = h // B_t.shape[1]
    Bh = jnp.repeat(B_t, rep, axis=1)            # (b, h, n)
    Ch = jnp.repeat(C_t, rep, axis=1)
    decay = jnp.exp(A[None, :] * dt_t)           # (b, h)
    add = jnp.einsum("bhn,bhp->bhnp", Bh, x_t * dt_t[..., None])
    new_state = state * decay[..., None, None] + add
    y = jnp.einsum("bhn,bhnp->bhp", Ch, new_state)
    return y, new_state


# --- full mixer (in_proj -> conv -> SSD -> gate -> out_proj) -----------------

def mamba_init(key, cfg: ModelConfig, dtype):
    """Projections are separate named weights (not one fused in_proj) so
    tensor-parallel sharding aligns with segment boundaries (z/x/dt shard
    over heads; the small B/C group projections replicate)."""
    s = cfg.ssm
    d = cfg.d_model
    di = s.d_inner(d)
    nh = s.n_ssm_heads(d)
    gdim = s.n_groups * s.d_state
    ks = jax.random.split(key, 6)
    p = {
        "w_z": dense_init(ks[0], d, di, dtype),
        "w_x": dense_init(ks[1], d, di, dtype),
        "w_B": dense_init(ks[2], d, gdim, dtype),
        "w_C": dense_init(ks[3], d, gdim, dtype),
        "w_dt": dense_init(ks[4], d, nh, dtype),
        "conv_x": (jax.random.normal(ks[5], (s.d_conv, di),
                                     jnp.float32) * 0.02).astype(dtype),
        "conv_B": jnp.full((s.d_conv, gdim), 0.02, dtype),
        "conv_C": jnp.full((s.d_conv, gdim), 0.02, dtype),
        "A_log": jnp.zeros((nh,), jnp.float32),   # A = -exp(A_log) in [-1,0)
        "dt_bias": jnp.zeros((nh,), jnp.float32),
        "D": jnp.ones((nh,), jnp.float32),
        "norm_scale": jnp.ones((di,), dtype),
        "out_proj": dense_init(ks[0], di, d, dtype),
    }
    if s.conv_bias:
        p.update(conv_x_bias=jnp.zeros((di,), dtype),
                 conv_B_bias=jnp.zeros((gdim,), dtype),
                 conv_C_bias=jnp.zeros((gdim,), dtype))
    return p


def _causal_conv(xBC, w, carry=None, bias=None):
    """Depthwise causal conv over (b, l, c) with kernel (k, c) and an
    optional per-channel bias (c,), then SiLU.

    carry: (b, k-1, c) previous context (decode) or None (train: zero pad).
    Returns (y, new_carry)."""
    k = w.shape[0]
    b, l, c = xBC.shape
    pad = (carry if carry is not None
           else jnp.zeros((b, k - 1, c), xBC.dtype))
    xp = jnp.concatenate([pad, xBC], axis=1)
    # sum_k w[k] * x[t - (K-1) + k]
    y = sum(xp[:, i:i + l, :] * w[i] for i in range(k))
    if bias is not None:
        y = y + bias
    new_carry = xp[:, -(k - 1):, :] if k > 1 else None
    return jax.nn.silu(y), new_carry


def mamba_apply(params, x, cfg: ModelConfig, state=None, conv_carry=None,
                decode: bool = False, plan=None):
    """x: (b, l, d).  Train/prefill when decode=False (l = seq);
    decode=True expects l == 1 and a (state, conv_carry) cache.
    Returns (y, (new_state, new_conv_carry))."""
    s = cfg.ssm
    b, l, d = x.shape
    di = s.d_inner(d)
    gdim = s.n_groups * s.d_state
    nh = s.n_ssm_heads(d)
    z = linear(params["w_z"], x, "ssm-z", plan)
    xs = linear(params["w_x"], x, "ssm-x", plan)
    # B/C/dt are one fused GEMM in the planner's taxonomy ("ssm-BCdt"):
    # three weights, one verdict, one call site
    B, C, dt = (linear(params[w], x, "ssm-BCdt", plan)
                for w in ("w_B", "w_C", "w_dt"))
    with jax.named_scope("ssd"):
        dt = jax.nn.softplus(dt.astype(jnp.float32)
                             + params["dt_bias"])          # (b, l, nh)
        A = -jnp.exp(params["A_log"])                      # (nh,)

        # depthwise causal conv on x / B / C separately (carry is concat)
        if conv_carry is not None:
            cx, cB, cC = (conv_carry[..., :di],
                          conv_carry[..., di:di + gdim],
                          conv_carry[..., di + gdim:])
        else:
            cx = cB = cC = None
        xs, nx = _causal_conv(xs, params["conv_x"], cx,
                              params.get("conv_x_bias"))
        B, nB = _causal_conv(B, params["conv_B"], cB,
                             params.get("conv_B_bias"))
        C, nC = _causal_conv(C, params["conv_C"], cC,
                             params.get("conv_C_bias"))
        new_conv = (jnp.concatenate([nx, nB, nC], axis=-1)
                    if nx is not None else None)
        p = s.headdim
        xh = xs.reshape(b, l, nh, p)
        Bh = B.reshape(b, l, s.n_groups, s.d_state)
        Ch = C.reshape(b, l, s.n_groups, s.d_state)

        if decode:
            y_t, new_state = ssd_decode_step(
                state, xh[:, 0], dt[:, 0], A, Bh[:, 0], Ch[:, 0])
            y = y_t[:, None]                               # (b, 1, nh, p)
        else:
            y, new_state = ssd_chunked(xh, dt, A, Bh, Ch,
                                       chunk=min(s.chunk, l),
                                       init_state=state)
        y = y + xh * params["D"][None, None, :, None]
        y = y.reshape(b, l, di)
        # gated RMSNorm: RMSNorm(y) * g * SiLU(z) (norm before the gate),
        # or the published RMSNorm(y * SiLU(z)) * g
        yf = y.astype(jnp.float32)
        if not s.norm_before_gate:
            yf = yf * jax.nn.silu(z.astype(jnp.float32))
        yf = yf * jax.lax.rsqrt(jnp.mean(yf ** 2, -1, keepdims=True)
                                + cfg.rmsnorm_eps)
        y = (yf * params["norm_scale"].astype(jnp.float32)).astype(x.dtype)
        if s.norm_before_gate:
            y = y * jax.nn.silu(z)
    return linear(params["out_proj"], y, "ssm-out", plan), \
        (new_state, new_conv)


def mamba_cache_shapes(cfg: ModelConfig, batch: int):
    s = cfg.ssm
    di = s.d_inner(cfg.d_model)
    nh = s.n_ssm_heads(cfg.d_model)
    gdim = s.n_groups * s.d_state
    return ((batch, nh, s.d_state, s.headdim),            # ssm state
            (batch, s.d_conv - 1, di + 2 * gdim))          # conv carry
