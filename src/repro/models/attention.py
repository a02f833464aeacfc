"""Attention implementations: naive, chunked-flash (pure JAX, memory-safe
for 32k prefill), decode with KV cache, and sliding-window (sub-quadratic).

The Pallas TPU kernels in repro.kernels implement the same contracts; the
`impl` switch selects between them (dry-run/CPU uses the jnp versions).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from .layers import (apply_rope, attn_out_proj, dense_init, linear, rmsnorm,
                     yarn_mscale)

NEG_INF = -1e30


def _gqa_expand(k, n_heads: int):
    """(b, s, kv, d) -> (b, s, H, d) by repeating kv heads."""
    b, s, kv, d = k.shape
    if kv == n_heads:
        return k
    rep = n_heads // kv
    return jnp.repeat(k, rep, axis=2)


def naive_causal(q, k, v, positions_q=None, positions_k=None,
                 window: int = 0):
    """Reference attention.  q: (b, sq, H, d); k/v: (b, sk, KV, d)."""
    b, sq, nh, d = q.shape
    k = _gqa_expand(k, nh)
    v = _gqa_expand(v, nh)
    sk = k.shape[1]
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    pos_q = (positions_q if positions_q is not None
             else jnp.arange(sq)[None, :] + (sk - sq))
    pos_k = (positions_k if positions_k is not None
             else jnp.arange(sk)[None, :])
    mask = pos_q[:, None, :, None] >= pos_k[:, None, None, :]
    if window:
        mask &= pos_q[:, None, :, None] - pos_k[:, None, None, :] < window
    logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


def flash_jnp(q, k, v, chunk: int = 1024, window: int = 0,
              unroll: bool = False):
    """Chunked online-softmax causal attention in pure JAX.

    O(sq * chunk) live memory per head — lowers cleanly for 32k prefill
    where the naive score matrix would not fit.  Streams KV chunks with a
    lax.scan carrying (m, l, acc) online-softmax state.
    """
    b, sq, nh, d = q.shape
    k = _gqa_expand(k, nh)
    v = _gqa_expand(v, nh)
    sk = k.shape[1]
    n_chunks = sk // chunk
    assert n_chunks * chunk == sk, (sk, chunk)
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))

    # operands stay in their input dtype; the MXU accumulates in f32
    # (preferred_element_type) — halves gather/reshard bytes vs upcasting
    qf = q
    kc = k.reshape(b, n_chunks, chunk, nh, d)
    vc = v.reshape(b, n_chunks, chunk, nh, v.shape[-1])
    pos_q = jnp.arange(sq) + (sk - sq)

    def step(carry, inputs):
        m, l, acc = carry
        kj, vj, j = inputs
        pos_k = j * chunk + jnp.arange(chunk)
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kj,
                       preferred_element_type=jnp.float32) * scale
        mask = pos_q[None, None, :, None] >= pos_k[None, None, None, :]
        if window:
            mask &= (pos_q[None, None, :, None]
                     - pos_k[None, None, None, :]) < window
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p.astype(vj.dtype), vj,
            preferred_element_type=jnp.float32)
        return (m_new, l, acc), None

    m0 = jnp.full((b, nh, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, nh, sq), jnp.float32)
    a0 = jnp.zeros((b, nh, sq, v.shape[-1]), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        step, (m0, l0, a0),
        (kc.swapaxes(0, 1), vc.swapaxes(0, 1), jnp.arange(n_chunks)),
        unroll=(n_chunks if unroll else 1))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.swapaxes(1, 2).astype(q.dtype)   # (b, sq, H, d)


def decode_attend(q, k_cache, v_cache, cache_len, window: int = 0,
                  scale=None):
    """Single-token decode attention over a (b, S, KV, d) cache.

    cache_len: (b,) valid lengths.  q: (b, 1, H, d).  Linear in S.
    The scores are scaled by `scale` (default 1 / sqrt(d)); the values
    may have a width of their own (v_cache (b, S, KV, dv) gives
    (b, 1, H, dv)).

    Each KV head's strip is contracted with its own group of H // KV
    query heads (MHA and MQA are groups of 1 and of H).  Both
    contractions read the cache as stored and accumulate in float32
    (bf16 x bf16 products are exact in f32): the cache is never repeated
    to H heads nor copied to f32.  Masking and the softmax run in f32;
    the probabilities enter the second contraction in the dtype the
    query and the cache promote to (the cache dtype where they agree, as
    in `flash_jnp`).  With a sequence-sharded cache every large tensor
    stays S-sharded, so the only collectives are the small softmax and
    output reductions.
    """
    b, _, nh, d = q.shape
    S, kv = k_cache.shape[1], k_cache.shape[2]
    if scale is None:
        scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
    pos = jnp.arange(S)[None, :]
    valid = pos < cache_len[:, None]
    if window:
        valid &= pos >= (cache_len[:, None] - window)
    qg = q.reshape(b, 1, kv, nh // kv, d)
    s = jnp.einsum("bqgrd,bsgd->bgrqs", qg, k_cache,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(valid[:, None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    p = p.astype(jnp.result_type(q, v_cache))
    out = jnp.einsum("bgrqs,bsgd->bqgrd", p, v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, 1, nh, v_cache.shape[-1]).astype(q.dtype)


def flash_block_causal(q, k, v, q_chunk: int = 4096, kv_chunk: int = 1024,
                       window: int = 0, unroll: bool = False):
    """Block-causal chunked attention: queries are processed in chunks and
    each chunk only visits KV chunks at or below its diagonal — halves the
    attention FLOPs vs scanning every KV chunk (and skips far-past chunks
    entirely under a sliding window)."""
    b, sq, nh, d = q.shape
    sk = k.shape[1]
    assert sq == sk, "block-causal path expects self-attention"
    nq = sq // q_chunk
    if nq * q_chunk != sq or nq <= 1:
        return flash_jnp(q, k, v, chunk=kv_chunk, window=window,
                         unroll=unroll)
    outs = []
    for qi in range(nq):
        qs = qi * q_chunk
        kv_end = qs + q_chunk
        kv_start = 0
        if window:
            kv_start = max(0, (qs - window) // kv_chunk * kv_chunk)
        qcb = q[:, qs:qs + q_chunk]
        kcb = k[:, kv_start:kv_end]
        vcb = v[:, kv_start:kv_end]
        outs.append(flash_jnp(qcb, kcb, vcb,
                              chunk=min(kv_chunk, kv_end - kv_start),
                              window=window, unroll=unroll))
    return jnp.concatenate(outs, axis=1)


def attend(q, k, v, impl: str = "flash_jnp", chunk: int = 1024,
           window: int = 0, unroll: bool = False, block_causal: bool = False,
           q_chunk: int = 4096):
    if impl == "naive" or k.shape[1] % max(chunk, 1) != 0 \
            or k.shape[1] <= chunk:
        return naive_causal(q, k, v, window=window)
    if impl == "pallas":
        from repro.kernels import ops as kops
        return kops.flash_attention(q, k, v, causal=True, window=window)
    if block_causal:
        return flash_block_causal(q, k, v, q_chunk=q_chunk, kv_chunk=chunk,
                                  window=window, unroll=unroll)
    return flash_jnp(q, k, v, chunk=chunk, window=window, unroll=unroll)


# --- multi-head latent attention (DeepSeek-V2) ------------------------------
#
# For h (b, l, d) and one layer (no q-LoRA):
#   q = h Wq                   per head: q_nope (nope) | q_pe (rope)
#   [c | k_pe] = h Wkv_a       latent (r) | one rotated key for all heads
#   c = RMSNorm(c) g_kv
#   k_nope_j = c W_UK,j, v_j = c W_UV,j   (Wkv_b's halves, per head j)
#   q_pe, k_pe rotated (the published interleaved pair order, YaRN)
#   s_j = ([q_nope_j | q_pe_j] . [k_nope_j | k_pe]) * scale
#   out = concat_j(softmax(s_j) v_j) Wo
# The full-sequence form computes it so.  The decode form absorbs W_UK
# into the query and W_UV into the output: q_lat_j = W_UK,j q_nope_j, the
# scores are q_lat_j . c + q_pe_j . k_pe and o_j = W_UV,j^T sum p c: one
# "KV head" of [c | k_pe] (its first r channels the values) that all
# query heads share, so the cache holds r + rope values a token.

def mla_init(key, cfg, dtype):
    m, d, nh = cfg.mla, cfg.d_model, cfg.n_heads
    ks = jax.random.split(key, 5)
    r = m.kv_lora_rank

    def per_head(k, n):                     # (heads, r, n): c -> n
        return (jax.random.normal(k, (nh, r, n), jnp.float32)
                / math.sqrt(r)).astype(dtype)
    return {"wq": dense_init(ks[0], d, nh * m.qk_head_dim, dtype),
            "wkv_a": dense_init(ks[1], d, m.latent_dim, dtype),
            "kv_norm": {"scale": jnp.ones((r,), dtype)},
            "w_uk": per_head(ks[2], m.qk_nope_head_dim),
            "w_uv": per_head(ks[3], m.v_head_dim),
            "wo": dense_init(ks[4], nh * m.v_head_dim, d, dtype,
                             scale=1.0 / math.sqrt(nh * m.v_head_dim))}


def mla_scale(cfg) -> float:
    """The score scale: qk_head_dim^-1/2, times YaRN's mscale_all_dim
    term squared."""
    s = cfg.mla.qk_head_dim ** -0.5
    rs = cfg.rope_scaling
    if rs is not None:
        s *= yarn_mscale(rs.factor, rs.mscale_all_dim) ** 2
    return s


def mla_qkv(p, h, positions, cfg, plan=None):
    """The front both forms share: (q_nope (b, l, H, nope), q_pe
    (b, l, H, rope), c (b, l, r), k_pe (b, l, rope)), the last two the
    latent row a token caches.  positions: (b, l)."""
    b, l, _ = h.shape
    m = cfg.mla
    q = linear(p["wq"], h, "Wq", plan).reshape(b, l, cfg.n_heads,
                                                m.qk_head_dim)
    kv = linear(p["wkv_a"], h, "mla-kv_a", plan)
    c = rmsnorm(p["kv_norm"], kv[..., :m.kv_lora_rank], cfg.rmsnorm_eps)
    with jax.named_scope("attn_core"):
        rope = partial(apply_rope, positions=positions,
                       theta=cfg.rope_theta, scaling=cfg.rope_scaling,
                       interleaved=True)
        q_pe = rope(q[..., m.qk_nope_head_dim:])
        k_pe = rope(kv[..., None, m.kv_lora_rank:])[:, :, 0]
    return q[..., :m.qk_nope_head_dim], q_pe, c, k_pe


def mla_full(p, h, cfg, rc, plan=None):
    """Full-sequence causal MLA, unabsorbed: (b, l, d) -> (b, l, d), and
    the latent rows (c, k_pe) for a cache."""
    b, l, _ = h.shape
    m, nh = cfg.mla, cfg.n_heads
    q_nope, q_pe, c, k_pe = mla_qkv(p, h, jnp.arange(l)[None, :], cfg, plan)
    k_nope = _mla_up(p, "w_uk", c, "blc,hcn->blhn", plan)
    v = _mla_up(p, "w_uv", c, "blc,hcn->blhn", plan)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_pe[:, :, None], (b, l, nh, m.qk_rope_head_dim))], -1)
    # `attend` scales by qk_head_dim^-1/2: the query carries the rest
    q = jnp.concatenate([q_nope, q_pe], -1)
    q = (q.astype(jnp.float32) * (mla_scale(cfg) * math.sqrt(
        m.qk_head_dim))).astype(q.dtype)
    o = attend(q, k, v, impl=rc.attn_impl, chunk=rc.attn_chunk,
               unroll=rc.scan_unroll > 0, block_causal=rc.block_causal,
               q_chunk=rc.attn_q_chunk)
    y = attn_out_proj(p, o.reshape(b, l, nh * m.v_head_dim), plan)
    return y, (c, k_pe)


_MLA_UP = {"w_uk": "mla-UK", "w_uv": "mla-UV"}


def _mla_up(p, name, x, spec, plan):
    """x through W_UK or W_UV (`name`), the per-head halves of Wkv_b,
    (heads, r, n): the one call site of their labels, whichever way
    `spec` applies them."""
    return linear(p[name], x, _MLA_UP[name], plan, spec=spec)


def mla_absorb_query(p, q_nope, plan=None):
    """q_lat_j = W_UK,j q_nope_j: (b, l, H, nope) -> (b, l, H, r).  The
    weight's per-channel scale belongs to the nope channels this
    contracts, so it scales the query before the contraction."""
    return _mla_up(p, "w_uk", q_nope, "blhn,hcn->blhc", plan)


def mla_expand_output(p, o_lat, plan=None):
    """o_j = W_UV,j^T o_lat_j: (b, l, H, r) -> (b, l, H * v)."""
    b, l = o_lat.shape[:2]
    return _mla_up(p, "w_uv", o_lat, "blhc,hcn->blhn", plan).reshape(
        b, l, -1)
