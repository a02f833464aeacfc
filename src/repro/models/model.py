"""Unified LM model covering all 10 assigned architectures.

A model is a stack of *periods*: the smallest repeating layer pattern.
Each period is a list of *slots*, each slot = (mixer, ffn) where
mixer ∈ {attn, mamba, cross} and ffn ∈ {dense, moe, None}.  Parameters for
slot s are stacked over periods, so the layer stack lowers to one
lax.scan over periods (small HLO, fast compile, remat-friendly):

  dense / moe / audio : period = [(attn, dense|moe)]
  ssm (mamba2)        : period = [(mamba, None)]
  hybrid (jamba)      : period = [(mamba, ffn) x 4, (attn, ffn),
                                  (mamba, ffn) x 3]: attention at index
                        attn_every // 2, ffn_i = moe where
                        i % every_n_layers == every_n_layers - 1
  vlm (llama3.2-v)    : period = [(attn, dense) x 4, (cross, dense)]

Entry points:
  init(key, cfg)                       -> params
  forward(params, batch, cfg, rc)      -> logits / loss   (train, prefill)
  init_cache(cfg, rc, batch, max_len)  -> cache pytree
  decode_step(params, cache, tok, pos) -> logits, cache   (serving)
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig, RunConfig
from .attention import attend, decode_attend
from .layers import (attn_init, attn_out_proj, apply_rope, dtype_of,
                     embed_init, linear, qkv_proj, rmsnorm, rmsnorm_init,
                     swiglu, swiglu_init)
from .mamba2 import (mamba_apply, mamba_cache_shapes, mamba_init)
from .moe import moe_apply, moe_init


def _batch_axes(rc: RunConfig):
    axes = tuple(a for a in rc.batch_axes.split(",") if a)
    return axes if len(axes) > 1 else (axes[0] if axes else None)


@dataclasses.dataclass(frozen=True)
class Slot:
    mixer: str          # "attn" | "mamba" | "cross"
    ffn: str | None     # "dense" | "moe" | None


def period_slots(cfg: ModelConfig) -> list[Slot]:
    if cfg.family in ("dense", "audio"):
        return [Slot("attn", "dense")]
    if cfg.family == "moe":
        return [Slot("attn", "moe")]
    if cfg.family == "ssm":
        return [Slot("mamba", None)]
    if cfg.family == "hybrid":
        slots = []
        for i in range(cfg.attn_every):
            # the published layouts put attention mid-period (Jamba's
            # attn_layer_offset 4 of 8, Granite-4.0-H's 5 of 10)
            mixer = "attn" if i == cfg.attn_every // 2 else "mamba"
            ffn = "moe" if (cfg.moe and i % cfg.moe.every_n_layers
                            == cfg.moe.every_n_layers - 1) else "dense"
            slots.append(Slot(mixer, ffn))
        return slots
    if cfg.family == "vlm":
        ce = cfg.vision.cross_attn_every
        return [Slot("attn", "dense")] * (ce - 1) + [Slot("cross", "dense")]
    raise ValueError(cfg.family)


def n_periods(cfg: ModelConfig) -> int:
    P = len(period_slots(cfg))
    assert cfg.n_layers % P == 0, (cfg.n_layers, P)
    return cfg.n_layers // P


# --- init --------------------------------------------------------------------

def _slot_init(key, slot: Slot, cfg: ModelConfig, dtype):
    km, kf, kn1, kn2 = jax.random.split(key, 4)
    p: dict[str, Any] = {"norm1": rmsnorm_init(cfg.d_model, dtype)}
    if slot.mixer in ("attn", "cross"):
        p["attn"] = attn_init(km, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                              cfg.head_dim(), dtype, cfg.qkv_bias)
    else:
        p["mamba"] = mamba_init(km, cfg, dtype)
    if slot.ffn is not None:
        p["norm2"] = rmsnorm_init(cfg.d_model, dtype)
        if slot.ffn == "dense":
            p["mlp"] = swiglu_init(kf, cfg.d_model, cfg.d_ff, dtype)
        else:
            p["moe"] = moe_init(kf, cfg, dtype)
    return p


def init(key, cfg: ModelConfig):
    dtype = dtype_of(cfg.param_dtype)
    slots = period_slots(cfg)
    np_ = n_periods(cfg)
    k_emb, k_head, k_layers = jax.random.split(key, 3)
    params: dict[str, Any] = {}
    if cfg.family == "audio":
        nb = cfg.audio.n_codebooks
        keys = jax.random.split(k_emb, nb)
        params["embed"] = jnp.stack(
            [embed_init(k, cfg.vocab, cfg.d_model, dtype) for k in keys])
        params["lm_head"] = jnp.stack(
            [embed_init(k, cfg.vocab, cfg.d_model, dtype).T
             for k in jax.random.split(k_head, nb)])
    else:
        params["embed"] = embed_init(k_emb, cfg.vocab, cfg.d_model, dtype)
        if not cfg.tie_embeddings:
            params["lm_head"] = embed_init(
                k_head, cfg.vocab, cfg.d_model, dtype).T
    params["final_norm"] = rmsnorm_init(cfg.d_model, dtype)

    # stacked per-slot params over periods
    slot_keys = jax.random.split(k_layers, len(slots))
    stacked = []
    for si, slot in enumerate(slots):
        pkeys = jax.random.split(slot_keys[si], np_)
        per = [_slot_init(k, slot, cfg, dtype) for k in pkeys]
        stacked.append(jax.tree.map(lambda *xs: jnp.stack(xs), *per))
    params["slots"] = stacked
    return params


def _times(w, c: float):
    """A weight times a scalar: a quantized {"q", "scale"} leaf through
    its per-output-channel scale, a float leaf in float32."""
    if isinstance(w, dict):
        return dict(w, scale=w["scale"] * c)
    return (w.astype(jnp.float32) * c).astype(w.dtype)


def fold_multipliers(params, cfg: ModelConfig, *, embedding: float,
                     residual: float, attention: float, logits: float):
    """Fold a model's scalar multipliers into the weights they multiply,
    for a program that has none.  Exact for inference:

      x_0 = embedding * E[t]           -> E <- embedding * E
      x <- x + residual * mixer(x)     -> wo, out_proj <- residual * .
      x <- x + residual * ffn(x)       -> every w_down <- residual * .
      softmax(attention * q k^T)       -> wq <- attention * sqrt(d_head) * .
                                          (the program scales by
                                          1 / sqrt(d_head))
      logits = head(x_L) / logits      -> final norm gain / logits, and
                                          / embedding when the head is
                                          tied to the scaled table

    Takes the float tree of `init` or the quantized one (the scales of
    a quantized projection take the factor)."""
    out = dict(params)
    out["embed"] = _times(params["embed"], embedding)
    head = logits * (embedding if cfg.tie_embeddings else 1.0)
    out["final_norm"] = {"scale": _times(params["final_norm"]["scale"],
                                         1.0 / head)}
    q_factor = attention * cfg.head_dim() ** 0.5

    def slot(sp):
        sp = dict(sp)
        if "attn" in sp:
            a = dict(sp["attn"], wq=_times(sp["attn"]["wq"], q_factor),
                     wo=_times(sp["attn"]["wo"], residual))
            if "bq" in a:
                a["bq"] = _times(a["bq"], q_factor)
            sp["attn"] = a
        if "mamba" in sp:
            sp["mamba"] = dict(sp["mamba"], out_proj=_times(
                sp["mamba"]["out_proj"], residual))
        for ffn in ("mlp", "moe"):
            if ffn in sp:
                sp[ffn] = dict(sp[ffn], w_down=_times(sp[ffn]["w_down"],
                                                      residual))
        if "shared" in sp.get("moe", {}):
            sp["moe"]["shared"] = dict(
                sp["moe"]["shared"],
                w_down=_times(sp["moe"]["shared"]["w_down"], residual))
        return sp
    out["slots"] = [slot(sp) for sp in params["slots"]]
    return out


# --- forward (train / prefill) --------------------------------------------------

def _cross_q_proj(sp, h, b, l, nh, dh, plan=None):
    """Cross-attention query projection ("xattn-Q"), shared by the
    full-sequence forward and the decode step."""
    return linear(sp["attn"]["wq"], h, "xattn-Q", plan).reshape(
        b, l, nh, dh)


def _lm_logits(params, x, cfg: ModelConfig, plan=None):
    """LM head ("lm_head"), shared by forward and decode.  Audio heads are
    per-codebook (nb, d, vocab) and contract via einsum; tied embeddings
    reuse the (float) embedding matrix transposed."""
    spec = "bld,ndv->blnv" if cfg.family == "audio" else None
    with jax.named_scope("lm_head"):
        head = (params["embed"].T
                if cfg.tie_embeddings and cfg.family != "audio"
                else params["lm_head"])
        return linear(head, x, "lm_head", plan, spec=spec)


def _apply_mixer_full(slot: Slot, sp, x, cfg: ModelConfig, rc: RunConfig,
                      image_kv=None, return_cache=False, plan=None):
    """Full-sequence mixer.  Returns (y, cache_entry_or_None)."""
    h = rmsnorm(sp["norm1"], x, cfg.rmsnorm_eps)
    if slot.mixer == "mamba":
        y, (st, cv) = mamba_apply(sp["mamba"], h, cfg, plan=plan)
        return y, ((st, cv) if return_cache else None)
    nh, kv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim()
    if slot.mixer == "cross":
        b, l, _ = x.shape
        q = _cross_q_proj(sp, h, b, l, nh, dh, plan)
        kimg, vimg = image_kv
        # bidirectional attention onto image tokens (no mask)
        s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                       _expand(kimg, nh).astype(jnp.float32))
        s = s / jnp.sqrt(jnp.asarray(dh, jnp.float32))
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", p,
                       _expand(vimg, nh).astype(jnp.float32))
        y = attn_out_proj(sp["attn"], o.astype(x.dtype).reshape(
            b, l, nh * dh), plan, label="xattn-out")
        return y, ((kimg, vimg) if return_cache else None)
    q, k, v = qkv_proj(sp["attn"], h, nh, kv, dh, plan)
    pos = jnp.arange(x.shape[1])[None, :]
    if cfg.rope_theta:                  # 0: no positional embedding
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)
    mode = rc.shard_attn or ("heads" if rc.shard_heads else "")
    if mode:
        # "heads": head-dim TP (GSPMD pads uneven head counts).
        # "seq": context parallelism — queries shard over sequence (always
        # mesh-divisible), K/V all-gather per layer (small for GQA).
        # Batch axes MUST be pinned: a None batch dim lets GSPMD replicate
        # the global batch (EXPERIMENTS.md §Perf iteration 4).
        from jax.sharding import PartitionSpec as _P
        ba = _batch_axes(rc)
        spec = (_P(ba, None, "model", None) if mode == "heads"
                else _P(ba, "model", None, None))
        q = jax.lax.with_sharding_constraint(q, spec)
        if mode == "heads":
            k = jax.lax.with_sharding_constraint(k, spec)
            v = jax.lax.with_sharding_constraint(v, spec)
        else:
            k = jax.lax.with_sharding_constraint(
                k, _P(ba, None, None, None))
            v = jax.lax.with_sharding_constraint(
                v, _P(ba, None, None, None))
    o = attend(q, k, v, impl=rc.attn_impl, chunk=rc.attn_chunk,
               window=cfg.sliding_window, unroll=rc.scan_unroll > 0,
               block_causal=rc.block_causal, q_chunk=rc.attn_q_chunk)
    b, l, _ = x.shape
    y = attn_out_proj(sp["attn"], o.reshape(b, l, nh * dh), plan)
    return y, ((k, v) if return_cache else None)


def _expand(t, nh):
    rep = nh // t.shape[2]
    return jnp.repeat(t, rep, axis=2) if rep > 1 else t


def _apply_ffn(slot: Slot, sp, x, cfg: ModelConfig, plan=None):
    if slot.ffn is None:
        return x, 0.0
    h = rmsnorm(sp["norm2"], x, cfg.rmsnorm_eps)
    if slot.ffn == "dense":
        return x + swiglu(sp["mlp"], h, plan), 0.0
    y, aux = moe_apply(sp["moe"], h, cfg, plan)
    return x + y, aux


def _project_image(params, cfg, image_embeds):
    """Precompute per-period cross-attn K/V from the image-embedding stub."""
    return image_embeds  # projected per-slot inside the scan


def forward(params, tokens, cfg: ModelConfig, rc: RunConfig,
            image_embeds=None, plan=None):
    """tokens: (b, l) int32, or (b, l, n_codebooks) for audio.
    Returns logits (b, l, vocab) (audio: (b, l, nb, vocab)).
    `plan` (KernelPlanTable, jit-static) gates quantized projections —
    prefill and decode share the same per-label verdicts."""
    slots = period_slots(cfg)
    if cfg.family == "audio":
        x = jnp.sum(jax.vmap(lambda e, t: e[t], in_axes=(0, 2),
                             out_axes=2)(params["embed"], tokens), axis=2)
    else:
        x = params["embed"][tokens]
    x = x.astype(dtype_of(cfg.compute_dtype))

    def _sp(t):
        if not rc.sp_residual:
            return t
        from jax.sharding import PartitionSpec as _P
        return jax.lax.with_sharding_constraint(
            t, _P(_batch_axes(rc), "model", None))

    def period_body(carry, period_params):
        x, aux = carry
        x = _sp(x)
        for si, slot in enumerate(slots):
            sp = period_params[si]
            ikv = None
            if slot.mixer == "cross":
                b, limg, _ = image_embeds.shape
                kvh, dh = cfg.n_kv_heads, cfg.head_dim()
                kimg, vimg = (
                    linear(sp["attn"][w], image_embeds, "xattn-KV", plan
                           ).reshape(b, limg, kvh, dh)
                    for w in ("wk", "wv"))
                ikv = (kimg, vimg)
            y, _ = _apply_mixer_full(slot, sp, x, cfg, rc, image_kv=ikv,
                                     plan=plan)
            x = _sp(x + y)
            x, a = _apply_ffn(slot, sp, x, cfg, plan)
            x = _sp(x)
            aux = aux + a
        return (x, aux), None

    body = period_body
    if rc.remat:
        policy = (jax.checkpoint_policies.dots_saveable
                  if rc.remat_policy == "dots"
                  else jax.checkpoint_policies.nothing_saveable)
        body = jax.checkpoint(period_body, policy=policy)
    (x, aux), _ = jax.lax.scan(body, (x, jnp.float32(0.0)),
                               params["slots"],
                               unroll=max(1, min(rc.scan_unroll,
                                                 n_periods(cfg))))
    x = rmsnorm(params["final_norm"], x, cfg.rmsnorm_eps)
    return _lm_logits(params, x, cfg, plan), aux


def loss_fn(params, batch, cfg: ModelConfig, rc: RunConfig):
    """batch: {"tokens": ..., "targets": ..., ["image_embeds"]}.

    The gold logit uses a masked sum over the vocab axis instead of
    take_along_axis: identical numerics, but it keeps the reduction local
    to a vocab-sharded logits tensor (a sharded-dim gather makes GSPMD
    replicate the fp32 logits — tens of GB; §Perf iteration 4)."""
    logits, aux = forward(params, batch["tokens"], cfg, rc,
                          image_embeds=batch.get("image_embeds"))
    tgt = batch["targets"]
    if rc.shard_loss:
        from jax.sharding import PartitionSpec as _P
        ba = _batch_axes(rc)
        spec = (_P(ba, None, None, "model") if cfg.family == "audio"
                else _P(ba, None, "model"))
        logits = jax.lax.with_sharding_constraint(logits, spec)
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    vocab_ids = jax.lax.broadcasted_iota(jnp.int32, lf.shape, lf.ndim - 1)
    gold = jnp.sum(jnp.where(vocab_ids == tgt[..., None], lf, 0.0),
                   axis=-1)
    ce = jnp.mean(lse - gold)
    return ce + aux, {"ce": ce, "aux": aux}


# --- KV / state caches -----------------------------------------------------------

def init_cache(cfg: ModelConfig, rc: RunConfig, batch: int, max_len: int,
               n_image_tokens: int = 0):
    """Cache pytree: one entry per slot, stacked over periods."""
    np_ = n_periods(cfg)
    kv_dtype = dtype_of(rc.kv_cache_dtype) if rc.kv_cache_dtype != "int8" \
        else jnp.int8
    dh, kvh = cfg.head_dim(), cfg.n_kv_heads
    caches = []
    for slot in period_slots(cfg):
        if slot.mixer == "attn":
            shape = (np_, batch, max_len, kvh, dh)
            caches.append({"k": jnp.zeros(shape, kv_dtype),
                           "v": jnp.zeros(shape, kv_dtype)})
            if rc.kv_cache_dtype == "int8":
                caches[-1]["k_scale"] = jnp.zeros(
                    (np_, batch, max_len, kvh), jnp.bfloat16)
                caches[-1]["v_scale"] = jnp.zeros(
                    (np_, batch, max_len, kvh), jnp.bfloat16)
        elif slot.mixer == "cross":
            shape = (np_, batch, n_image_tokens, kvh, dh)
            caches.append({"k": jnp.zeros(shape, jnp.bfloat16),
                           "v": jnp.zeros(shape, jnp.bfloat16)})
        else:
            sst, scv = mamba_cache_shapes(cfg, batch)
            caches.append({"state": jnp.zeros((np_,) + sst, jnp.float32),
                           "conv": jnp.zeros((np_,) + scv, jnp.bfloat16)})
    return caches


def init_paged_cache(cfg: ModelConfig, rc: RunConfig, n_slots: int,
                     n_blocks: int, block_size: int,
                     n_image_tokens: int = 0):
    """Block-pool KV cache for slot-scheduled continuous batching.

    Attention slots get a shared *pool* of `n_blocks` fixed-size blocks,
    (periods, n_blocks, block_size, kv_heads, head_dim), instead of one
    contiguous (batch, max_len) strip per request: each serving slot owns
    a host-managed list of physical block ids (its block table) and
    ragged request lengths share one jitted decode executable.  Mamba
    state / conv carries and cross-attn image KV stay per-slot (they are
    O(1) in sequence length, nothing to page)."""
    np_ = n_periods(cfg)
    kv_dtype = dtype_of(rc.kv_cache_dtype) if rc.kv_cache_dtype != "int8" \
        else jnp.int8
    dh, kvh = cfg.head_dim(), cfg.n_kv_heads
    caches = []
    for slot in period_slots(cfg):
        if slot.mixer == "attn":
            shape = (np_, n_blocks, block_size, kvh, dh)
            caches.append({"k": jnp.zeros(shape, kv_dtype),
                           "v": jnp.zeros(shape, kv_dtype)})
            if rc.kv_cache_dtype == "int8":
                caches[-1]["k_scale"] = jnp.zeros(
                    (np_, n_blocks, block_size, kvh), jnp.bfloat16)
                caches[-1]["v_scale"] = jnp.zeros(
                    (np_, n_blocks, block_size, kvh), jnp.bfloat16)
        elif slot.mixer == "cross":
            shape = (np_, n_slots, n_image_tokens, kvh, dh)
            caches.append({"k": jnp.zeros(shape, jnp.bfloat16),
                           "v": jnp.zeros(shape, jnp.bfloat16)})
        else:
            sst, scv = mamba_cache_shapes(cfg, n_slots)
            caches.append({"state": jnp.zeros((np_,) + sst, jnp.float32),
                           "conv": jnp.zeros((np_,) + scv, jnp.bfloat16)})
    return caches


def _quantize_kv(t):
    scale = jnp.max(jnp.abs(t), axis=-1, keepdims=True) / 127.0 + 1e-8
    return (jnp.round(t / scale).astype(jnp.int8),
            scale[..., 0].astype(jnp.bfloat16))


def _dequantize_kv(q, scale, dtype):
    """int8 codes times their row's scale, in the query's `dtype`: exact
    in float32, one rounding in bfloat16."""
    return q.astype(dtype) * scale[..., None].astype(dtype)


def _paged_write(pool, i, new, pos, block_tables, active):
    """Scatter one row per slot into layer `i` of a stacked block pool,
    in place.

    pool: (periods, n_blocks, block_size, ...); new: (b, ...); pos: (b,)
    logical positions; block_tables: (b, max_blocks) physical block ids.
    Inactive slots write out of bounds and are dropped (their KV must not
    clobber live blocks)."""
    bs = pool.shape[2]
    blk = jnp.take_along_axis(block_tables, (pos // bs)[:, None],
                              axis=1)[:, 0]
    if active is not None:
        blk = jnp.where(active, blk, pool.shape[1])       # OOB -> drop
    return pool.at[i, blk, pos % bs].set(new.astype(pool.dtype),
                                         mode="drop")


def _paged_view(pool, i, block_tables):
    """Gather each slot's logical KV strip from layer `i` of a stacked
    pool: (periods, n_blocks, bs, ...) + (b, max_blocks) ->
    (b, max_blocks * bs, ...)."""
    v = pool[i, block_tables]
    return v.reshape((v.shape[0], v.shape[1] * v.shape[2]) + v.shape[3:])


def _mask_rows(new, old, active):
    """Per-slot select: active slots take the updated cache row, evicted /
    free slots keep (frozen) state so garbage tokens can't corrupt them."""
    if active is None:
        return new
    with jax.named_scope("cache_mask"):
        m = active.reshape((-1,) + (1,) * (new.ndim - 1))
        return jnp.where(m, new.astype(old.dtype), old)


def _zero_fresh(old, fresh):
    """Per-slot select: lanes at position 0 read a zero SSM state / conv
    carry (a lane with no context has none), the others their own.  A
    select, not a product, so a NaN left in a freed lane cannot leak."""
    with jax.named_scope("cache_mask"):
        m = fresh.reshape(fresh.shape + (1,) * (old.ndim - fresh.ndim))
        return jnp.where(m, jnp.zeros((), old.dtype), old)


def _decode_attention(q, k, v, cache_s, i, pos, cfg: ModelConfig,
                      rc: RunConfig, active, block_tables):
    """RoPE (none when rope_theta is 0), this token's KV write into layer `i` of the stacked cache
    entry and attention over the slot's cache: (o, updated stacked entry).
    Paged when `block_tables` is given: the row is scattered into the
    slot's current block and its logical strip is gathered back;
    otherwise the row goes to `pos` of the contiguous cache.  Both write
    into the stack in place: no layer is sliced out and written back."""
    b = q.shape[0]
    ragged = jnp.ndim(pos) == 1
    pvec = pos[:, None] if ragged else jnp.full((b, 1), pos, jnp.int32)
    if cfg.rope_theta:                  # 0: no positional embedding
        q = apply_rope(q, pvec, cfg.rope_theta)
        k = apply_rope(k, pvec, cfg.rope_theta)
    int8_kv = rc.kv_cache_dtype == "int8"
    if int8_kv:
        (kq, ks), (vq, vs) = _quantize_kv(k), _quantize_kv(v)
        rows = {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}
    else:
        rows = {"k": k, "v": v}
    if block_tables is not None:
        entry = {n: _paged_write(cache_s[n], i, r[:, 0], pos, block_tables,
                                 active) for n, r in rows.items()}
        view = {n: _paged_view(c, i, block_tables)
                for n, c in entry.items()}
        lens = pos + 1 if ragged else jnp.full((b,), pos + 1, jnp.int32)
    else:
        entry = {n: jax.lax.dynamic_update_slice(
                     cache_s[n], r[None].astype(cache_s[n].dtype),
                     (i, 0, pos) + (0,) * (r.ndim - 2))
                 for n, r in rows.items()}
        view = {n: jax.lax.dynamic_index_in_dim(c, i, keepdims=False)
                for n, c in entry.items()}
        lens = jnp.full((b,), pos + 1, jnp.int32)
    if int8_kv:
        kd = _dequantize_kv(view["k"], view["k_scale"], q.dtype)
        vd = _dequantize_kv(view["v"], view["v_scale"], q.dtype)
    else:
        kd, vd = view["k"], view["v"]
    o = decode_attend(q, kd, vd, lens, window=cfg.sliding_window)
    return o, entry


# --- decode -----------------------------------------------------------------------

def decode_step(params, cache, tokens, pos, cfg: ModelConfig,
                rc: RunConfig, plan=None, active=None, block_tables=None):
    """One decode step.  tokens: (b, 1) (audio: (b, 1, nb)); pos: () int32
    current length (uniform across batch) OR (b,) int32 per-slot lengths
    (ragged, continuous batching).  Returns (logits, new_cache).
    `plan` is the jit-static KernelPlanTable: gated projection labels
    lower to the INT8 Pallas path inside the one compiled step.

    Continuous-batching extensions (all jit-dynamic — one executable):
      * ragged `pos` (b,): each slot attends/ropes at its own length;
      * `active` (b,) bool: cache writes of inactive (free / draining)
        slots are masked out, so join/evict never retraces or corrupts
        neighbouring requests;
      * a slot at position 0 starts from a zero SSM state and conv
        carry, whatever its cache rows hold: a joining request needs no
        reset outside the step;
      * `block_tables` (b, max_blocks) int32: attention KV lives in the
        block pool laid out by `init_paged_cache`; reads gather the
        slot's logical strip, writes scatter one row into its current
        block.  Required whenever `pos` is ragged and the arch has
        attention slots."""
    slots = period_slots(cfg)
    b = tokens.shape[0]
    ragged = jnp.ndim(pos) == 1
    if ragged and block_tables is None and any(s.mixer == "attn"
                                              for s in slots):
        raise ValueError(
            "ragged per-slot positions need a paged KV cache: pass "
            "block_tables (see init_paged_cache) for attention archs")
    with jax.named_scope("embed"):
        if cfg.family == "audio":
            x = jnp.sum(jax.vmap(lambda e, t: e[t], in_axes=(0, 2),
                                 out_axes=2)(params["embed"], tokens),
                        axis=2)
        else:
            x = params["embed"][tokens]
        x = x.astype(dtype_of(cfg.compute_dtype))
    nh, kvh, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim()
    fresh = jnp.asarray(pos) == 0       # () or (b,): lanes with no context

    # The stacked cache rides in the scan's carry and each layer reads and
    # writes its own index of it in place.  Donation aliases the
    # program's input and output, not a scan's xs and ys: passed as those,
    # the cache is copied per layer and again whole after the loop.
    def period_body(carry, scanned):
        x, cache = carry
        period_params, i = scanned
        cache = list(cache)
        for si, slot in enumerate(slots):
            sp, cache_s = period_params[si], cache[si]
            h = rmsnorm(sp["norm1"], x, cfg.rmsnorm_eps)
            if slot.mixer == "mamba":
                old = {n: jax.lax.dynamic_index_in_dim(c, i, keepdims=False)
                       for n, c in cache_s.items()}
                y, new = mamba_apply(
                    sp["mamba"], h, cfg,
                    state=_zero_fresh(old["state"], fresh),
                    conv_carry=_zero_fresh(old["conv"], fresh),
                    decode=True, plan=plan)
                cache[si] = {
                    n: jax.lax.dynamic_update_index_in_dim(
                        cache_s[n],
                        _mask_rows(t, old[n], active).astype(old[n].dtype),
                        i, 0)
                    for n, t in zip(("state", "conv"), new)}
            elif slot.mixer == "cross":
                q = _cross_q_proj(sp, h, b, 1, nh, dh, plan)
                kx, vx = (jax.lax.dynamic_index_in_dim(cache_s[n], i,
                                                       keepdims=False)
                          for n in ("k", "v"))
                with jax.named_scope("attn_core"):
                    o = decode_attend(q, kx, vx,
                                      jnp.full((b,), kx.shape[1], jnp.int32))
                y = attn_out_proj(sp["attn"], o.reshape(b, 1, nh * dh),
                                  plan, label="xattn-out")
            else:
                q, k, v = qkv_proj(sp["attn"], h, nh, kvh, dh, plan)
                with jax.named_scope("attn_core"):
                    o, cache[si] = _decode_attention(
                        q, k, v, cache_s, i, pos, cfg, rc, active,
                        block_tables)
                y = attn_out_proj(sp["attn"], o.reshape(b, 1, nh * dh),
                                  plan)
            x = x + y
            x, _ = _apply_ffn(slot, sp, x, cfg, plan)
        return (x, cache), None

    np_ = n_periods(cfg)
    with jax.named_scope("layer_scan"):
        (x, cache), _ = jax.lax.scan(
            period_body, (x, list(cache)),
            (params["slots"], jnp.arange(np_, dtype=jnp.int32)),
            unroll=max(1, min(rc.scan_unroll, np_)))
    x = rmsnorm(params["final_norm"], x, cfg.rmsnorm_eps)
    return _lm_logits(params, x, cfg, plan), cache
