"""Decode hot-path optimizations: epilogue-fused INT8 dequant, KV-cache
buffer donation, the sync-free (pipelined) token loop, batched
first-logits fetch, and the Pallas block-size autotune table.

The contract under test: none of these optimizations may change the
math.  The fused dequant epilogue must match the canonical
`dequantize_weight` expression within float-reassociation tolerance on
every in-repo einsum spec (stacked experts included); the pipelined
engine must produce token streams EXACTLY equal to the synchronous
engine; donation must demonstrably update the cache pools in place; and
autotuned GEMM blocks must always be legal (divisible, VMEM-fitting).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, RunConfig, reduced
from repro.kernels.autotune import (INT8_GEMM_TABLE, SWEEP_ROW_LADDER,
                                    autotune_report, int8_gemm_blocks,
                                    int8_gemm_vmem_bytes, sweep_block_rows)
from repro.models import init, init_cache
from repro.quant.int8 import (dequant_contract, dequantize_weight,
                              quantize_weight)
from repro.serving import (ContinuousBatchingEngine, DecodeCore,
                           ServeSession, synthetic_requests)

RC = RunConfig(remat=False, attn_impl="naive")
MAX_LEN = 24
BLOCK = 4


def _core(arch: str):
    cfg = reduced(ARCHS[arch])
    params = init(jax.random.PRNGKey(0), cfg)
    return cfg, params, DecodeCore(cfg, RC, params, quantize=True,
                                   plan_batch=4, plan_max_len=MAX_LEN)


@pytest.fixture(scope="module")
def mamba():
    return _core("mamba2-780m")


@pytest.fixture(scope="module")
def attn():
    return _core("mistral-nemo-12b")


@pytest.fixture(scope="module")
def hybrid():
    return _core("granite-4.0-h-small")


# --- epilogue-fused dequant --------------------------------------------------

def _quantized(key, k, n, stacked=()):
    w = jax.random.normal(key, (*stacked, k, n), jnp.float32)
    fn = quantize_weight
    for _ in stacked:
        fn = jax.vmap(fn)
    return fn(w)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_dequant_contract_matches_reference(dtype):
    """Fused epilogue == canonical dequantize_weight contraction, and the
    output keeps the activation dtype (no silent f32 upcast)."""
    q, s = _quantized(jax.random.PRNGKey(0), 64, 48)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 64), jnp.float32) \
        .astype(dtype)
    got = dequant_contract(x, q, s)
    ref = dequant_contract(x, q, s, materialize=True)
    assert got.dtype == dtype and ref.dtype == dtype
    tol = 1e-5 if dtype == jnp.float32 else 0.05
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)
    # and against the canonical expression itself
    ref2 = x @ dequantize_weight(q, s, dtype)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref2, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("spec,x_shape,stacked", [
    # stacked MoE experts, both contraction directions (models/moe.py)
    ("ecd,edf->ecf", (3, 4, 16), (3,)),
    ("ecf,efd->ecd", (3, 4, 16), (3,)),
    # MoE decode fast path: all experts over the shared token batch
    ("td,edf->etf", (4, 16), (3,)),
    ("etf,efd->etd", (3, 4, 16), (3,)),
    # multi-head readout (models/layers.py audio head)
    ("bld,ndv->blnv", (2, 5, 16), (4,)),
    # latent attention's per-head W_UK / W_UV (models/attention.py):
    # up from the latent, and the absorbed decode form, whose W_UK
    # contraction sums over the scaled channels themselves
    ("blc,hcn->blhn", (2, 5, 16), (4,)),
    ("blhc,hcn->blhn", (2, 1, 4, 16), (4,)),
    ("blhn,hcn->blhc", (2, 1, 4, 8), (4,)),
])
def test_dequant_contract_stacked_specs(spec, x_shape, stacked):
    """Every in-repo einsum spec: the per-(expert, channel) scale applied
    as an output epilogue (on the input where the spec contracts the
    scaled channels) equals materializing each expert's weight."""
    q, s = _quantized(jax.random.PRNGKey(2), 16, 8, stacked)
    x = jax.random.normal(jax.random.PRNGKey(3), x_shape, jnp.float32)
    got = dequant_contract(x, q, s, spec)
    ref = dequant_contract(x, q, s, spec, materialize=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_dequant_contract_fallback_spec():
    """A spec whose scale axis is summed out of the output cannot take
    the epilogue path; dequant_contract must detect it (None from the
    reshape helper) and fall back to materializing — same answer."""
    from repro.quant.int8 import _epilogue_scale
    q, s = _quantized(jax.random.PRNGKey(4), 16, 8, (3,))
    assert _epilogue_scale("ab,cbd->ad", s) is None
    x = jax.random.normal(jax.random.PRNGKey(5), (4, 16), jnp.float32)
    got = dequant_contract(x, q, s, "ab,cbd->ad")
    ref = dequant_contract(x, q, s, "ab,cbd->ad", materialize=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_moe_decode_fast_path_matches_buffered():
    """When every token fits expert capacity (T <= C — any decode
    micro-batch), dropping is impossible and the dense fast path must
    equal the scatter/gather dispatch: the same per-(expert, token)
    contractions and top-k weighted sum.  The two paths reduce in a
    different order (`etf` einsums + sum over k vs `ecf` einsums +
    segment_sum), so reassociation moves the f32 result by a few ulps
    (measured on XLA:CPU: at most 4.8e-7 absolute, outputs of mean
    magnitude 0.68 and max 3.4).  atol 1e-6 + rtol 5e-6 allows about
    ten ulps at that scale, far below a routing or capacity error,
    which would move an output by its own magnitude."""
    from repro.models import moe
    cfg = reduced(ARCHS["qwen2-moe-a2.7b"])
    params = moe.moe_init(jax.random.PRNGKey(10), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(11), (8, 1, cfg.d_model),
                          jnp.float32)
    assert 8 <= moe.capacity(cfg, 8)
    y_fast, aux_f = moe.moe_apply(params, x, cfg)
    y_buf, aux_b = moe.moe_apply(params, x, cfg, force_buffered=True)
    np.testing.assert_allclose(np.asarray(y_fast), np.asarray(y_buf),
                               rtol=5e-6, atol=1e-6)
    assert float(aux_f) == float(aux_b)


def test_epilogue_golden_logits_parity_mamba(mamba, monkeypatch):
    """Whole-model gate on the mixed-verdict mamba2 cell: decode logits
    with the fused epilogue vs a model traced with the canonical
    materializing dequant must agree within kernel-numerics tolerance
    and pick the same greedy tokens."""
    cfg, params, _ = mamba
    prompt = np.asarray(
        jax.random.randint(jax.random.PRNGKey(7), (2, 5), 0, cfg.vocab))

    fused = ServeSession(cfg, RC, params, max_len=MAX_LEN, batch=2,
                         quantize=True)
    lf = np.asarray(fused.prefill(prompt), np.float32)

    import repro.models.layers as layers
    import repro.quant.int8 as int8mod
    ref_fn = lambda x, q, s, spec=None, **kw: dequant_contract(
        x, q, s, spec, materialize=True)
    monkeypatch.setattr(layers, "dequant_contract", ref_fn)
    monkeypatch.setattr(int8mod, "dequant_contract", ref_fn)
    ref = ServeSession(cfg, RC, params, max_len=MAX_LEN, batch=2,
                       quantize=True)
    lr = np.asarray(ref.prefill(prompt), np.float32)

    assert float(np.max(np.abs(lf - lr))) <= 0.05
    np.testing.assert_array_equal(lf[:, -1].argmax(-1),
                                  lr[:, -1].argmax(-1))


# --- buffer donation ---------------------------------------------------------

@pytest.fixture(scope="module")
def mamba_donating(mamba):
    """Same weights, donation forced on (the accelerator default; CPU
    defaults off because XLA:CPU's aliased program measured slower)."""
    cfg, params, _ = mamba
    return cfg, params, DecodeCore(cfg, RC, params, quantize=True,
                                   plan_batch=4, plan_max_len=MAX_LEN,
                                   donate=True)


def test_donation_defaults_per_platform(mamba):
    """donate=None resolves from the backend: off on CPU (where the
    aliased program is slower), on everywhere else."""
    _, _, core = mamba
    assert core.donate == (jax.default_backend() != "cpu")


def test_decode_core_step_donates_cache(mamba_donating):
    """With donation on, the jitted fixed-batch step consumes its cache
    argument: after one call the input pools are gone (aliased into the
    output), proving the multi-MB state updates in place instead of
    copying per token."""
    cfg, _, core = mamba_donating
    cache = jax.tree.map(jnp.asarray, init_cache(cfg, RC, 4, MAX_LEN))
    leaves = [l for l in jax.tree.leaves(cache) if hasattr(l, "is_deleted")]
    assert leaves, "cache has no donatable array leaves"
    tokens = jnp.zeros((4, 1), jnp.int32)
    _, cache2 = core.step(cache, tokens, jnp.int32(0))
    jax.block_until_ready(jax.tree.leaves(cache2)[0])
    assert all(l.is_deleted() for l in leaves)


def test_engine_donation_probe(mamba_donating, mamba):
    """The continuous engine's first-step probe reports donation took
    effect on a donating core; a non-donating core reports None (probe
    skipped), never a false failure."""
    for (cfg, _, core), want in ((mamba_donating, True),
                                 (mamba, None)):
        if core.donate:        # default CPU core: donation off -> None
            want = True
        eng = ContinuousBatchingEngine(core, n_slots=2, max_len=MAX_LEN,
                                       block_size=BLOCK)
        eng.run(synthetic_requests(cfg, 2, seed=0, prompt_len=(4, 6),
                                   new_tokens=(4, 6)), None)
        agg = eng.telemetry()["aggregate"]
        assert agg["kv_donation_ok"] is want


def test_donating_engine_tokens_match_default(mamba, mamba_donating):
    """Donation is an aliasing change only — token streams are exactly
    equal between a donating and a non-donating core."""
    cfg = mamba[0]
    streams = []
    for _, _, core in (mamba, mamba_donating):
        eng = ContinuousBatchingEngine(core, n_slots=3, max_len=MAX_LEN,
                                       block_size=BLOCK)
        reqs = synthetic_requests(cfg, 4, seed=3, prompt_len=(4, 7),
                                  new_tokens=(4, 7))
        eng.run(reqs, None)
        streams.append({r.rid: np.asarray(r.tokens).reshape(-1)
                        for r in eng.completed})
    assert streams[0].keys() == streams[1].keys()
    for rid in streams[0]:
        np.testing.assert_array_equal(streams[0][rid], streams[1][rid])


# --- sync-free (pipelined) token loop ----------------------------------------

def _stream(core, cfg, pipeline):
    eng = ContinuousBatchingEngine(core, n_slots=3, max_len=MAX_LEN,
                                   block_size=BLOCK, pipeline=pipeline,
                                   record_logits=True)
    reqs = synthetic_requests(cfg, 5, seed=1, prompt_len=(4, 8),
                              new_tokens=(4, 8))
    eng.run(reqs, None)
    assert len(eng.completed) == len(reqs)
    return eng, {r.rid: np.asarray(r.tokens).reshape(-1)
                 for r in eng.completed}


@pytest.mark.parametrize("arch_fixture", ["mamba", "attn", "hybrid"])
def test_pipelined_tokens_exactly_match_sync(arch_fixture, request):
    """The one-step-deep pipelined loop is a scheduling change only:
    token streams are EXACTLY the synchronous engine's, per request, on
    the ssm, the paged-KV and the hybrid arch."""
    cfg, _, core = request.getfixturevalue(arch_fixture)
    eng_p, piped = _stream(core, cfg, pipeline=True)
    _, synced = _stream(core, cfg, pipeline=False)
    assert piped.keys() == synced.keys()
    for rid in piped:
        np.testing.assert_array_equal(piped[rid], synced[rid])
    # the pipelined run must actually have run pipelined (greedy traffic)
    bd = eng_p.telemetry()["aggregate"]["decode_step_breakdown"]
    assert bd["pipelined"] is True


def test_first_logits_batched_fetch_matches_legacy(mamba):
    """first_logits recorded through the batched one-transfer-per-step
    fetch equal the legacy session's prefill logits for each request."""
    cfg, params, core = mamba
    eng, _ = _stream(core, cfg, pipeline=True)
    legacy = ServeSession(cfg, RC, params, max_len=MAX_LEN, batch=1,
                          quantize=True)
    for r in eng.completed:
        assert r.first_logits is not None
        legacy.reset()
        ref = np.asarray(legacy.prefill(np.asarray(r.prompt)[None]),
                         np.float32)[0, -1]
        d = float(np.max(np.abs(np.asarray(r.first_logits,
                                           np.float32) - ref)))
        assert d <= 0.05


def test_step_breakdown_telemetry(mamba):
    """decode_step_breakdown accounts the host budget of every step."""
    cfg, _, core = mamba
    eng, _ = _stream(core, cfg, pipeline=True)
    bd = eng.telemetry()["aggregate"]["decode_step_breakdown"]
    assert bd["steps"] == eng.steps > 0
    for k in ("dispatch_s", "host_fetch_s", "telemetry_s",
              "dispatch_ms_per_step", "host_fetch_ms_per_step",
              "telemetry_ms_per_step"):
        assert bd[k] >= 0.0


def test_temperature_falls_back_to_sync(mamba):
    """Temperature sampling needs host logits every step: submitting one
    such request flips the engine out of pipelined mode (correctness
    over overlap) and everything still completes."""
    cfg, _, core = mamba
    eng = ContinuousBatchingEngine(core, n_slots=2, max_len=MAX_LEN,
                                   block_size=BLOCK, pipeline=True)
    reqs = synthetic_requests(cfg, 3, seed=2, prompt_len=(4, 6),
                              new_tokens=(4, 6))
    reqs[1].temperature = 0.8
    eng.run(reqs, None)
    assert len(eng.completed) == len(reqs)
    bd = eng.telemetry()["aggregate"]["decode_step_breakdown"]
    assert bd["pipelined"] is False


# --- block-size autotune table -----------------------------------------------

@pytest.mark.parametrize("M,N,K", [
    (1, 512, 256), (8, 512, 256), (8, 256, 2048), (64, 1024, 1024),
    (256, 128, 512), (1024, 1024, 1024), (4096, 96, 768), (7, 130, 96),
])
def test_int8_gemm_blocks_always_legal(M, N, K):
    """Whatever the table decides, the blocks meet the TPU tiling rule
    (the full dim, or a multiple of 8 on the sublane axis and of 128 on
    the lane axes), divide every dim that has such a divisor, and fit
    the VMEM budget."""
    bm, bn, bk = int8_gemm_blocks(M, N, K)
    assert bm == M or bm % 8 == 0
    assert bn == N or bn % 128 == 0
    assert bk == K or bk % 128 == 0
    assert M % bm == 0 and N % bn == 0 and K % bk == 0
    from repro.core.tpu_adapter import VMEM_BUDGET
    assert int8_gemm_vmem_bytes(bm, bn, bk) <= VMEM_BUDGET


def test_int8_gemm_table_shape_classes():
    """Decode GEMVs take the small-M entries (whole M resident, deep
    weight tile); prefill-scale GEMMs take the balanced entry."""
    bm, bn, bk = int8_gemm_blocks(8, 512, 1024)
    assert bm == 8 and bk > bn >= 256            # decode: K-deep tile
    bm2, _, _ = int8_gemm_blocks(4096, 4096, 4096)
    assert bm2 > 8                               # prefill: real M tiling
    report = autotune_report()
    assert {r["entry"] for r in report} <= \
        {name for name, _, _ in INT8_GEMM_TABLE} | {None}
    assert all(r["grid_steps"] >= 1 for r in report)


def test_int8_gemm_blocks_fallback_on_tiny_budget():
    """A budget the pinned entry cannot fit falls back to the analytic
    choose_blocks answer (never an illegal config)."""
    from repro.core.tpu_adapter import choose_blocks
    tiny = 64 * 1024
    assert int8_gemm_blocks(256, 512, 512, vmem=tiny) == \
        choose_blocks(256, 512, 512, vmem=tiny)


def test_int8_matmul_autotuned_matches_reference():
    """ops.int8_matmul with table-chosen blocks == the canonical
    dequantized matmul (same gate the fixed-256 config passed)."""
    from repro.kernels import ops
    q, s = _quantized(jax.random.PRNGKey(8), 256, 128)
    x = jax.random.normal(jax.random.PRNGKey(9), (8, 256), jnp.float32)
    got = np.asarray(ops.int8_matmul(x, q, s))
    ref = np.asarray(x @ dequantize_weight(q, s))
    np.testing.assert_allclose(got, ref, atol=1e-4, rtol=1e-4)


def test_sweep_block_rows_ladder():
    """Planner-sized batches take one grid step; the choice always comes
    from the ladder; a starved budget degrades to the smallest entry."""
    n_fields, n_out = 40, 11
    for n_rows in (100, 1024, 5000, 8192):
        blk = sweep_block_rows(n_rows, n_fields, n_out)
        assert blk in SWEEP_ROW_LADDER
        if blk < max(SWEEP_ROW_LADDER):
            assert blk >= min(n_rows, blk)       # ladder-legal cap
    assert sweep_block_rows(5000, n_fields, n_out) >= 5000  # single step
    assert sweep_block_rows(10 ** 6, n_fields, n_out,
                            vmem=1) == SWEEP_ROW_LADDER[0]


# --- the layer scan carries the stacked cache --------------------------------

# The batch step is one program, the layer loop runs op by op: float32
# sums may be reassociated (a few ulps), and where such a difference
# straddles a rounding edge a bfloat16 cache entry moves by one ulp
# (2**-8 of its magnitude) and an int8 one by one quantization step.
LOOP_TOL = {"float32": dict(rtol=1e-5, atol=1e-5),
            "bfloat16": dict(rtol=2 ** -7, atol=1e-5),
            "int8": dict(rtol=0, atol=1)}


def _loop_step(cfg, rc, params, cache, tokens, pos, active, tables):
    """A plain Python loop over the layers: each layer's weights and
    cache taken with `unstack_tree`, every cache write a row-by-row
    `.at[].set` on that layer's cache by host indices (active slots
    only), and the layer's cache put back into the stack.  A slot at
    position 0 reads a zero SSM state and conv carry."""
    from repro.models.attention import decode_attend
    from repro.models.layers import (apply_rope, attn_out_proj, dtype_of,
                                     qkv_proj, rmsnorm, unstack_tree)
    from repro.models.mamba2 import mamba_apply
    from repro.models.model import (_apply_ffn, _cross_q_proj,
                                    _dequantize_kv, _lm_logits,
                                    _quantize_kv, n_periods, period_slots)
    nh, kvh, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim()
    b = tokens.shape[0]
    live = [s for s in range(b) if active[s]]
    x = params["embed"][tokens].astype(dtype_of(cfg.compute_dtype))
    out = [dict(c) for c in cache]
    for i in range(n_periods(cfg)):
        for si, slot in enumerate(period_slots(cfg)):
            sp = unstack_tree(params["slots"][si], i)
            layer = unstack_tree(cache[si], i)
            h = rmsnorm(sp["norm1"], x, cfg.rmsnorm_eps)
            if slot.mixer == "mamba":
                read = dict(layer)
                for n in ("state", "conv"):
                    for s in range(b):
                        if pos[s] == 0:
                            read[n] = read[n].at[s].set(0)
                y, (st, cv) = mamba_apply(
                    sp["mamba"], h, cfg, state=read["state"],
                    conv_carry=read["conv"], decode=True)
                for n, new in (("state", st), ("conv", cv)):
                    for s in live:
                        layer[n] = layer[n].at[s].set(
                            new[s].astype(layer[n].dtype))
            elif slot.mixer == "cross":
                q = _cross_q_proj(sp, h, b, 1, nh, dh)
                o = decode_attend(q, layer["k"], layer["v"],
                                  jnp.full((b,), layer["k"].shape[1]))
                y = attn_out_proj(sp["attn"], o.reshape(b, 1, nh * dh),
                                  label="xattn-out")
            else:
                q, k, v = qkv_proj(sp["attn"], h, nh, kvh, dh)
                q = apply_rope(q, jnp.asarray(pos)[:, None], cfg.rope_theta)
                k = apply_rope(k, jnp.asarray(pos)[:, None], cfg.rope_theta)
                if "k_scale" in layer:
                    (kq, ks), (vq, vs) = _quantize_kv(k), _quantize_kv(v)
                    rows = {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}
                else:
                    rows = {"k": k, "v": v}
                bs = layer["k"].shape[1]
                for n, r in rows.items():
                    for s in live:
                        layer[n] = layer[n].at[
                            tables[s, pos[s] // bs], pos[s] % bs].set(
                                r[s, 0].astype(layer[n].dtype))
                strip = {n: layer[n][tables].reshape(
                    (b, -1) + layer[n].shape[2:]) for n in layer}
                kd, vd = strip["k"], strip["v"]
                if "k_scale" in layer:
                    kd = _dequantize_kv(kd, strip["k_scale"], q.dtype)
                    vd = _dequantize_kv(vd, strip["v_scale"], q.dtype)
                o = decode_attend(q, kd, vd, jnp.asarray(pos) + 1)
                y = attn_out_proj(sp["attn"], o.reshape(b, 1, nh * dh))
            for n in layer:
                out[si][n] = out[si][n].at[i].set(layer[n])
            x = x + y
            x, _ = _apply_ffn(slot, sp, x, cfg)
    x = rmsnorm(params["final_norm"], x, cfg.rmsnorm_eps)
    return _lm_logits(params, x, cfg), out


@pytest.mark.parametrize("arch,kv", [
    ("mamba2-780m", "bfloat16"),
    ("mistral-nemo-12b", "bfloat16"),
    ("mistral-nemo-12b", "int8"),
    ("jamba-1.5-large-398b", "bfloat16"),
    ("llama-3.2-vision-90b", "bfloat16"),
])
def test_batch_step_updates_stacked_cache_like_a_layer_loop(arch, kv):
    """The batch step, with the stacked cache carried through its layer
    scan and written in place, against a plain loop over the layers, for
    every mixer kind (SSM state, paged KV in bf16 and int8, hybrid
    periods, read-only cross-attention KV): the same logits and cache
    over several steps of ragged positions and a changing active mask.
    With and without donation the results are bit-exact, and inactive
    slots' state rows and every KV block no active slot writes keep
    their bits."""
    from repro.models import init_paged_cache
    cfg = dataclasses.replace(reduced(ARCHS[arch]), param_dtype="float32",
                              compute_dtype="float32")
    rc = RunConfig(remat=False, attn_impl="naive", kv_cache_dtype=kv)
    params = init(jax.random.PRNGKey(0), cfg)
    b, bs, max_blocks = 4, 4, 4
    n_blocks = b * max_blocks + 2
    n_img = (cfg.vision.n_image_tokens if cfg.vision else 0)
    shapes = jax.eval_shape(lambda: init_paged_cache(
        cfg, rc, b, n_blocks, bs, n_image_tokens=n_img))
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 64))

    def fill(a):
        if a.dtype == jnp.int8:
            return jax.random.randint(next(keys), a.shape, -127, 128,
                                      jnp.int8)
        return jax.random.normal(next(keys), a.shape).astype(a.dtype)
    cache0 = jax.tree.map(fill, shapes)
    rng = np.random.default_rng(2)
    tables = rng.permutation(n_blocks)[:b * max_blocks].reshape(
        b, max_blocks).astype(np.int32)
    pos = np.array([3, 9, 0, 13], np.int32)
    masks = [np.array(m) for m in ([1, 1, 0, 1], [1, 0, 1, 1],
                                   [0, 1, 1, 0])]
    cores = {d: DecodeCore(cfg, rc, params, donate=d) for d in (True, False)}
    caches = {d: jax.tree.map(jnp.copy, cache0) for d in cores}
    ref = cache0
    for step, active in enumerate(masks):
        active = active.astype(bool)
        tokens = jnp.asarray(rng.integers(0, cfg.vocab, (b, 1)), jnp.int32)
        logits = {}
        for d, core in cores.items():
            logits[d], caches[d] = core.batch_step(
                params, caches[d], tokens, jnp.asarray(pos),
                jnp.asarray(active), jnp.asarray(tables))
        want, new_ref = _loop_step(cfg, rc, params, ref, tokens, pos,
                                   active, tables)
        np.testing.assert_array_equal(np.asarray(logits[True]),
                                      np.asarray(logits[False]))
        for got, exp in zip(jax.tree.leaves(caches[True]),
                            jax.tree.leaves(caches[False])):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(exp))
        np.testing.assert_allclose(np.asarray(logits[False]),
                                   np.asarray(want), **LOOP_TOL["float32"])
        written = {int(tables[s, pos[s] // bs]) for s in range(b)
                   if active[s]}
        for si, entry in enumerate(caches[False]):
            for n, got in entry.items():
                got, old = np.asarray(got), np.asarray(ref[si][n])
                np.testing.assert_allclose(
                    got.astype(np.float32),
                    np.asarray(new_ref[si][n]).astype(np.float32),
                    err_msg=f"{si} {n}", **LOOP_TOL[got.dtype.name])
                if n in ("state", "conv"):
                    np.testing.assert_array_equal(got[:, ~active],
                                                  old[:, ~active])
                elif got.shape[1] == n_blocks:
                    kept = [k for k in range(n_blocks) if k not in written]
                    np.testing.assert_array_equal(got[:, kept],
                                                  old[:, kept])
                else:
                    np.testing.assert_array_equal(got, old)
        ref = caches[False]
        pos = np.where(active, pos + 1, pos)


# --- a lane at position 0 starts from a zero SSM state -----------------------

@pytest.mark.parametrize("arch_fixture", ["mamba", "hybrid"])
@pytest.mark.parametrize("ragged", [True, False])
def test_fresh_lane_state_is_zeroed_in_the_step(arch_fixture, ragged,
                                                request):
    """The step zeroes the SSM state and conv carry of a lane at
    position 0, whatever its cache rows hold (garbage, NaN included):
    logits and the whole updated cache equal those of the same call with
    that lane's rows zeroed, bit for bit.  Lanes at a later position
    read their own state.  Ragged positions (the engine's batch step) and
    a uniform position (the fixed-batch step, where every lane is at 0)."""
    from repro.models import init_paged_cache
    cfg, _, core = request.getfixturevalue(arch_fixture)
    b, bs, max_blocks = 4, BLOCK, 4
    keys = iter(jax.random.split(jax.random.PRNGKey(5), 32))

    def fill(a):
        return jax.random.normal(next(keys), a.shape).astype(a.dtype)
    tokens = jax.random.randint(next(keys), (b, 1), 0, cfg.vocab)
    if ragged:
        pos = jnp.array([0, 3, 7, 1], jnp.int32)
        tables = jnp.arange(b * max_blocks, dtype=jnp.int32).reshape(
            b, max_blocks)
        cache = jax.tree.map(fill, init_paged_cache(
            cfg, RC, b, b * max_blocks, bs))

        def step(c):
            return core.batch_step(core.params, c, tokens, pos,
                                   jnp.ones(b, bool), tables)
    else:
        pos = jnp.int32(0)
        cache = jax.tree.map(fill, init_cache(cfg, RC, b, MAX_LEN))

        def step(c):
            return core.step(c, tokens, pos)
    fresh = np.broadcast_to(np.asarray(pos) == 0, (b,))

    def with_rows(c, lanes, value):
        m = jnp.asarray(lanes)
        return [{n: (jnp.where(m.reshape((1, b) + (1,) * (a.ndim - 2)),
                               jnp.asarray(value, a.dtype), a)
                     if n in ("state", "conv") else a)
                 for n, a in e.items()} for e in c]
    garbage = with_rows(cache, np.arange(b) == 0, np.nan)
    zeroed = with_rows(cache, fresh, 0.0)
    lg, cg = step(garbage)
    lz, cz = step(zeroed)
    assert np.all(np.isfinite(np.asarray(lg, np.float32)))
    np.testing.assert_array_equal(np.asarray(lg), np.asarray(lz))
    for got, want in zip(jax.tree.leaves(cg), jax.tree.leaves(cz)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    if ragged:
        # lanes 1-3 read their state: zeroing it changes their logits
        lo, _ = step(with_rows(cache, np.ones(b, bool), 0.0))
        lo, lz = np.asarray(lo, np.float32), np.asarray(lz, np.float32)
        np.testing.assert_array_equal(lo[0], lz[0])
        for s in (1, 2, 3):
            assert np.max(np.abs(lo[s] - lz[s])) > 1e-3, s
