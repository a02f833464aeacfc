"""Planner-gated execution: KernelPlanTable routing, label coverage,
dtype discipline, and the end-to-end gated quantized decode.

The tentpole contract under test: What/When/Where verdicts become a
jit-static KernelPlanTable; every projection matmul in the model stack
routes through the single `models.layers.linear` entry point; with
quantize=True a ServeSession lowers CiM-gated labels to the INT8 Pallas
kernel and everything else to the standard path inside ONE compiled
decode executable, with logits parity against the ungated program.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, RunConfig, reduced
from repro.configs.base import ShapeConfig
from repro.core.llm_workloads import gemms_of_model, projection_labels
from repro.core.planner import plan_workload
from repro.models import decode_step, forward, init, init_cache
from repro.models.layers import route_trace
from repro.quant import (KernelPlanTable, planned_linear,
                         quantize_model_params, quantize_weight)
from repro.serving import ServeSession

RC = RunConfig(remat=False, attn_impl="naive")

# one arch per family: the coverage sweep must see every projection kind
COVERAGE_ARCHS = ("mistral-nemo-12b", "qwen2-moe-a2.7b", "mamba2-780m",
                  "jamba-1.5-large-398b", "llama-3.2-vision-90b",
                  "musicgen-large")


def _plan_table(cfg, batch, max_len=32):
    shape = ShapeConfig("serve", max_len, batch, "decode")
    decisions = plan_workload(gemms_of_model(cfg, shape),
                              backend="vectorized")
    return KernelPlanTable.from_decisions(decisions, model_name=cfg.name)


# --- KernelPlanTable: static, hashable, loud on drift ------------------------

def test_plan_table_hashable_and_jit_static():
    table = _plan_table(reduced(ARCHS["mistral-nemo-12b"]), batch=2)
    assert hash(table) == hash(table)
    assert table == table
    assert table != table.ungated() or not any(
        e.use_cim for _, e in table.entries)
    # usable as a jit static argument (the engine closes over it instead,
    # but staticness is the load-bearing property either way)
    @jax.jit
    def f(x):
        return x + sum(e.use_cim for _, e in table.entries)
    f(jnp.zeros(()))


def test_plan_table_unknown_label_raises_with_known_list():
    table = _plan_table(reduced(ARCHS["mistral-nemo-12b"]), batch=2)
    assert table.use_cim("Wq") in (True, False)
    with pytest.raises(KeyError, match="mlp-gate"):
        table.use_cim("Wq_renamed")


def test_serve_session_use_cim_for_unknown_label_raises():
    cfg = reduced(ARCHS["mistral-nemo-12b"])
    s = ServeSession(cfg, RC, init(jax.random.PRNGKey(0), cfg),
                     max_len=16, batch=2)
    # full and short labels both resolve
    assert s.use_cim_for(f"{cfg.name} Wq") == s.use_cim_for("Wq")
    with pytest.raises(KeyError, match="known"):
        s.use_cim_for("no-such-gemm")


# --- planned_linear dtype discipline ----------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("use_cim", [True, False])
def test_planned_linear_respects_input_dtype(dtype, use_cim):
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 64), dtype)
    w = jax.random.normal(jax.random.PRNGKey(1), (64, 128),
                          jnp.float32) * 0.05
    q, s = quantize_weight(w)
    y = planned_linear(x, q, s, use_cim_path=use_cim, interpret=True)
    assert y.dtype == x.dtype, (y.dtype, x.dtype)
    ref = x.astype(jnp.float32) @ w
    np.testing.assert_allclose(np.asarray(y, np.float32), np.asarray(ref),
                               rtol=0.1, atol=0.05)


def test_planned_linear_branch_parity_bf16():
    """Both branches in bfloat16 agree within kernel-numerics tolerance
    (the gated-decode parity gate in miniature)."""
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 64), jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(1), (64, 128),
                          jnp.float32) * 0.05
    q, s = quantize_weight(w)
    y_cim = planned_linear(x, q, s, use_cim_path=True, interpret=True)
    y_std = planned_linear(x, q, s, use_cim_path=False)
    np.testing.assert_allclose(
        np.asarray(y_cim, np.float32), np.asarray(y_std, np.float32),
        rtol=5e-2, atol=5e-2)


# --- label coverage: the model consumes exactly the planner's labels --------

@pytest.mark.parametrize("arch", COVERAGE_ARCHS)
def test_every_projection_label_has_exactly_one_linear_callsite(arch):
    """Every projection label emitted by gemms_of_model is consumed by
    the model stack through exactly one `linear(...)` call site (forward
    and decode share the projection helpers), and the model emits no
    label the planner doesn't know."""
    cfg = reduced(ARCHS[arch])
    b, l = 2, 8
    shape = ShapeConfig("serve", l, b, "decode")
    expected = projection_labels(cfg, shape)
    params = init(jax.random.PRNGKey(0), cfg)
    if cfg.family == "audio":
        tokens = jnp.zeros((b, l, cfg.audio.n_codebooks), jnp.int32)
        tok1 = jnp.zeros((b, 1, cfg.audio.n_codebooks), jnp.int32)
    else:
        tokens = jnp.zeros((b, l), jnp.int32)
        tok1 = jnp.zeros((b, 1), jnp.int32)
    kw = {}
    nimg = 0
    if cfg.family == "vlm":
        nimg = cfg.vision.n_image_tokens
        kw["image_embeds"] = jnp.zeros((b, nimg, cfg.d_model),
                                       jnp.bfloat16)
    cache = init_cache(cfg, RC, b, l, n_image_tokens=nimg)

    with route_trace() as records:
        jax.eval_shape(lambda p: forward(p, tokens, cfg, RC, **kw),
                       params)
        jax.eval_shape(
            lambda p, c: decode_step(p, c, tok1, jnp.int32(0), cfg, RC),
            params, cache)

    seen = {}
    for r in records:
        seen.setdefault(r["label"], set()).add(r["callsite"])
    assert set(seen) == expected, (
        f"label drift: model emits {sorted(set(seen) - expected)}, "
        f"misses {sorted(expected - set(seen))}")
    multi = {lab: sites for lab, sites in seen.items() if len(sites) > 1}
    assert not multi, f"labels with multiple linear call sites: {multi}"


# --- end-to-end gated decode ------------------------------------------------

def test_gated_decode_parity_and_single_executable():
    """Acceptance: with quantize=True the session routes at least one
    projection through the Pallas INT8 path and at least one through the
    standard path (verdict-dependent, mamba2 smoke at batch 8), matches
    the ungated program within kernel tolerance and the float program
    within INT8 tolerance, and compiles exactly one decode executable."""
    cfg = reduced(ARCHS["mamba2-780m"])
    params = init(jax.random.PRNGKey(1), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(2), (8, 5), 0,
                                cfg.vocab)
    gated = ServeSession(cfg, RC, params, max_len=16, batch=8,
                         quantize=True)

    routes = {lab: r["route"] for lab, r in gated.route_report().items()}
    assert "cim-int8-pallas" in routes.values(), routes
    assert "int8-dequant-xla" in routes.values(), routes

    ungated = ServeSession(cfg, RC, params, max_len=16, batch=8,
                           quantize=True, gated=False)
    floats = ServeSession(cfg, RC, params, max_len=16, batch=8)

    lg = gated.prefill(prompt).astype(jnp.float32)
    lu = ungated.prefill(prompt).astype(jnp.float32)
    lf = floats.prefill(prompt).astype(jnp.float32)
    # routing parity: same INT8 weights, only the kernel differs
    np.testing.assert_allclose(np.asarray(lg), np.asarray(lu),
                               rtol=5e-2, atol=5e-2)
    # quantization parity: gated INT8 vs float within INT8 tolerance
    scale = float(jnp.max(jnp.abs(lf))) + 1e-6
    assert float(jnp.max(jnp.abs(lg - lf))) < 0.1 * scale + 0.05

    out_g = gated.generate(prompt[:, -1:], n_new=4)
    out_u = ungated.generate(prompt[:, -1:], n_new=4)
    np.testing.assert_array_equal(np.asarray(out_g), np.asarray(out_u))
    # one lowered program: prefill + every decode token hit the same
    # executable (no per-token retrace)
    # (None only if the private jax jit-cache probe disappears)
    assert gated.decode_executables in (1, None)
    assert ungated.decode_executables in (1, None)


@pytest.mark.parametrize("arch", ["mistral-nemo-12b", "qwen2-moe-a2.7b"])
def test_gated_prefill_logits_within_parity_atol(arch):
    """The dense and MoE archs at decode batch 8: gated and ungated
    prefill logits agree within the serve CLI's PARITY_ATOL, and the
    gated session compiles one decode executable.  (mamba2, the
    mixed-verdict arch, is held to the same bound by the serve CLI's
    fixed-batch report test.)"""
    from repro.launch.serve import PARITY_ATOL
    cfg = reduced(ARCHS[arch])
    params = init(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (8, 6), 0,
                                cfg.vocab)
    gated = ServeSession(cfg, RC, params, max_len=24, batch=8,
                         quantize=True)
    ungated = ServeSession(cfg, RC, params, max_len=24, batch=8,
                           quantize=True, gated=False)
    lg = gated.prefill(prompt).astype(jnp.float32)
    lu = ungated.prefill(prompt).astype(jnp.float32)
    assert float(jnp.max(jnp.abs(lg - lu))) <= PARITY_ATOL
    assert gated.decode_executables in (1, None)


def test_gated_session_plan_built_before_jit():
    """quantize=True builds the plan eagerly; the table is frozen and the
    gated labels match the planner verdicts."""
    cfg = reduced(ARCHS["mamba2-780m"])
    s = ServeSession(cfg, RC, init(jax.random.PRNGKey(0), cfg),
                     max_len=16, batch=8, quantize=True)
    assert s._kernel_plan is not None       # no lazy build left pending
    assert s.plan_table is not None
    for lab, entry in s.plan_table.entries:
        assert entry.use_cim == s.use_cim_for(lab)


@pytest.mark.parametrize("arch", ["qwen2-moe-a2.7b", "musicgen-large"])
def test_quantized_families_generate(arch):
    """Quantized+gated generation stays finite and deterministic across
    the structurally distinct families (MoE expert einsums, audio
    multi-codebook head)."""
    cfg = reduced(ARCHS[arch])
    params = init(jax.random.PRNGKey(0), cfg)
    if cfg.family == "audio":
        prompt = jax.random.randint(
            jax.random.PRNGKey(1), (2, 4, cfg.audio.n_codebooks), 0,
            cfg.vocab)
    else:
        prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 4), 0,
                                    cfg.vocab)
    s1 = ServeSession(cfg, RC, params, max_len=16, batch=2, quantize=True)
    s2 = ServeSession(cfg, RC, params, max_len=16, batch=2, quantize=True)
    o1 = s1.generate(prompt, n_new=4)
    o2 = s2.generate(prompt, n_new=4)
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))
    assert s1.decode_executables in (1, None)


def test_quantize_model_params_structure():
    """Projection leaves become {"q", "scale"} with per-layer (stacked)
    scales; norms, biases, convs, router and embed stay float."""
    cfg = reduced(ARCHS["qwen2-moe-a2.7b"])
    params = init(jax.random.PRNGKey(0), cfg)
    qp = quantize_model_params(params)
    slot = qp["slots"][0]
    attn = slot["attn"]
    assert set(attn["wq"]) == {"q", "scale"}
    assert attn["wq"]["q"].dtype == jnp.int8
    # stacked leading layer axis survives with per-layer scales
    assert attn["wq"]["q"].shape[0] == attn["wq"]["scale"].shape[0]
    # MoE expert weights: (layers, E, d, f) with (layers, E, f) scales
    moe = slot["moe"]
    assert moe["w_gate"]["q"].ndim == 4
    assert moe["w_gate"]["scale"].shape == moe["w_gate"]["q"].shape[:2] \
        + (moe["w_gate"]["q"].shape[-1],)
    assert moe["router"].dtype == jnp.float32      # router stays float
    assert not isinstance(qp["embed"], dict)       # embedding gather
    assert not isinstance(slot["norm1"]["scale"], dict)
