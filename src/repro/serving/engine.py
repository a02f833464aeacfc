"""Serving engine: prefill + batched decode with KV caches.

`make_serve_step` builds the jit/pjit-able single-token decode step that
the multi-pod dry-run lowers for decode_32k / long_500k shapes.  The
serving stack splits into two layers on top of it:

  * `DecodeCore` (repro.serving.core) — the immutable compiled core:
    params, jit-static KernelPlanTable, and the jitted decode
    executables, frozen before any traffic;
  * the mutable request layers — the legacy fixed-batch `ServeSession`
    below (one cache, one uniform position, greedy/temperature
    sampling), and the slot-scheduled `ContinuousBatchingEngine`
    (repro.serving.scheduler) for ragged request streams.

Kernel gating: `ServeSession.kernel_plan` runs the What/When/Where
planner (batched sweep backend — repro.core.sweep, one fused device call,
LRU-cached so every session serving the same model shape reuses the
verdicts) over this session's decode GEMMs.  With `quantize=True` the
verdicts become the execution policy: the plan is built *before* jitting,
frozen into a jit-static `KernelPlanTable`, and the jitted decode step
closes over it — gated projection labels lower to the weight-stationary
INT8 Pallas kernel (repro.quant.planned_linear), ungated ones to the
standard XLA matmul, all inside ONE compiled executable (prefill runs the
same per-token step, so prefill and decode share the gate and nothing
retraces after the first step).  `use_cim_for(label)` exposes the
per-GEMM gate; `route_report()` traces the step abstractly and reports
the route each label actually lowered to.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig, RunConfig
from ..models import decode_step, forward, init_cache
from ..models.layers import CIM_ROUTE
from ..quant import KernelPlanTable
from .core import DecodeCore, _token_struct, sample_token


def make_serve_step(cfg: ModelConfig, rc: RunConfig,
                    plan: KernelPlanTable | None = None) -> Callable:
    """(params, cache, tokens, pos) -> (logits, cache) — one decode step.

    This is exactly the fn the dry-run lowers for decode shapes: one new
    token against a seq_len-deep KV cache.  `plan` (jit-static) gates
    quantized projections through the INT8 Pallas path per label.
    """
    def step(params, cache, tokens, pos):
        return decode_step(params, cache, tokens, pos, cfg, rc, plan=plan)
    return step


def make_prefill(cfg: ModelConfig, rc: RunConfig,
                 plan: KernelPlanTable | None = None) -> Callable:
    """(params, tokens[, image_embeds]) -> logits — the prefill forward.

    Fills no cache inline (cache writes for prefill re-run the per-token
    decode path in `prefill_into_cache`); used for the prefill_32k shape
    where only the forward matters for lowering.  Pass the *prefill*
    phase's plan table (DecodeCore.prefill_plan_table): each serving
    phase is gated by its own What/When/Where verdicts."""
    def run(params, tokens, image_embeds=None):
        logits, _ = forward(params, tokens, cfg, rc,
                            image_embeds=image_embeds, plan=plan)
        return logits
    return run


def cim_fraction(routes: dict) -> float:
    """Fraction of traced projection routes that lowered to the CiM
    INT8 Pallas path (shared by the serve CLI's two modes and the dry-run
    decode cells — one definition, one meaning)."""
    vals = [r["route"] if isinstance(r, dict) else r
            for r in routes.values()]
    return sum(v == CIM_ROUTE for v in vals) / max(1, len(vals))


def decode_routes(cfg: ModelConfig, rc: RunConfig, plan: KernelPlanTable,
                  batch: int, max_len: int,
                  n_image_tokens: int = 0) -> dict:
    """label -> executed route of the plan-gated decode step.

    Builds quantized params and cache *abstractly* (jax.eval_shape — no
    allocation, works for full production configs) and traces the step
    under `route_trace`; the result is exactly what the jitted program
    lowers, per projection label.  Used by the dry-run decode cells."""
    from ..models import init
    from ..models.layers import route_trace
    from ..quant import quantize_model_params
    step = make_serve_step(cfg, rc, plan)

    def run(key):
        params = quantize_model_params(init(key, cfg))
        cache = init_cache(cfg, rc, batch, max_len,
                          n_image_tokens=n_image_tokens)
        tok = jnp.zeros(_token_struct(cfg, batch).shape, jnp.int32)
        return step(params, cache, tok, jnp.int32(0))

    with route_trace() as records:
        jax.eval_shape(run, jax.random.PRNGKey(0))
    return {r["label"]: r["route"] for r in records}


@dataclasses.dataclass
class ServeSession:
    """Minimal fixed-batch serving session (greedy or temperature
    sampling): all `batch` lanes advance in lockstep at one uniform
    position over one contiguous KV cache.  For ragged request streams
    (per-request join/evict, paged KV) use
    repro.serving.ContinuousBatchingEngine over the same DecodeCore.

    quantize=True turns the planner verdicts into the execution policy:
    projection weights are INT8-quantized at init, the kernel plan is
    built eagerly (before jitting), and the jitted decode step closes
    over the static KernelPlanTable.  gated=False keeps the quantized
    weights but forces every label onto the standard path — the parity
    baseline for the gated program (identical numerics source, routing
    the only difference)."""
    cfg: ModelConfig
    rc: RunConfig
    params: Any
    max_len: int
    batch: int
    n_image_tokens: int = 0
    quantize: bool = False
    gated: bool = True
    # weight precision of the quantized path: "int8" / "int4" / "fp8"
    precision: str = "int8"

    def __post_init__(self):
        self.core = DecodeCore(self.cfg, self.rc, self.params,
                               quantize=self.quantize, gated=self.gated,
                               precision=self.precision,
                               plan_batch=self.batch,
                               plan_max_len=self.max_len)
        self.params = self.core.params       # quantized if quantize=True
        self.plan_table = self.core.plan_table
        self.prefill_plan_table = self.core.prefill_plan_table
        self._step = self.core._step
        self._prefill_step = self.core._prefill_step
        self.cache = init_cache(self.cfg, self.rc, self.batch,
                                self.max_len,
                                n_image_tokens=self.n_image_tokens)
        self.pos = 0

    # --- planner plumbing: delegated to the compiled core --------------

    @property
    def kernel_plan(self) -> dict:
        """label -> planner Decision for this session's decode GEMMs
        (lazy; LRU-cached across sessions — see DecodeCore.kernel_plan)."""
        return self.core.kernel_plan

    @property
    def _kernel_plan(self):
        return self.core._kernel_plan

    @property
    def plan_cache_telemetry(self) -> dict:
        """sweep.cache_info() telemetry of this session's kernel_plan
        build (triggers the build on first access) — see
        DecodeCore.plan_cache_telemetry."""
        return self.core.plan_cache_telemetry

    @property
    def verdict_table(self) -> KernelPlanTable:
        """This session's raw verdicts as a KernelPlanTable (short
        labels).  Unlike `plan_table` it is never force-ungated, and it
        exists for non-quantized sessions too (lazy plan build)."""
        return self.core.verdict_table

    def use_cim_for(self, label: str) -> bool:
        """The planner's "when" gate for one GEMM of this session —
        see DecodeCore.use_cim_for."""
        return self.core.use_cim_for(label)

    def route_report(self) -> dict:
        """label -> {route, use_cim, what, where} as actually lowered by
        this session's jitted decode step (abstract trace, no compute)."""
        return self.core.route_report(self.batch, self.max_len,
                                      self.n_image_tokens)

    @property
    def decode_executables(self) -> int | None:
        """How many programs the jitted decode step compiled (the
        no-retrace gate expects exactly 1 after any amount of traffic).
        None when the private jax jit-cache probe is unavailable."""
        return self.core.decode_executables

    @property
    def prefill_executables(self) -> int | None:
        """Programs compiled by the prefill-phase step — see
        DecodeCore.prefill_executables."""
        return self.core.prefill_executables

    @property
    def phase_verdict_tables(self) -> dict:
        """phase -> raw-verdict KernelPlanTable — see
        DecodeCore.phase_verdict_tables."""
        return self.core.phase_verdict_tables

    # --- request state --------------------------------------------------

    def reset(self) -> None:
        """Clear the KV cache and position for a fresh request; the
        compiled decode step (and its plan gate) is reused as-is."""
        self.cache = init_cache(self.cfg, self.rc, self.batch,
                                self.max_len,
                                n_image_tokens=self.n_image_tokens)
        self.pos = 0

    def prefill(self, tokens):
        """Feed a prompt token-by-token through the *prefill-phase* step
        — the same per-token program shape as decode, gated by the
        prefill plan table (one lowered program per phase; they share a
        program when the phase plans coincide)."""
        logits = None
        for t in range(tokens.shape[1]):
            tok = tokens[:, t:t + 1]
            logits, self.cache = self._prefill_step(
                self.params, self.cache, tok, jnp.int32(self.pos))
            self.pos += 1
        return logits

    def generate(self, prompt_tokens, n_new: int, temperature: float = 0.0,
                 seed: int = 0):
        logits = self.prefill(prompt_tokens)
        key = jax.random.PRNGKey(seed)
        out = []
        tok = self._sample(logits, temperature, key)
        for i in range(n_new):
            out.append(tok)
            logits, self.cache = self._step(self.params, self.cache, tok,
                                            jnp.int32(self.pos))
            self.pos += 1
            key, sub = jax.random.split(key)
            tok = self._sample(logits, temperature, sub)
        return jnp.concatenate(out, axis=1)

    def _sample(self, logits, temperature, key):
        return sample_token(self.cfg, logits, temperature, key)
