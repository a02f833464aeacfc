"""The immutable compiled core of the serving stack.

`DecodeCore` owns everything that must be frozen *before* jitting and
then never changes while requests stream through: the model/run configs,
the (optionally INT8-quantized) parameters, the What/When/Where verdicts
as a jit-static `KernelPlanTable`, and the jitted decode executables.
The scheduler layer (repro.serving.scheduler) and the legacy fixed-batch
`ServeSession` (repro.serving.engine) are both thin mutable shells over
one core — requests join and leave, the core never retraces.

Two kinds of executables live here, each compiled exactly once per plan:

  * `step(params, cache, tokens, pos)` — the legacy fixed-batch step
    (scalar uniform position), what the dry-run lowers and ServeSession
    drives;
  * `batch_step(params, cache, tokens, pos, active, block_tables)` — the
    continuous-batching step: ragged per-slot positions, an active-slot
    mask, and a paged KV block pool (models.model.init_paged_cache).
    All four scheduler-side inputs are jit-*dynamic*, so slot churn under
    live traffic hits the same compiled program every step.

The continuous-batching step is served from a **bounded per-plan
executable cache** (`batch_step_for(plan)`): each distinct (versioned)
`KernelPlanTable` gets its own jitted program, LRU-bounded at
`max_plan_variants`.  That is what lets the adaptive serving layer
(`repro.serving.scheduler` + `repro.core.plan_service`) hot-swap the
decode plan when a shape bucket's verdict flips — a flip compiles the
new variant once, off the critical decode step, and every later step
under either plan reuses its already-compiled program
(`batch_decode_executables == number of distinct plans served`).
"""
from __future__ import annotations

import dataclasses
import threading
from collections import OrderedDict
from typing import Any

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig, RunConfig
from ..models import decode_step
from ..models.layers import route_trace
from ..quant import (KernelPlanTable, quantize_model_params_lowbit,
                     strip_model_prefix)


def _token_struct(cfg: ModelConfig, batch: int):
    shape = (batch, 1) + ((cfg.audio.n_codebooks,)
                          if cfg.family == "audio" else ())
    return jax.ShapeDtypeStruct(shape, jnp.int32)


def sample_token(cfg: ModelConfig, logits, temperature: float, key):
    """Greedy / temperature sampling of the next token from step logits.

    One definition shared by the fixed-batch session and the continuous
    engine, so the two paths cannot drift.  Returns tokens shaped for
    feeding back into the decode step ((b, 1), audio: (b, 1, nb))."""
    last = logits[:, -1]
    if temperature <= 0.0:
        tok = jnp.argmax(last, axis=-1)
    else:
        tok = jax.random.categorical(key, last / temperature)
    if cfg.family == "audio":
        return tok[:, None, :] if tok.ndim == 2 else tok[:, None]
    return tok[:, None].astype(jnp.int32)


@dataclasses.dataclass
class DecodeCore:
    """Frozen compiled core: params + plan + the jitted decode programs.

    quantize=True turns the planner verdicts into the execution policy:
    projection weights are INT8-quantized at init, the kernel plan is
    built eagerly (before jitting), and both jitted steps close over the
    static KernelPlanTable.  gated=False keeps the quantized weights but
    forces every label onto the standard path — the parity baseline for
    the gated program (identical numerics source, routing the only
    difference)."""
    cfg: ModelConfig
    rc: RunConfig
    params: Any
    quantize: bool = False
    gated: bool = True
    # weight precision of the quantized execution path (the What axis at
    # runtime): "int8" (default), "int4" (packed nibbles) or "fp8"
    # (e4m3 scaled) — models.layers.linear dispatches each format to its
    # own CiM-Pallas / dequant-XLA route pair
    precision: str = "int8"
    # decode shape the planner reasons about (batch is what matters for
    # the paper's M=1 pathology; ServeSession passes its own)
    plan_batch: int = 8
    plan_max_len: int = 1024
    # bound on concurrently-cached jitted batch-step variants (one per
    # distinct plan table the adaptive layer has served)
    max_plan_variants: int = 4
    # donate the cache argument of both jitted steps so XLA aliases the
    # KV pools / mamba state into the outputs (in-place update, no
    # per-token copy of the multi-MB cache).  None resolves per
    # platform: on accelerators aliasing is the point; on CPU the
    # aliased program measured ~20% SLOWER (XLA:CPU), so it defaults
    # off there.  Tests force donate=True to prove the in-place
    # semantics regardless of platform.
    donate: bool | None = None

    def __post_init__(self):
        if self.max_plan_variants < 1:
            raise ValueError(f"max_plan_variants must be >= 1, "
                             f"got {self.max_plan_variants}")
        self._kernel_plan = None
        self._kernel_plans = None
        self._plan_cache_telemetry = None
        self._plan_lock = threading.Lock()
        self._verdict_table = None
        self._phase_verdict_tables = None
        self._batch_steps: OrderedDict = OrderedDict()
        self._exec_lock = threading.Lock()
        self.plan_evictions = 0
        self.plan_table = None
        self.prefill_plan_table = None
        if self.quantize:
            # plan BEFORE jit: the verdicts are static inputs of the
            # lowered decode/prefill programs, not runtime state.  Each
            # serving phase gets its *own* table (planner
            # plan_workload_by_phase): prefill GEMMs carry M = seq_len
            # reuse, decode GEMMs collapse to M = batch, so their
            # What/When verdicts legitimately differ.
            tables = self.phase_verdict_tables
            table, ptable = tables["decode"], tables["prefill"]
            self.plan_table = table if self.gated else table.ungated()
            pgate = ptable if self.gated else ptable.ungated()
            # when the phases gate every *projection* identically, the
            # lowered programs would be identical — alias the execution
            # table so the phases share ONE compiled step.  Activation
            # GEMMs (QK^T / pV scores) have no stationary weight and
            # never consult the table, so their phase-specific labels
            # must not force a redundant second program.
            from ..core.llm_workloads import is_projection_label
            proj_flips = [lab for lab in self.plan_table.flips(pgate)
                          if is_projection_label(lab)]
            self.prefill_plan_table = (pgate if proj_flips
                                       else self.plan_table)
            self.params = quantize_model_params_lowbit(self.params,
                                                       self.precision)
        if self.donate is None:
            self.donate = jax.default_backend() != "cpu"
        cfg, rc, plan = self.cfg, self.rc, self.plan_table
        # when donating, the cache argument is consumed: XLA aliases the
        # input KV pools / mamba state to the output and updates them in
        # place instead of copying the multi-MB cache every token.
        # Callers must rebind (`logits, cache = step(params, cache,
        # ...)`) and never touch the donated input again — every in-repo
        # caller does.
        # named functions: the programs are "jit_serve_step",
        # "jit_serve_prefill_step" and "jit_serve_batch_step" in HLO dumps
        # and profiler traces
        def serve_step(params, cache, tokens, pos, _plan=plan):
            return decode_step(params, cache, tokens, pos, cfg, rc,
                               plan=_plan)

        self._step = jax.jit(serve_step,
                             donate_argnums=(1,) if self.donate else ())
        # the prefill-phase step: same per-token decode fn closed over
        # the prefill table.  When the phases agree (or the core is
        # unquantized/ungated: both plans identical) the decode program
        # is shared — one executable per *distinct* phase plan, never a
        # retrace.
        pplan = self.prefill_plan_table
        if pplan == plan:
            self._prefill_step = self._step
        else:
            def serve_prefill_step(params, cache, tokens, pos):
                return serve_step(params, cache, tokens, pos, _plan=pplan)

            self._prefill_step = jax.jit(
                serve_prefill_step,
                donate_argnums=(1,) if self.donate else ())

    # --- planner plumbing (the session-level API, now core-owned) ------

    @property
    def kernel_plan(self) -> dict:
        """label -> planner Decision for this core's decode GEMMs.

        Computed lazily on first access through the batched sweep planner
        (plan_workload, backend="vectorized"); the sweep engine's LRU
        cache makes repeat cores over the same shapes free.  The build is
        locked per core: concurrent first accesses must not double-build
        (the second build would be all-hits and overwrite the real
        telemetry)."""
        if self._kernel_plan is None:
            with self._plan_lock:
                if self._kernel_plan is None:
                    self._build_kernel_plan()
        return self._kernel_plan

    def _build_kernel_plan(self) -> None:
        from ..core.llm_workloads import phase_gemms_of_model
        from ..core.planner import plan_workload_by_phase
        from ..core.sweep import measured_cache_delta
        # plan BOTH serving phases: decode GEMMs at M = plan_batch (the
        # paper's M=1 pathology, batched) and prefill GEMMs at
        # M = plan_max_len.  One batched sweep per phase; the sweep
        # engine's LRU makes repeat cores over the same shapes free.
        phases = phase_gemms_of_model(self.cfg, self.plan_max_len,
                                      self.plan_batch)
        by_phase, self._plan_cache_telemetry = measured_cache_delta(
            lambda: plan_workload_by_phase(phases, backend="vectorized"))
        self._kernel_plans = {ph: {d.gemm.label: d for d in ds}
                              for ph, ds in by_phase.items()}
        self._kernel_plan = self._kernel_plans["decode"]

    @property
    def plan_cache_telemetry(self) -> dict:
        """sweep.cache_info() telemetry of this core's kernel_plan build
        (triggers the build on first access): how many of the GEMM
        verdicts were served from the process-wide LRU vs freshly
        evaluated, plus the engine-wide counters (streaming-chunk
        accounting and, on a multi-host mesh, per-process shard
        balance)."""
        _ = self.kernel_plan
        return self._plan_cache_telemetry

    @property
    def kernel_plans(self) -> dict:
        """phase -> {label -> Decision} for both serving phases
        ("prefill" / "decode"); triggers the lazy per-phase plan build."""
        _ = self.kernel_plan
        return self._kernel_plans

    @property
    def phase_verdict_tables(self) -> dict[str, KernelPlanTable]:
        """phase -> raw-verdict KernelPlanTable for both serving phases.
        Never force-ungated; exists for non-quantized cores too (lazy
        plan build)."""
        if self._phase_verdict_tables is None:
            self._phase_verdict_tables = {
                ph: KernelPlanTable.from_decisions(
                    plan.values(), model_name=self.cfg.name)
                for ph, plan in self.kernel_plans.items()}
        return self._phase_verdict_tables

    @property
    def verdict_table(self) -> KernelPlanTable:
        """The decode-phase raw verdicts as a KernelPlanTable (short
        labels).  Unlike `plan_table` it is never force-ungated, and it
        exists for non-quantized cores too (lazy plan build)."""
        if self._verdict_table is None:
            self._verdict_table = self.phase_verdict_tables["decode"]
        return self._verdict_table

    def use_cim_for(self, label: str) -> bool:
        """The planner's "when" gate for one GEMM (feeds
        repro.quant.planned_linear's use_cim_path).  Accepts full
        ("<model> Wq") or short ("Wq") labels; unknown labels raise
        KeyError with the known-label list (the KernelPlanTable
        contract) — model-side label drift must not silently disable
        gating."""
        return self.verdict_table.use_cim(
            strip_model_prefix(label, self.cfg.name))

    # --- the two compiled programs -------------------------------------

    def step(self, cache, tokens, pos):
        """Legacy fixed-batch decode step (uniform scalar position)."""
        return self._step(self.params, cache, tokens, pos)

    def prefill_step(self, cache, tokens, pos):
        """The prefill-phase per-token step: the same decode fn closed
        over the *prefill* plan table (shared program when the phase
        plans coincide)."""
        return self._prefill_step(self.params, cache, tokens, pos)

    def batch_step_for(self, plan):
        """The continuous-batching executable for one (versioned) plan
        table: (params, cache, tokens, pos_vec, active, block_tables) ->
        (logits, cache).  pos_vec (b,) int32, active (b,) bool and
        block_tables (b, max_blocks) int32 are dynamic — join/evict/
        ragged lengths never retrace.

        Variants are memoized per plan table (the table's hash/equality
        is its version) in an LRU bounded by `max_plan_variants`: an
        adaptive engine swapping between plans reuses each variant's
        single compiled program; a plan evicted from the bound recompiles
        if it ever returns (`plan_evictions` counts those drops)."""
        with self._exec_lock:
            fn = self._batch_steps.get(plan)
            if fn is None:
                cfg, rc = self.cfg, self.rc
                # cache donated like `_step` (same platform gate): the
                # paged KV block pools, int8-kv scale pools and per-slot
                # mamba state update in place across steps (no per-token
                # pool copy)
                def serve_batch_step(params, cache, tokens, pos, active,
                                     block_tables, _plan=plan):
                    return decode_step(params, cache, tokens, pos, cfg, rc,
                                       plan=_plan, active=active,
                                       block_tables=block_tables)

                fn = jax.jit(serve_batch_step,
                             donate_argnums=(1,) if self.donate else ())
                self._batch_steps[plan] = fn
            self._batch_steps.move_to_end(plan)
            while len(self._batch_steps) > self.max_plan_variants:
                self._batch_steps.popitem(last=False)
                self.plan_evictions += 1
        return fn

    @property
    def batch_step(self):
        """The continuous-batching executable for this core's own frozen
        plan table (the non-adaptive path) — see `batch_step_for`."""
        return self.batch_step_for(self.plan_table)

    @property
    def plan_variants(self) -> int:
        """Distinct plan tables with a live jitted batch-step variant."""
        with self._exec_lock:
            return len(self._batch_steps)

    @staticmethod
    def _executables(fn) -> int | None:
        probe = getattr(fn, "_cache_size", None)
        return probe() if probe is not None else None

    @property
    def decode_executables(self) -> int | None:
        """Programs compiled by the fixed-batch step (no-retrace gate:
        exactly 1 after any traffic).  None if the private jax jit-cache
        probe is unavailable."""
        return self._executables(self._step)

    @property
    def prefill_executables(self) -> int | None:
        """Programs compiled by the prefill-phase step (no-retrace gate:
        exactly 1 after any traffic; when the phase plans coincide this
        is the decode step's own count — one shared program).  None if
        the private jax jit-cache probe is unavailable."""
        return self._executables(self._prefill_step)

    def plan_executables(self, plan) -> int | None:
        """Programs compiled for one plan table's batch-step variant (0
        when that variant never ran).  None if the private jax jit-cache
        probe is unavailable."""
        with self._exec_lock:
            fn = self._batch_steps.get(plan)
        return 0 if fn is None else self._executables(fn)

    @property
    def batch_decode_executables(self) -> int | None:
        """Total programs compiled across every cached batch-step variant
        — the no-retrace gate: equals 1 for frozen-plan traffic and the
        number of distinct plan tables for adaptive traffic (each variant
        compiles exactly once).  None if the private jax jit-cache probe
        is unavailable."""
        with self._exec_lock:
            fns = list(self._batch_steps.values())
        if not fns:
            return 0
        counts = [self._executables(f) for f in fns]
        if any(c is None for c in counts):
            return None
        return sum(counts)

    def route_report(self, batch: int, max_len: int,
                     n_image_tokens: int = 0) -> dict:
        """label -> {route, use_cim, what, where, shapes} as actually
        lowered by the jitted decode step (abstract trace, no compute);
        "shapes" lists the executed (M, N, K) of each plain projection
        call under the label."""
        from ..models import init_cache
        cache = jax.eval_shape(
            lambda: init_cache(self.cfg, self.rc, batch, max_len,
                               n_image_tokens=n_image_tokens))
        cfg, rc, plan = self.cfg, self.rc, self.plan_table
        with route_trace() as records:
            jax.eval_shape(
                lambda p, c, t, i: decode_step(p, c, t, i, cfg, rc,
                                               plan=plan),
                self.params, cache, _token_struct(cfg, batch),
                jax.ShapeDtypeStruct((), jnp.int32))
        report = {}
        for r in records:
            entry = (self.plan_table.entry(r["label"])
                     if self.plan_table is not None else None)
            shapes = report.get(r["label"], {}).get("shapes", [])
            if r["shape"] is not None and list(r["shape"]) not in shapes:
                shapes = shapes + [list(r["shape"])]
            report[r["label"]] = {
                "route": r["route"],
                "use_cim": entry.use_cim if entry else False,
                "what": entry.what if entry else "baseline",
                "where": entry.where if entry else "PE",
                "shapes": shapes}
        return report
