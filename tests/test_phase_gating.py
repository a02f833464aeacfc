"""Phase-split gating: per-phase plan tables, one executable per phase,
and the widened-precision (INT4/FP8) gated execution routes.

The serving stack plans prefill and decode as separate workloads
(planner.plan_workload_by_phase over llm_workloads.phase_gemms_of_model):
prefill GEMMs carry M = seq_len reuse while decode GEMMs collapse to
M = batch, so their What/When verdicts legitimately differ.  The
contracts under test:

  * a mixed-verdict architecture (mamba2's ssm-BCdt projection at
    batch 8 / seq 2048) really produces different prefill and decode
    verdict tables, and the core gates each phase by its own table;
  * each phase compiles exactly ONE executable — and when the phases
    gate every projection identically the execution tables are aliased,
    so both phases share one program instead of lowering a redundant
    second copy;
  * an empty phase workload raises instead of silently disabling gating
    (plan_workload_by_phase's zero-GEMM guard);
  * the plan is fixed when the core is built: `batch_step_for` keeps one
    jitted step per distinct phase table, every engine step is counted
    in exactly one phase, and an unquantized core (no tables) serves
    from one program with phase gating off;
  * `KernelPlanTable` equality/hash by content, `flips`, the
    KeyError-with-known-labels drift gate and `strip_model_prefix`;
  * the widened What axis at runtime: quantize=True sessions at
    precision="int4" / "fp8" route gated projections through the
    low-bit CiM Pallas paths and match the ungated program's logits —
    routing is the only difference, same quantized weights.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import ARCHS, RunConfig, reduced
from repro.core.llm_workloads import (is_projection_label,
                                      phase_gemms_of_model)
from repro.core.planner import plan_workload_by_phase
from repro.models import init
from repro.quant.plan_table import (KernelPlanTable, PlanEntry,
                                    strip_model_prefix)
from repro.serving import (ContinuousBatchingEngine, Request, ServeSession,
                           synthetic_requests)
from repro.serving.core import DecodeCore

RC = RunConfig(remat=False, attn_impl="naive")

# the mixed-verdict serving shape: at plan_batch=8 / plan_max_len=2048
# the reduced mamba2 ssm-BCdt projection gate flips between phases
# (prefill's M=2048 reuse earns CiM a different verdict than decode's
# M=8 GEMV) while e.g. batch 4 gates both phases identically.
MIXED_ARCH, MIXED_BATCH, MIXED_LEN = "mamba2-780m", 8, 2048
# one arch per served family: ssm, dense GQA, Mamba-2 + GQA + MoE hybrid
SERVED_ARCHS = ("mamba2-780m", "mistral-nemo-12b", "granite-4.0-h-small")


# --- per-phase planning ------------------------------------------------------

def test_phase_tables_differ_on_mixed_verdict_arch():
    """The two serving phases produce genuinely different verdict
    tables on the mixed arch, the flip is a *projection* label (a gate
    the runtime actually consults), and the quantized core wires each
    phase's execution table from its own verdicts."""
    cfg = reduced(ARCHS[MIXED_ARCH])
    core = DecodeCore(cfg, RC, None, plan_batch=MIXED_BATCH,
                      plan_max_len=MIXED_LEN)
    tables = core.phase_verdict_tables
    assert set(tables) == {"prefill", "decode"}
    flips = tables["decode"].flips(tables["prefill"])
    proj_flips = [lab for lab in flips if is_projection_label(lab)]
    assert "ssm-BCdt" in proj_flips, flips
    # verdict_table stays the decode phase's view
    assert core.verdict_table == tables["decode"]


def test_phase_gemms_of_model_shapes():
    """Prefill GEMMs carry M = seq_len, decode GEMMs M = batch — the
    structural asymmetry the per-phase verdicts come from."""
    cfg = ARCHS["mistral-nemo-12b"]
    phases = phase_gemms_of_model(cfg, 2048, 8)
    pre = {g.label: g for g in phases["prefill"]}
    dec = {g.label: g for g in phases["decode"]}
    assert pre[f"{cfg.name} Wq"].M == 2048
    assert dec[f"{cfg.name} Wq"].M == 8
    # same projection label set in both phases (activation-score labels
    # may legitimately differ per phase)
    pp = {l for l in pre if is_projection_label(l)}
    dp = {l for l in dec if is_projection_label(l)}
    assert pp == dp


def test_plan_workload_by_phase_empty_phase_raises():
    """A phase with zero eligible GEMMs must raise, not return an empty
    plan — an empty aggregate would silently ungate that phase."""
    cfg = ARCHS["mistral-nemo-12b"]
    phases = phase_gemms_of_model(cfg, 64, 2)
    with pytest.raises(ValueError, match="zero eligible GEMMs"):
        plan_workload_by_phase({**phases, "decode": []})
    with pytest.raises(ValueError, match="at least one phase"):
        plan_workload_by_phase({})


# --- one executable per phase ------------------------------------------------

def test_mixed_verdict_core_compiles_one_executable_per_phase():
    """On the mixed arch the phases gate differently -> two distinct
    plan tables, two programs — but each phase still compiles exactly
    once, no matter how much traffic runs through it."""
    cfg = reduced(ARCHS[MIXED_ARCH])
    params = init(jax.random.PRNGKey(1), cfg)
    s = ServeSession(cfg, RC, params, max_len=MIXED_LEN,
                     batch=MIXED_BATCH, quantize=True)
    assert s.prefill_plan_table != s.plan_table
    assert s.prefill_plan_table is not s.plan_table
    prompt = jax.random.randint(jax.random.PRNGKey(2),
                                (MIXED_BATCH, 6), 0, cfg.vocab)
    s.generate(prompt, n_new=4)
    s.reset()
    s.generate(prompt, n_new=3)
    # each phase's step traced exactly one program (None only if the
    # private jax jit-cache probe disappears)
    assert s.decode_executables in (1, None)
    assert s.prefill_executables in (1, None)
    # distinct programs: the phase steps are different jitted callables
    assert s._prefill_step is not s._step


def test_identical_phase_plans_share_one_program():
    """When no *projection* gate flips between phases the execution
    tables are aliased and both phases run the same compiled step —
    activation-score labels (phase-specific, never gated) must not
    force a redundant second program."""
    cfg = reduced(ARCHS[MIXED_ARCH])
    params = init(jax.random.PRNGKey(1), cfg)
    s = ServeSession(cfg, RC, params, max_len=64, batch=4, quantize=True)
    assert s.prefill_plan_table is s.plan_table
    assert s._prefill_step is s._step
    prompt = jax.random.randint(jax.random.PRNGKey(2), (4, 5), 0,
                                cfg.vocab)
    s.generate(prompt, n_new=4)
    assert s.decode_executables in (1, None)
    assert s.prefill_executables in (1, None)


def test_scheduler_switches_phase_tables_under_traffic():
    """The continuous-batching engine selects the prefill table on
    pure-prefill steps and flips back for decode, counting switches in
    telemetry; the total compiled variants stay at the number of
    distinct phase plans."""
    cfg = reduced(ARCHS[MIXED_ARCH])
    params = init(jax.random.PRNGKey(1), cfg)
    core = DecodeCore(cfg, RC, params, quantize=True,
                      plan_batch=MIXED_BATCH, plan_max_len=MIXED_LEN)
    assert core.prefill_plan_table != core.plan_table
    eng = ContinuousBatchingEngine(core, n_slots=4, max_len=32)
    prompts = np.arange(4 * 3, dtype=np.int32).reshape(4, 3) % cfg.vocab
    tel_all = eng.run([Request(rid=i, prompt=prompts[i],
                               max_new_tokens=4) for i in range(4)])
    tel = tel_all["aggregate"]["phase_gating"]
    assert tel["enabled"] is True
    assert tel["phase_switches"] >= 1        # prefill -> decode at least
    assert tel["phase_steps"]["prefill"] >= 1
    assert tel["phase_steps"]["decode"] >= 1
    assert (tel["phase_steps"]["prefill"] + tel["phase_steps"]["decode"]
            == eng.steps)
    # one compiled batch-step per distinct phase plan, nothing more
    assert core.batch_decode_executables in (2, None)


# --- one plan per phase, fixed when the core is built ------------------------

def _served_core(arch, **kw):
    cfg = reduced(ARCHS[arch])
    params = init(jax.random.PRNGKey(0), cfg)
    return cfg, DecodeCore(cfg, RC, params, **kw)


@pytest.mark.parametrize("arch", SERVED_ARCHS)
def test_batch_step_for_one_step_per_phase_table(arch):
    """`batch_step_for` hands back the identical callable for equal
    tables (an equal copy of a table included) and one callable per
    distinct phase table."""
    _, core = _served_core(arch, quantize=True, plan_batch=MIXED_BATCH,
                           plan_max_len=MIXED_LEN)
    tables = (core.plan_table, core.prefill_plan_table)
    fns = [core.batch_step_for(t) for t in tables]
    copy = KernelPlanTable(entries=core.plan_table.entries)
    assert copy is not core.plan_table
    assert core.batch_step_for(copy) is fns[0]
    assert core.batch_step is fns[0]
    assert (fns[0] is fns[1]) == (tables[0] == tables[1])
    if arch == MIXED_ARCH:
        assert fns[0] is not fns[1]      # the phases gate differently


@pytest.mark.parametrize("arch", SERVED_ARCHS)
def test_phase_steps_cover_every_step_of_mixed_traffic(arch):
    """Requests joining while others decode: every engine step runs in
    exactly one phase, both phases occur, and the core compiled one
    program per distinct phase table."""
    cfg, core = _served_core(arch, quantize=True, plan_batch=MIXED_BATCH,
                             plan_max_len=MIXED_LEN)
    eng = ContinuousBatchingEngine(core, n_slots=3, max_len=24,
                                   block_size=4)
    t = eng.run(synthetic_requests(cfg, 5, seed=4, prompt_len=(3, 6),
                                   new_tokens=(3, 6)), None)
    assert t["aggregate"]["completed"] == 5
    tel = t["aggregate"]["phase_gating"]
    assert tel["enabled"] is True
    assert tel["phase_steps"]["prefill"] >= 1
    assert tel["phase_steps"]["decode"] >= 1
    assert sum(eng.phase_steps.values()) == eng.steps
    n_tables = len({core.plan_table, core.prefill_plan_table})
    assert core.batch_decode_executables in (n_tables, None)


@pytest.mark.parametrize("arch", SERVED_ARCHS)
def test_unquantized_core_serves_one_program_ungated(arch):
    """An unquantized core has no plan tables: the engine never splits
    phases and serves every step from one compiled program."""
    cfg, core = _served_core(arch, quantize=False)
    assert core.plan_table is None and core.prefill_plan_table is None
    eng = ContinuousBatchingEngine(core, n_slots=2, max_len=24,
                                   block_size=4)
    t = eng.run(synthetic_requests(cfg, 3, seed=5, prompt_len=(3, 5),
                                   new_tokens=(3, 5)), None)
    assert t["aggregate"]["completed"] == 3
    tel = t["aggregate"]["phase_gating"]
    assert tel["enabled"] is False
    assert tel["phase_switches"] == 0
    assert tel["phase_steps"] == {"prefill": 0, "decode": 0}
    assert tel["executables"] == {}
    assert core.batch_decode_executables in (1, None)


# --- KernelPlanTable ---------------------------------------------------------

def _decision(label, use_cim, what="baseline", where="PE"):
    """Minimal planner-Decision stand-in for from_decisions."""
    gemm = dataclasses.make_dataclass("G", ["label"])(label)
    return dataclasses.make_dataclass(
        "D", ["gemm", "use_cim", "what", "where"])(
            gemm, use_cim, what, where)


def test_equality_and_hash_stable_across_orderings():
    a = KernelPlanTable.from_decisions(
        [_decision("m Wq", True), _decision("m lm_head", False)],
        model_name="m")
    b = KernelPlanTable.from_decisions(
        [_decision("m lm_head", False), _decision("m Wq", True)],
        model_name="m")
    assert a == b
    assert hash(a) == hash(b)
    # any verdict change is a different table
    c = KernelPlanTable.from_decisions(
        [_decision("Wq", False), _decision("lm_head", False)])
    assert c != a
    assert c.flips(a) == ("Wq",)


def test_flips_diffs_by_gate_and_one_sided_labels():
    a = KernelPlanTable.from_decisions(
        [_decision("Wq", True), _decision("Wk", False)])
    assert a.flips(a) == ()
    b = KernelPlanTable.from_decisions(
        [_decision("Wq", True), _decision("Wk", True)])
    assert a.flips(b) == ("Wk",)
    # a label present in only one table counts as flipped
    wider = KernelPlanTable(entries=a.entries
                            + (("Wv", PlanEntry(use_cim=True)),))
    assert a.flips(wider) == ("Wv",)
    assert wider.flips(a) == ("Wv",)


def test_drift_gate_holds_on_every_table():
    """The KeyError-with-known-labels contract holds on any table, one
    with a flipped verdict included: silent ungating on label drift is
    the failure mode."""
    base = KernelPlanTable.from_decisions(
        [_decision("Wq", True), _decision("lm_head", False)])
    flipped = KernelPlanTable.from_decisions(
        [_decision("Wq", True), _decision("lm_head", True)])
    assert flipped.use_cim("lm_head") != base.use_cim("lm_head")
    assert flipped.use_cim("Wq") == base.use_cim("Wq")
    with pytest.raises(KeyError, match="known labels.*Wq"):
        flipped.use_cim("mlp-up")
    with pytest.raises(KeyError, match="unknown GEMM label"):
        base.entry("nope")


def test_strip_model_prefix_edges():
    assert strip_model_prefix("m Wq", "m") == "Wq"
    # empty model name: no prefix to strip
    assert strip_model_prefix("m Wq", "") == "m Wq"
    # label equal to the bare prefix (no trailing space): untouched
    assert strip_model_prefix("m", "m") == "m"
    # prefix-with-space but empty remainder strips to empty
    assert strip_model_prefix("m ", "m") == ""
    assert strip_model_prefix("other Wq", "m") == "other Wq"


# --- widened-precision routes: INT4 / FP8 gated execution --------------------

@pytest.mark.parametrize("precision,routes", [
    ("int4", {"cim-int4-pallas", "int4-dequant-xla"}),
    ("fp8", {"cim-fp8-pallas", "fp8-dequant-xla"}),
])
def test_gated_vs_ungated_parity_lowbit(precision, routes):
    """Acceptance for the runtime What axis: a quantized session at
    INT4/FP8 routes at least one projection through the low-bit CiM
    Pallas path and at least one through the dequant-XLA path
    (verdict-dependent), matches the ungated program within kernel
    tolerance, and generates identical tokens — same low-bit weights,
    routing the only difference.  One executable per phase throughout."""
    cfg = reduced(ARCHS[MIXED_ARCH])
    params = init(jax.random.PRNGKey(1), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(2), (8, 5), 0,
                                cfg.vocab)
    gated = ServeSession(cfg, RC, params, max_len=16, batch=8,
                         quantize=True, precision=precision)
    seen = {r["route"] for r in gated.route_report().values()}
    assert routes <= seen, seen

    ungated = ServeSession(cfg, RC, params, max_len=16, batch=8,
                           quantize=True, gated=False,
                           precision=precision)
    lg = np.asarray(gated.prefill(prompt), np.float32)
    lu = np.asarray(ungated.prefill(prompt), np.float32)
    np.testing.assert_allclose(lg, lu, rtol=5e-2, atol=5e-2)

    out_g = gated.generate(prompt[:, -1:], n_new=4)
    out_u = ungated.generate(prompt[:, -1:], n_new=4)
    np.testing.assert_array_equal(np.asarray(out_g), np.asarray(out_u))
    assert gated.decode_executables in (1, None)
    assert gated.prefill_executables in (1, None)
