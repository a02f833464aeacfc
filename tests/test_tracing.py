"""How the serving path marks itself for a profiler: the sublayer scopes
in the decode step's compiled HLO and the stable name of its program,
and the engine's phase spans, opened and closed where the engine's
counters read the clock."""
import re

import jax
import numpy as np
import pytest

from repro.configs import ARCHS, RunConfig, reduced
from repro.launch.serve import build_parser, serve
from repro.models import decode_step, init
from repro.models.layers import PROJ_SCOPE, SCOPES, route_trace
from repro.serving import (ContinuousBatchingEngine, DecodeCore,
                           synthetic_requests)

RC = RunConfig(remat=False, attn_impl="naive")
MAX_LEN = 24
BLOCK = 4
SLOTS = 4
# the scopes each family's decode step opens; together, all of SCOPES
FAMILY_SCOPES = {
    "mamba2-780m": {"embed", "norm", "ssd", "cache_mask", "lm_head",
                    "layer_scan"},
    "mistral-nemo-12b": {"embed", "norm", "ffn_act", "attn_core",
                         "lm_head", "layer_scan"},
}


@pytest.fixture(scope="module", params=sorted(FAMILY_SCOPES))
def served(request):
    """A plan-gated core of one family and an engine that has served a
    few requests, so each phase plan's batch step has compiled."""
    cfg = reduced(ARCHS[request.param])
    core = DecodeCore(cfg, RC, init(jax.random.PRNGKey(0), cfg),
                      quantize=True, plan_batch=SLOTS, plan_max_len=MAX_LEN)
    eng = ContinuousBatchingEngine(core, n_slots=SLOTS, max_len=MAX_LEN,
                                   block_size=BLOCK)
    reqs = synthetic_requests(cfg, 6, seed=3, prompt_len=(3, 6),
                              new_tokens=(3, 6))
    eng.run(reqs, None)
    return request.param, core, eng, reqs


def _compiled_text(core, eng, table) -> str:
    n = eng.n_slots
    tokens = eng._mix_tokens(eng._token_batch(), np.zeros(n, bool))
    return core.batch_step_for(table).lower(
        core.params, eng.cache, tokens, np.zeros(n, np.int32),
        np.zeros(n, bool), eng.block_tables).compile().as_text()


def test_decode_step_carries_its_scopes_in_one_program(served):
    arch, core, eng, _ = served
    assert set().union(*FAMILY_SCOPES.values()) == set(SCOPES)
    for phase, table in (("decode", core.plan_table),
                         ("prefill", core.prefill_plan_table)):
        text = _compiled_text(core, eng, table)
        assert text.startswith("HloModule jit_serve_batch_step")
        names = re.findall(r'op_name="([^"]*)"', text)
        parts = {p for name in names for p in name.split("/")}
        assert FAMILY_SCOPES[arch] <= parts, phase
        # every projection sits in "proj/<route>/<label>", with the route
        # the step records for that label
        with route_trace() as recs:
            jax.eval_shape(
                lambda p, c, t, q, a, b: decode_step(
                    p, c, t, q, core.cfg, RC, plan=table, active=a,
                    block_tables=b),
                core.params, eng.cache,
                jax.ShapeDtypeStruct((SLOTS, 1), np.int32),
                np.zeros(SLOTS, np.int32), np.zeros(SLOTS, bool),
                eng.block_tables)
        assert recs
        for r in recs:
            scope = f"{PROJ_SCOPE}/{r['route']}/{r['label']}/"
            assert any(scope in name for name in names), scope
        # looking the program up again compiled nothing
        assert core.plan_executables(table) == 1, phase
    assert set(eng.telemetry()["aggregate"]["phase_gating"][
        "executables"].values()) == {1}


def test_admission_counters(served):
    _, _, eng, reqs = served
    assert eng.admissions == len(reqs) == len(eng.completed)
    assert 0.0 < eng.admit_s <= eng.telemetry_s
    assert eng.plan_s > 0.0
    bd = eng.telemetry()["aggregate"]["decode_step_breakdown"]
    assert bd["admissions"] == bd["fresh_lanes"] == len(reqs)
    for name in ("admit", "plan", "dispatch", "host_fetch", "telemetry"):
        assert bd[f"{name}_s"] >= 0.0
        assert bd[f"{name}_ms_per_step"] >= 0.0
    assert bd["admit_s"] <= bd["telemetry_s"]


class _Clock:
    """A clock that moves one tick per reading."""

    def __init__(self):
        self.now = 0

    def __call__(self) -> float:
        self.now += 1
        return float(self.now - 1)


def test_spans_and_counters_share_their_edges(monkeypatch):
    """Each "engine.<phase>" span opens just before its counter reads the
    clock and closes just after, so the counter holds exactly the ticks
    read inside the span.  Admission opens no span of its own per
    joining slot: the step zeroes a joining lane's state, and
    `fresh_lanes` counts one such lane-step per admission."""
    cfg = reduced(ARCHS["mamba2-780m"])
    core = DecodeCore(cfg, RC, init(jax.random.PRNGKey(0), cfg),
                      quantize=True, plan_batch=2, plan_max_len=MAX_LEN)
    clock = _Clock()
    log = []

    class Recorder:
        def __init__(self, name, **args):
            self.name, self.args = name, args

        def __enter__(self):
            log.append(("enter", self.name, clock.now, self.args))

        def __exit__(self, *exc):
            log.append(("exit", self.name, clock.now, self.args))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    monkeypatch.setattr(jax.profiler, "StepTraceAnnotation", Recorder)
    eng = ContinuousBatchingEngine(core, n_slots=2, max_len=MAX_LEN,
                                   block_size=BLOCK, clock=clock)
    reqs = synthetic_requests(cfg, 3, seed=1, prompt_len=(3, 4),
                              new_tokens=(2, 3))
    for r in reqs:
        eng.submit(r)
    calls = 0
    while eng.step():
        calls += 1
    calls += 1
    ticks, open_at, depth = {}, {}, []
    for kind, name, now, _ in log:
        if kind == "enter":
            open_at[name] = now
            depth.append(name)
            continue
        assert depth.pop() == name          # spans nest
        ticks.setdefault(name, []).append(now - open_at[name])
    assert not depth
    # a counted span reads the clock twice, at its very edges
    for phase, counter in (("admit", "admit_s"), ("plan", "plan_s"),
                           ("dispatch", "dispatch_s")):
        spans = ticks[f"engine.{phase}"]
        assert getattr(eng, counter) == sum(t - 1 for t in spans), phase
    assert len(ticks["engine.step"]) == calls
    assert "engine.reset_slot" not in {name for _, name, _, _ in log}
    assert set(ticks) == {"engine.step", "engine.admit", "engine.plan",
                          "engine.dispatch", "engine.retire",
                          "engine.telemetry"}
    assert eng.fresh_lanes == eng.admissions == len(reqs)


def test_serve_traffic_report_splits_admission_and_planning():
    rep = serve(build_parser().parse_args(
        ["--arch", "mamba2-780m", "--smoke", "--quantize", "--batch", "8",
         "--prompt-len", "4", "--new-tokens", "4", "--requests", "3",
         "--slots", "2", "--arrival-rate", "0"]))
    bd = rep["traffic"]["aggregate"]["decode_step_breakdown"]
    assert bd["admissions"] == 3
    assert 0.0 < bd["admit_ms_per_step"] <= bd["telemetry_ms_per_step"]
    assert bd["plan_ms_per_step"] > 0.0
