"""Device time by sublayer scope and idle time by engine phase: on
hand-made ops and spans whose answers are known, on a trace recorded
here on the CPU, and on a program that marks neither."""
import time

import jax
import jax.numpy as jnp
import pytest

from bench.lib import scopes as sc
from bench.lib import trace as tr

MS = 1_000_000      # ns
ATTN = "jit(step)/layer_scan/while/body/closed_call/attn_core/gather"
SSD = "jit(step)/layer_scan/while/body/closed_call/ssd/exp"


@pytest.mark.parametrize("path,scope", [
    (ATTN, "attn_core"),
    ("jit(step)/lm_head/proj/int8-dequant-xla/lm_head/dot_general",
     "proj/int8-dequant-xla/lm_head"),
    ("jit(step)/layer_scan/while/body/proj/cim-int8-pallas/Wo/"
     "jit(int8_matmul)/pallas_call", "proj/cim-int8-pallas/Wo"),
    ("jit(step)/layer_scan/while/body/add", "layer_scan"),
    ("jit(greedy_tokens)/argmax", sc.UNSCOPED),
    (None, sc.UNSCOPED),
])
def test_the_innermost_scope_names_the_bucket(path, scope):
    assert sc.scope_of(path) == scope


def test_program_and_reduction_name_the_same_scopes():
    from repro.models.layers import PROJ_SCOPE, SCOPES
    assert sc.SCOPES == SCOPES and sc.PROJ_SCOPE == PROJ_SCOPE


def test_an_op_without_metadata_takes_its_operands_path():
    text = "\n".join([
        "HloModule jit_step, entry_computation_layout={()->f32[]}",
        "ENTRY %main (p: f32[8]) -> f32[8] {",
        '  %p = f32[8]{0} parameter(0), metadata={op_name="p"}',
        '  %fusion.3 = f32[8]{0} fusion(%p), kind=kLoop, '
        f'calls=%fc, metadata={{op_name="{ATTN}"}}',
        "  %gte.1 = f32[8]{0} get-tuple-element(%fusion.3), index=0",
        "  %convert.9 = f32[8]{0} convert(%gte.1), backend_config={}",
        "  %copy.2 = f32[8]{0} copy(%p)",
        "}"])
    module, paths = sc.hlo_paths(text)
    assert module == "jit_step"
    assert paths["convert.9"] == paths["fusion.3"] == ATTN
    assert sc.scope_of(paths["copy.2"]) == sc.UNSCOPED


def test_a_fusion_rooted_in_scan_plumbing_takes_its_sublayer():
    def fused(name, *paths):
        return [f"%{name} (p: f32[8]) -> f32[8] {{"] + [
            f'  %{name}.i{k} = f32[8]{{0}} multiply(%p, %p), '
            f'metadata={{op_name="{path}"}}' for k, path in enumerate(paths)
        ] + ["}"]
    scan = "jit(step)/layer_scan/while/body/broadcast_in_dim"
    norm = "jit(step)/layer_scan/while/body/norm/mul"
    proj = "jit(step)/layer_scan/while/body/proj/int8-dequant-xla/Wq/dot"
    text = "\n".join(
        ["HloModule jit_step"]
        + fused("fc.1", SSD, SSD, norm, scan)
        + fused("fc.2", norm, norm, norm, proj)
        + ["ENTRY %main (p: f32[8]) -> f32[8] {",
           f'  %fusion.1 = f32[8]{{0}} fusion(%p), kind=kLoop, calls=%fc.1, '
           f'metadata={{op_name="{scan}"}}',
           f'  %fusion.2 = f32[8]{{0}} fusion(%p), kind=kLoop, calls=%fc.2, '
           f'metadata={{op_name="{proj}"}}',
           "}"])
    _, paths = sc.hlo_paths(text)
    # the stacking broadcast roots the SSD update: the SSD's fusion
    assert sc.scope_of(paths["fusion.1"]) == "ssd"
    # a projection's matmul with its norm fused in stays the projection's
    assert paths["fusion.2"] == proj


def hand_made():
    """A 100 ms window on one device.  Program "jit_a" runs a while loop
    over 10-40 holding fusion.1 (attn_core, 10-20), a copy (no scope,
    15-30) and fusion.2 (ssd, 25-35); the loop alone runs 35-40.
    Program "jit_b" runs its own fusion.1 (ssd there) at 60-70.  The
    host's engine step covers 1-99: admission 50-60 holds a slot reset
    52-58, dispatch 60-70."""
    ops = [("while.1", 10 * MS, 40 * MS, "jit_a"),
           ("fusion.1", 10 * MS, 20 * MS, "jit_a"),
           ("copy-start.4", 15 * MS, 30 * MS, "jit_a"),
           ("fusion.2", 25 * MS, 35 * MS, "jit_a"),
           ("fusion.1", 60 * MS, 70 * MS, "jit_b")]
    spans = [(tr.WINDOW_SPAN, 0, 100 * MS), ("bench.step", 0, 100 * MS),
             ("engine.step", 1 * MS, 99 * MS),
             ("engine.admit", 50 * MS, 60 * MS),
             ("engine.reset_slot", 52 * MS, 58 * MS),
             ("engine.dispatch", 60 * MS, 70 * MS)]
    paths = {("jit_a", "while.1"): "jit(a)/layer_scan/while",
             ("jit_a", "fusion.1"): ATTN, ("jit_a", "fusion.2"): SSD,
             ("jit_b", "fusion.1"): SSD}
    return tr.Events({"/device:TPU:0": ops}, spans), paths


def test_scope_buckets_are_disjoint_and_sum_to_busy_time():
    ev, paths = hand_made()
    busy = tr.reduce(sc.plain(ev), kernel=r"int8")["busy_s"]
    r = sc.scopes(ev, paths)
    got = dict(r["device_scopes"])
    assert sum(got.values()) == pytest.approx(busy) == pytest.approx(0.040)
    # where ops overlap the one that started last takes the time: the
    # copy over 15-25, fusion.2 from 25; the loop alone over 35-40; the
    # same-named fusion.1 of jit_b is SSD time, not attention
    assert got == pytest.approx({"attn_core": 0.005, sc.UNSCOPED: 0.010,
                                 "ssd": 0.020, "layer_scan": 0.005})
    assert dict(r["device_programs"]) == pytest.approx(
        {"jit_a": 0.030, "jit_b": 0.010})
    assert dict(r["unscoped_ops"]) == pytest.approx({"copy-start": 0.010})


def test_idle_gaps_are_named_by_the_innermost_engine_phase():
    ev, _ = hand_made()
    r = sc.idle(ev)
    gaps = dict((round(s, 6), n) for n, s in r["idle_gaps"])
    # 0-10 and 70-100 (mid 5 and 85: the engine step, inside the bench
    # step), 40-60 (mid 50: admission, inside the engine step)
    assert gaps == {0.030: "engine.step", 0.020: "engine.admit",
                    0.010: "engine.step"}
    idle = r["idle_in_spans_s"]
    assert idle["engine.admit"] == pytest.approx(0.010)
    assert idle["engine.reset_slot"] == pytest.approx(0.006)
    assert idle["engine.dispatch"] == pytest.approx(0.0)
    assert idle["engine.step"] == pytest.approx(0.058)


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """A trace recorded here: three engine steps, each admitting for
    20 ms and then dispatching a jitted step with two scopes.  Returns
    the trace's path, the step's module name and its op paths."""
    def named_step(x):
        with jax.named_scope("attn_core"):
            y = jnp.tanh(x @ x)
        with jax.named_scope("ssd"):
            return jnp.exp(-y).sum()

    f = jax.jit(named_step)
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    module, paths = sc.hlo_paths(f.lower(x).compile().as_text())
    out = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(out)
    with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
        for i in range(3):
            with jax.profiler.StepTraceAnnotation("engine.step",
                                                  step_num=i):
                with jax.profiler.TraceAnnotation("engine.admit"):
                    time.sleep(0.02)
                with jax.profiler.TraceAnnotation("engine.dispatch"):
                    f(x).block_until_ready()
    jax.profiler.stop_trace()
    return tr.latest_xplane(out), module, paths


def test_recorded_trace_by_scope_and_phase(recorded):
    path, module, paths = recorded
    assert module == "jit_named_step"
    ev = sc.extract(path)
    assert {n for n, _, _ in ev.host_spans} >= {
        tr.WINDOW_SPAN, "engine.step", "engine.admit", "engine.dispatch"}
    r = tr.reduce(sc.plain(ev), kernel=r"dot")
    s = sc.scopes(ev, {(module, op): p for op, p in paths.items()})
    got = dict(s["device_scopes"])
    assert sum(got.values()) == pytest.approx(r["busy_s"])
    assert got.get("attn_core", 0) > 0 and got.get("ssd", 0) > 0
    assert dict(s["device_programs"])[module] > 0
    # the device waits while the host admits: the longest gaps are there
    i = sc.idle(ev)
    assert i["idle_gaps"][0][0] == "engine.admit"
    assert i["idle_in_spans_s"]["engine.admit"] >= 0.05


def test_plain_events_reduce_as_the_harness_reads_them(recorded):
    path, _, _ = recorded
    ev = sc.extract(path)
    assert tr.reduce(sc.plain(ev), kernel=r"dot") == tr.reduce(
        tr.extract(path), kernel=r"dot")
    assert all(n.startswith("bench.") for n, _, _ in sc.plain(ev).host_spans)


def test_new_readers_fall_silent_on_a_program_without_them():
    ev, _ = hand_made()
    ev.host_spans = [s for s in ev.host_spans
                     if not s[0].startswith("engine.")]
    busy = tr.reduce(sc.plain(ev), kernel=r"int8")["busy_s"]
    # no scopes: the whole busy time is unscoped; no engine spans: the
    # gaps are named by the harness's step
    assert dict(sc.scopes(ev)["device_scopes"]) == pytest.approx(
        {sc.UNSCOPED: busy})
    i = sc.idle(ev)
    assert {n for n, _ in i["idle_gaps"]} == {"bench.step"}
    assert set(i["idle_in_spans_s"]) == {"bench.step"}
