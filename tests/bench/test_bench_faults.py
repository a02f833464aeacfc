"""A whole run of a tiny cell on the CPU, past the harness's look for a
chip: sound, it comes out correct and reports its metrics; with the
timed path broken underneath, `correct` comes out false.  The faults are
those a serving cell on one chip can have: a step that returns its state
unchanged, half of the batch left out, a token altered where it is
produced, and a state kept below the precision the configuration states.
(No exchange between chips: every cell runs on one.)"""
import json

import numpy as np
import pytest

from bench.lib.cell import run_cell


def run(bench, workload, trace=False, precision="int8"):
    return run_cell(bench, workload, seed=2 ** 32 + 77, seconds=1.0,
                    trace=trace, t_start=0.0, require_tpu=False,
                    precision=precision)


@pytest.mark.parametrize("workload,trace", [("ssm_offline", False),
                                            ("dense_chat", True)])
def test_a_sound_run_is_correct(tiny_bench, workload, trace):
    r = run(tiny_bench, workload, trace)
    assert r["correct"] is True
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["run"]["compiles_in_window"] == 0
    assert r["run"]["tokens_compared"] > 0
    want = {m["name"] for m in tiny_bench.metrics(workload, trace)}
    # on the CPU there is no peak-memory reading and no INT8 kernel call
    want -= {"peak_hbm_gib", "int8_gemm_roofline.offline"}
    assert want <= set(r["metrics"])
    line = json.loads(json.dumps(r))
    assert list(line)[-1] == "checks"
    for c in line["checks"].values():
        assert c["value"] <= c["limit"]
    if trace:
        assert 0 < r["device"]["busy_s"] <= r["device"]["window_s"]
        assert len(r["breakdown"]["device_ops"]) <= 10
        assert len(r["breakdown"]["idle_gaps"]) <= 10


@pytest.mark.parametrize("workload", ["ssm_offline", "dense_chat"])
def test_the_window_opens_on_a_loaded_engine(tiny_bench, workload):
    """A backlog primes every slot part-way through a request before the
    window opens; an open-loop schedule runs its pre-roll first.  Neither
    counts among the requests due in the window."""
    r = run(tiny_bench, workload)
    rows = r["requests"]
    cell = tiny_bench.cell(workload)
    if workload == "ssm_offline":
        assert r["run"]["requests_primed"] == cell["slots"]
        assert r["attempted"] == len(rows)
    else:
        assert r["run"]["requests_primed"] == 0
        early = [row for row in rows if row[0] < 0]
        assert early and r["attempted"] == len(rows) - len(early)
    assert r["run"]["setup_parts_s"]["loaded"] >= r["run"][
        "setup_parts_s"]["warm"]


def _state_unchanged(real):
    def fn(params, cache, *rest):
        logits, _ = real(params, cache, *rest)
        return logits, cache
    return fn


def _half_batch(real):
    def fn(params, cache, tokens, pos, active, tables):
        half = np.arange(active.shape[0]) < active.shape[0] // 2
        return real(params, cache, tokens, pos, active & half, tables)
    return fn


def _state_in_bf16(real):
    """A step that keeps the SSM state in bfloat16 (the configuration
    states float32)."""
    import jax.numpy as jnp

    def fn(*args):
        logits, cache = real(*args)
        return logits, [dict(c, state=c["state"].astype(jnp.bfloat16))
                        if "state" in c else c for c in cache]
    return fn


FAULTS = {"state_unchanged": ("dense_chat", _state_unchanged),
          "half_batch": ("ssm_offline", _half_batch),
          "state_in_bf16": ("ssm_offline", _state_in_bf16)}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_broken_step_is_not_correct(tiny_bench, monkeypatch, fault):
    from repro.serving.core import DecodeCore
    workload, wrap = FAULTS[fault]
    real = DecodeCore.batch_step_for
    monkeypatch.setattr(DecodeCore, "batch_step_for",
                        lambda self, plan: wrap(real(self, plan)))
    r = run(tiny_bench, workload)
    assert r["correct"] is False
    assert any(c["value"] is None or c["value"] > c["limit"]
               for c in r["checks"].values())


def test_an_altered_token_is_not_correct(tiny_bench, monkeypatch):
    import repro.serving.scheduler as sched
    real = sched.sample_token

    def altered(cfg, logits, temperature, key):
        return (real(cfg, logits, temperature, key) + 1) % cfg.vocab
    monkeypatch.setattr(sched, "sample_token", altered)
    r = run(tiny_bench, "dense_chat")
    assert r["correct"] is False


@pytest.mark.parametrize("workload", ["ssm_offline", "dense_chat"])
def test_the_int4_control_is_not_correct(tiny_bench, workload):
    """The control: the program's own INT4 weight path, one precision
    step below the INT8 the configuration states, compared against the
    INT8 weights' reference."""
    r = run(tiny_bench, workload, precision="int4")
    assert r["correct"] is False
