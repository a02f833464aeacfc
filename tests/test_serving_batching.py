"""Continuous batching: slot scheduling, paged KV, and parity with the
legacy fixed-batch session.

The engine contract under test: any ragged request stream — join at
full occupancy, evict-on-EOS mid-scan, single-lane traffic — runs
through ONE compiled masked decode step (`decode_executables == 1`)
and produces, per request, exactly the tokens the legacy
`ServeSession(batch=1)` produces for that request alone.  mamba2-780m
is the mixed-verdict gated case (ssm-BCdt on CiM, the rest standard);
mistral-nemo-12b exercises the paged-KV gather/scatter across block
boundaries; granite-4.0-h-small holds both kinds of cache (SSM state and
paged KV) and an expert layer in one step.
"""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, RunConfig, reduced
from repro.models import init
from repro.serving import (BlockAllocator, ContinuousBatchingEngine,
                           DecodeCore, Request, ServeSession,
                           synthetic_requests)

RC = RunConfig(remat=False, attn_impl="naive")
MAX_LEN = 24
BLOCK = 4          # small so smoke prompts cross block edges


def _core(arch: str, quantize: bool, dtype: str | None = None):
    cfg = reduced(ARCHS[arch])
    if dtype is not None:
        cfg = dataclasses.replace(cfg, param_dtype=dtype,
                                  compute_dtype=dtype)
    params = init(jax.random.PRNGKey(0), cfg)
    return cfg, params, DecodeCore(cfg, RC, params, quantize=quantize,
                                   plan_batch=4, plan_max_len=MAX_LEN)


@pytest.fixture(scope="module")
def mamba():
    """Quantized gated ssm core (the mixed-verdict arch)."""
    return _core("mamba2-780m", quantize=True)


@pytest.fixture(scope="module")
def attn():
    """Quantized attention core (paged KV path)."""
    return _core("mistral-nemo-12b", quantize=True)


@pytest.fixture(scope="module")
def hybrid():
    """Quantized Mamba-2 + GQA + MoE core (SSM state and paged KV), in
    float32: the expert layer contracts every held expert over all of a
    step's tokens, and the CPU's accumulation order for that contraction
    depends on the token count, so in bfloat16 a 3-slot engine and the
    1-lane session can round a logit one bfloat16 step apart.  In
    float32 the two agree to well inside the parity tolerance."""
    return _core("granite-4.0-h-small", quantize=True,
                 dtype="float32")


def _engine(core, n_slots, **kw):
    kw.setdefault("max_len", MAX_LEN)
    kw.setdefault("block_size", BLOCK)
    return ContinuousBatchingEngine(core, n_slots=n_slots, **kw)


def _legacy_tokens(cfg, params, prompt, n_new, quantize=True):
    s = ServeSession(cfg, RC, params, max_len=MAX_LEN, batch=1,
                     quantize=quantize)
    out = s.generate(np.asarray(prompt)[None], n_new=n_new)
    return np.asarray(jax.device_get(out)).reshape(-1)


# --- BlockAllocator ----------------------------------------------------------

def test_allocator_all_or_nothing_and_reuse():
    a = BlockAllocator(4)
    first = a.alloc(3)
    assert len(first) == 3 and a.free_blocks == 1
    assert a.alloc(2) is None          # exhaustion: nothing is handed out
    assert a.free_blocks == 1
    a.free(first)
    again = a.alloc(4)
    assert a.free_blocks == 0
    assert set(first) <= set(again)      # freed ids are reused, not grown
    assert set(again) == set(range(4))
    assert a.peak_in_use == 4


def test_allocator_rejects_double_free():
    """A double-free (in a later call or within one call) raises and
    leaves the free list untouched — a silently re-listed id would be
    handed to two slots."""
    a = BlockAllocator(4)
    blocks = a.alloc(2)
    a.free(blocks)
    with pytest.raises(ValueError, match="double-free"):
        a.free(blocks)                       # already back in the pool
    assert a.free_blocks == 4                # state untouched by the raise
    fresh = a.alloc(1)
    with pytest.raises(ValueError, match="double-free"):
        a.free(fresh + fresh)                # duplicate within one call
    assert a.in_use == 1                     # still held: nothing mutated
    a.free(fresh)                            # the valid free still works
    assert a.free_blocks == 4
    assert a.peak_in_use == 2                # unchanged by the bad calls


def test_allocator_rejects_foreign_ids():
    """Ids the pool never issued (negative or >= n_blocks) raise; a
    mixed batch of valid+foreign ids mutates nothing."""
    a = BlockAllocator(4)
    held = a.alloc(2)
    for bad in ([-1], [4], [99]):
        with pytest.raises(ValueError, match=r"outside pool \[0, 4\)"):
            a.free(bad)
    with pytest.raises(ValueError):
        a.free([held[0], 7])                 # valid id rides along: still atomic
    assert a.in_use == 2                     # the valid id was NOT freed
    a.free(held)
    assert a.free_blocks == 4
    assert a.peak_in_use == 2


def test_pool_exhaustion_defers_admission(mamba):
    """A KV-less arch can't exercise pool pressure, so force it via a
    tiny allocator on the attention-free engine path is moot — instead
    check the admission math directly on the scheduler."""
    cfg, params, core = mamba
    eng = _engine(core, n_slots=2)
    assert eng.allocator.n_blocks == 0          # ssm: no KV pool needed
    r = Request(rid=0, prompt=np.arange(4, dtype=np.int32),
                max_new_tokens=MAX_LEN)         # horizon > max_len
    with pytest.raises(ValueError, match="exceeds engine max_len"):
        eng.submit(r)


def test_pool_exhaustion_blocks_admission_attn(attn):
    cfg, params, core = attn
    # pool holds exactly one request's horizon: the second must queue
    # until the first evicts and frees its blocks
    blocks_per_req = math.ceil((4 + 4) / BLOCK)
    eng = _engine(core, n_slots=2, n_kv_blocks=blocks_per_req)
    reqs = synthetic_requests(cfg, 2, seed=3, prompt_len=(4, 4),
                              new_tokens=(4, 4))
    eng.run(reqs, None)
    assert len(eng.completed) == 2
    a, b = sorted(eng.completed, key=lambda r: r.t_admit)
    assert b.t_admit >= a.t_done          # serialized by pool pressure
    assert eng.allocator.peak_in_use == blocks_per_req
    assert eng.allocator.free_blocks == blocks_per_req   # all reclaimed


# --- slot scheduling ---------------------------------------------------------

def test_join_at_full_occupancy(mamba):
    """Three requests into two slots: the third queues, then takes the
    first freed slot mid-run."""
    cfg, params, core = mamba
    eng = _engine(core, n_slots=2)
    reqs = synthetic_requests(cfg, 3, seed=1, prompt_len=(3, 5),
                              new_tokens=(4, 8))
    t = eng.run(reqs, None)
    assert t["aggregate"]["completed"] == 3
    assert t["aggregate"]["queue_depth_max"] >= 1
    last = max(eng.completed, key=lambda r: r.t_admit)
    first_done = min(r.t_done for r in eng.completed)
    assert last.t_admit > last.t_submit        # it waited in the queue
    assert last.t_admit >= first_done          # ...until a slot freed
    # and the queued request still matches its solo legacy run
    want = _legacy_tokens(cfg, params, last.prompt, last.max_new_tokens)
    assert np.array_equal(np.asarray(last.tokens), want)


@pytest.mark.parametrize("arch_fixture", ["mamba", "attn", "hybrid"])
def test_evict_on_eos_mid_scan(arch_fixture, request):
    """Learn the greedy token stream, re-run with one of its tokens as
    EOS: the request must finish early with done_reason='eos' while the
    other slot keeps decoding to max_tokens."""
    cfg, params, core = request.getfixturevalue(arch_fixture)
    probe = _engine(core, n_slots=1)
    probe.run(synthetic_requests(cfg, 1, seed=2, prompt_len=(4, 4),
                                 new_tokens=(8, 8)), None)
    stream = [int(t) for t in probe.completed[0].tokens]
    eos = stream[2]                     # third token => early stop
    prompt = probe.completed[0].prompt

    eng = _engine(core, n_slots=2)
    eng.submit(Request(rid="eos", prompt=prompt, max_new_tokens=8,
                       eos_id=eos))
    eng.submit(Request(rid="full", prompt=prompt, max_new_tokens=8))
    eng.drain()
    by_rid = {r.rid: r for r in eng.completed}
    assert by_rid["eos"].done_reason == "eos"
    assert len(by_rid["eos"].tokens) == 3      # stops AT the eos token
    assert [int(t) for t in by_rid["eos"].tokens] == stream[:3]
    assert by_rid["full"].done_reason == "max_tokens"
    assert len(by_rid["full"].tokens) == 8
    assert by_rid["full"].t_done > by_rid["eos"].t_done


@pytest.mark.parametrize("arch_fixture", ["mamba", "attn", "hybrid"])
def test_single_request_batch_matches_legacy(arch_fixture, request):
    cfg, params, core = request.getfixturevalue(arch_fixture)
    eng = _engine(core, n_slots=1)
    reqs = synthetic_requests(cfg, 1, seed=5, prompt_len=(6, 6),
                              new_tokens=(10, 10))
    eng.run(reqs, None)
    r = eng.completed[0]
    want = _legacy_tokens(cfg, params, r.prompt, r.max_new_tokens)
    assert np.array_equal(np.asarray(r.tokens), want)


# --- parity + no-retrace -----------------------------------------------------

@pytest.mark.parametrize("arch_fixture", ["mamba", "attn", "hybrid"])
def test_continuous_matches_fixed_batch(arch_fixture, request):
    """Token + first-logits parity of the continuous engine against the
    legacy per-request session, through slot churn.  Prompts are long
    enough that the attention arch's paged KV crosses block boundaries
    (prompt + output > BLOCK)."""
    cfg, params, core = request.getfixturevalue(arch_fixture)
    eng = _engine(core, n_slots=3, record_logits=True)
    reqs = synthetic_requests(cfg, 5, seed=7, prompt_len=(4, 9),
                              new_tokens=(5, 12))
    t = eng.run(reqs, None)
    assert t["aggregate"]["completed"] == 5
    legacy = ServeSession(cfg, RC, params, max_len=MAX_LEN, batch=1,
                          quantize=True)
    for r in eng.completed:
        prompt = np.asarray(r.prompt)[None]
        legacy.reset()
        ref_logits = legacy.prefill(prompt).astype(jnp.float32)
        legacy.reset()
        want = np.asarray(jax.device_get(
            legacy.generate(prompt, n_new=r.max_new_tokens))).reshape(-1)
        assert np.array_equal(np.asarray(r.tokens), want), r.rid
        np.testing.assert_allclose(
            np.asarray(r.first_logits),
            np.asarray(jax.device_get(ref_logits[0, -1])),
            rtol=0, atol=1e-5)
    # no-retrace: a second pass of different ragged traffic at the same
    # slot count must reuse the executable (the module-shared core has
    # one program per distinct n_slots used by earlier tests, so the
    # meaningful gate here is "no growth", not an absolute count)
    n_before = eng.decode_executables
    eng2 = _engine(core, n_slots=3)
    eng2.run(synthetic_requests(cfg, 2, seed=8, prompt_len=(3, 7),
                                new_tokens=(3, 7)), None)
    if n_before is not None:
        assert eng2.decode_executables == n_before


@pytest.mark.parametrize("arch", ["mamba2-780m", "mistral-nemo-12b",
                                  "granite-4.0-h-small"])
def test_decode_executables_one_after_churn(arch):
    """Fresh gated core, fixed slot count, back-to-back runs with
    different ragged traffic: exactly one compiled masked step (at this
    planning shape both phases share one table) — the absolute
    no-retrace gate."""
    cfg, params, core = _core(arch, quantize=True)
    assert core.prefill_plan_table == core.plan_table
    for n_req, seed in ((3, 11), (1, 12)):
        eng = _engine(core, n_slots=2)
        eng.run(synthetic_requests(cfg, n_req, seed=seed,
                                   prompt_len=(3, 6),
                                   new_tokens=(3, 6)), None)
        assert len(eng.completed) == n_req
        assert eng.decode_executables in (1, None)


@pytest.mark.parametrize("arch_fixture", ["mamba", "hybrid"])
def test_admission_dispatches_no_device_work(arch_fixture, request):
    """Admission is host bookkeeping: across a churned run (more requests
    than slots, so freed slots are taken again) no admission replaces a
    cache array or makes a new device array.  The step zeroes each
    joining lane's SSM state instead, and `fresh_lanes` counts one
    lane-step at position 0 per join."""
    cfg, _, core = request.getfixturevalue(arch_fixture)
    eng = _engine(core, n_slots=2)
    admit, joins = eng._admit, []

    def watched_admit():
        before = eng.admissions
        leaves = [id(a) for a in jax.tree.leaves(eng.cache)]
        live = {id(a) for a in jax.live_arrays()}
        admit()
        if eng.admissions > before:
            joins.append(eng.admissions - before)
            assert [id(a) for a in jax.tree.leaves(eng.cache)] == leaves
            assert {id(a) for a in jax.live_arrays()} <= live
    eng._admit = watched_admit
    reqs = synthetic_requests(cfg, 5, seed=4, prompt_len=(3, 5),
                              new_tokens=(2, 4))
    eng.run(reqs, None)
    assert len(eng.completed) == len(reqs)
    assert sum(joins) == len(reqs) and len(joins) >= 3   # slots reused
    bd = eng.telemetry()["aggregate"]["decode_step_breakdown"]
    assert bd["fresh_lanes"] == bd["admissions"] == len(reqs)


def test_vlm_rejected():
    cfg = reduced(ARCHS["llama-3.2-vision-90b"])
    params = init(jax.random.PRNGKey(0), cfg)
    core = DecodeCore(cfg, RC, params, quantize=False)
    with pytest.raises(NotImplementedError, match="image embeddings"):
        ContinuousBatchingEngine(core, n_slots=2, max_len=MAX_LEN)


def test_telemetry_handles_request_without_first_token(mamba):
    """A request can complete without ever generating a token (evicted
    before its first decode): t_first is None.  telemetry() must emit
    None latency fields for it and exclude it from the TTFT percentiles
    instead of raising (the regression: `None - float` TypeError)."""
    cfg, params, core = mamba
    eng = _engine(core, n_slots=2)
    eng.run(synthetic_requests(cfg, 2, seed=3, prompt_len=(3, 5),
                               new_tokens=(3, 5)), None)
    ghost = Request(rid="ghost", prompt=np.arange(3, dtype=np.int32),
                    max_new_tokens=4)
    ghost.state, ghost.done_reason = "done", "max_tokens"
    ghost.t_submit, ghost.t_done = 0.0, 1.0   # admitted/decoded: never
    eng.completed.append(ghost)
    t = eng.telemetry()                        # must not raise
    by_rid = {r["rid"]: r for r in t["requests"]}
    g = by_rid["ghost"]
    assert g["ttft_s"] is None
    assert g["queue_wait_s"] is None
    assert g["decode_tokens_per_s"] is None
    # percentiles computed over the two real requests only
    real_ttfts = [r["ttft_s"] for r in t["requests"] if r["rid"] != "ghost"]
    assert all(x is not None for x in real_ttfts)
    agg = t["aggregate"]
    assert agg["completed"] == 3
    assert min(real_ttfts) <= agg["ttft_p50_s"] <= max(real_ttfts)
    assert agg["ttft_p95_s"] is not None
