"""The plain float32 references against the serving engine's own step,
prefill token by token and then decode through the cache, at tiny
widths on the CPU, with the benchmark's seeded INT8 weights.

Tolerances, as max |logit difference| (max |logit| is about 4 here):
  * float32 model, dense: 1e-4.  Same weights, float32 throughout; only
    the order of summation differs (measured 4e-6).
  * float32 model, Mamba-2: 0.05.  The program keeps its conv carry in
    bfloat16 whatever the model's dtype (measured 0.015).
  * bfloat16 model as served: 0.25.  Activations round to bfloat16 in
    every layer (measured 0.056 Mamba-2, 0.076 dense).
A wrong equation is far outside all of them: the Mamba-2 reference with
the gate on the other side of the norm differs by 1.06-1.08.
"""
import numpy as np
import pytest

from bench.lib import weights
from bench.lib.spec import Bench

from conftest import TINY_DENSE, TINY_SSM, tiny_config

CASES = {("ssm", "float32"): 0.05, ("ssm", "bfloat16"): 0.25,
         ("dense", "float32"): 1e-4, ("dense", "bfloat16"): 0.25}
REAL = {"ssm": ("mamba2-780m", TINY_SSM),
        "dense": ("mistral-nemo-12b-pp4", TINY_DENSE)}


def setup_model(family, dtype):
    real, sizes = REAL[family]
    conf = tiny_config(real, "tiny", sizes)
    conf.update(param_dtype=dtype, compute_dtype=dtype)
    conf["reduced"].update(param_dtype="test", compute_dtype="test")
    cfg = weights.model_config(conf)
    return conf, cfg, weights.make_params(cfg, seed=2 ** 33 + 5)


def engine_logits(cfg, params, prompt, n_new, kv_dtype):
    """Feed the prompt through the engine's batch step one token a step,
    then decode greedily; returns the fed tokens and every step's
    logits."""
    import jax.numpy as jnp
    from repro.configs import RunConfig
    from repro.models.model import init_paged_cache
    from repro.serving import DecodeCore
    rc = RunConfig(attn_impl="naive", remat=False, kv_cache_dtype=kv_dtype)
    core = DecodeCore(cfg, rc, params, quantize=True, plan_batch=2,
                      plan_max_len=16)
    step = core.batch_step_for(core.plan_table)
    b, bs, nb = prompt.shape[0], 4, 4
    cache = init_paged_cache(cfg, rc, b, b * nb, bs)
    tables = np.arange(b * nb, dtype=np.int32).reshape(b, nb)
    fed, out = [], []
    tok = prompt[:, :1]
    for t in range(prompt.shape[1] + n_new):
        logits, cache = step(core.params, cache, jnp.asarray(tok),
                             np.full(b, t, np.int32), np.ones(b, bool),
                             tables)
        last = np.asarray(logits[:, -1], np.float32)
        fed.append(tok[:, 0])
        out.append(last)
        tok = (prompt[:, t + 1:t + 2] if t + 1 < prompt.shape[1]
               else last.argmax(-1)[:, None].astype(np.int32))
    return np.stack(fed, 1), np.stack(out, 1)


@pytest.mark.parametrize("family,dtype", sorted(CASES))
def test_engine_matches_reference(family, dtype):
    conf, cfg, params = setup_model(family, dtype)
    prompt = np.random.default_rng(1).integers(
        0, cfg.vocab, (2, 8)).astype(np.int32)
    fed, got = engine_logits(cfg, params, prompt, 4,
                             "float32" if dtype == "float32" else "bfloat16")
    ref = Bench().reference(conf["family"]).logits(conf, params, fed)
    assert np.abs(got - ref).max() <= CASES[family, dtype]
    # the decoded (greedy) tokens sit at or near the reference's best
    targets = fed[:, 1:].copy()
    targets[:, :prompt.shape[1] - 1] = -1
    gaps = Bench().reference(conf["family"]).logit_gaps(
        conf, params, fed[:, :-1], targets)
    assert np.nanmax(gaps) <= 2 * CASES[family, dtype]


def test_reference_tells_the_gate_order_apart():
    conf, cfg, params = setup_model("ssm", "float32")
    prompt = np.random.default_rng(1).integers(
        0, cfg.vocab, (2, 8)).astype(np.int32)
    fed, got = engine_logits(cfg, params, prompt, 4, "float32")
    ref = Bench().reference("ssm")
    wrong = ref.logits(dict(conf, norm_before_gate=False), params, fed)
    assert np.abs(got - wrong).max() > 10 * CASES["ssm", "float32"]


def test_the_state_control_rounds_the_state():
    """The second control of a Mamba-2 cell: the reference with its SSM
    state rounded to bfloat16 after every update, put in the program's
    place.  At tiny widths it moves the logits by ~0.008 and flips no
    token, so it is read on the chip, at the cell's own size."""
    from types import SimpleNamespace

    from bench.lib import check
    conf, cfg, params = setup_model("ssm", "float32")
    ref = Bench().reference("ssm")
    inputs = np.random.default_rng(1).integers(
        0, cfg.vocab, (2, 64)).astype(np.int32)
    exact = ref.logits(conf, params, inputs)
    rounded = ref.logits(conf, params, inputs, state_dtype="bfloat16")
    assert 0 < np.abs(exact - rounded).max() < 0.05
    picked = [SimpleNamespace(req=SimpleNamespace(prompt=row[:8],
                                                  tokens=row[8:20]))
              for row in inputs]
    gaps = check.control_gaps(ref, conf, params, picked, 32,
                              state_dtype="bfloat16")
    assert gaps.shape == (24,) and (gaps >= 0).all()
