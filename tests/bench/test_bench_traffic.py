"""The traffic generator: seeded, the same work for every seed, and the
lengths and arrivals its mix files state."""
import itertools
import json
import os

import numpy as np
import pytest

from bench.lib.traffic import gap_set, primed, quantile_lengths, schedule

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
MIXES = ("offline", "chat_burst", "chat_steady")
CELL = {"slots": 16, "max_len": 2048, "block_size": 16, "rate_req_s": 4.0}
BIG_SEED = 2 ** 31 + 12345


def mix(name):
    with open(os.path.join(REPO, "bench", "traffic", f"{name}.json")) as f:
        return json.load(f)


def take(m, seed, n, vocab=1000):
    return list(itertools.islice(schedule(m, CELL, seed, vocab), n))


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_schedule(name):
    a, b = take(mix(name), BIG_SEED, 130), take(mix(name), BIG_SEED, 130)
    for x, y in zip(a, b):
        assert x.due == y.due and x.max_new == y.max_new
        assert np.array_equal(x.prompt, y.prompt)
    c = take(mix(name), BIG_SEED + 1, 130)
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_gets_the_same_work_per_block(name):
    m = mix(name)
    block = m["block"]
    for seed in (1, 2, BIG_SEED):
        reqs = take(m, seed, 2 * block)
        for k in range(2):
            part = reqs[k * block:(k + 1) * block]
            assert sorted(len(r.prompt) for r in part) == sorted(
                quantile_lengths(m["prompt_len"], block))
            assert sorted(r.max_new for r in part) == sorted(
                quantile_lengths(m["output_len"], block))


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_gets_the_same_requests_per_block(name):
    m = mix(name)
    block = m["block"]
    pairs = [sorted((len(r.prompt), r.max_new)
                    for r in take(m, seed, block)) for seed in (1, BIG_SEED)]
    assert pairs[0] == pairs[1]


@pytest.mark.parametrize("name", MIXES)
def test_every_chunk_carries_one_value_of_each_stratum(name):
    m = mix(name)
    block, chunk = m["block"], m["chunk"]
    strata = np.sort(quantile_lengths(m["prompt_len"], block)).reshape(
        chunk, block // chunk)
    for seed in (1, BIG_SEED):
        reqs = take(m, seed, block)
        for k in range(block // chunk):
            got = sorted(len(r.prompt) for r in reqs[k * chunk:
                                                     (k + 1) * chunk])
            for i, n in enumerate(got):
                assert strata[i, 0] <= n <= strata[i, -1]


def test_chunks_carry_nearly_the_same_work():
    m = mix("offline")
    block, chunk = m["block"], m["chunk"]
    vals = quantile_lengths(m["prompt_len"], block)
    # no chunk's total is further from the mean than the widest stratum
    width = np.ptp(np.sort(vals).reshape(chunk, block // chunk), 1).max()
    for seed in (1, 2, BIG_SEED):
        reqs = take(m, seed, block)
        totals = [sum(len(r.prompt) for r in reqs[k:k + chunk])
                  for k in range(0, block, chunk)]
        assert max(totals) - min(totals) <= width


@pytest.mark.parametrize("name", MIXES)
def test_lengths_follow_the_mix(name):
    m = mix(name)
    reqs = take(m, 7, m["block"])
    for key, vals in (("prompt_len", [len(r.prompt) for r in reqs]),
                      ("output_len", [r.max_new for r in reqs])):
        d = m[key]
        assert min(vals) >= d["min"] and max(vals) <= d["max"]
        assert abs(np.median(vals) - d["median"]) <= 0.1 * d["median"]
    assert all(int(r.prompt.max()) < 1000 for r in reqs)


def test_open_loop_rate_and_burstiness():
    for name, cv in (("chat_steady", 1.0), ("chat_burst", 2.0)):
        m = mix(name)
        reqs = take(m, BIG_SEED, 4 * m["block"])
        due = np.array([r.due for r in reqs])
        gaps = np.diff(np.concatenate([[0.0], due]))
        # each block's gaps average exactly 1 / rate
        assert due[m["block"] - 1] == pytest.approx(
            m["block"] / CELL["rate_req_s"])
        assert due[-1] == pytest.approx(len(reqs) / CELL["rate_req_s"])
        assert gaps.std() / gaps.mean() == pytest.approx(cv, rel=0.25)
    g = gap_set({"kind": "poisson"}, 64)
    assert g.mean() == pytest.approx(1.0)


def _dispersion(due, width):
    """Variance over mean of the arrivals counted in bins of `width`."""
    counts = np.bincount((np.asarray(due) // width).astype(int))[:-1]
    return counts.var() / counts.mean()


def test_bursts_cluster_as_independent_gamma_gaps_do():
    """chat_burst's gaps are a free permutation of the Gamma quantiles:
    counted over bins of 8 mean gaps, arrivals spread as widely as those
    of independent Gamma draws of CV 2 (the stratified order of
    chat_burst's first version held each 8 arrivals to 8 mean gaps)."""
    m = mix("chat_burst")
    cell = dict(CELL, rate_req_s=1.0)
    n = 200 * m["block"]
    iid = np.cumsum(np.random.default_rng(0).gamma(0.25, 4.0, n))
    want = _dispersion(iid, 8.0)
    for seed in (1, BIG_SEED):
        due = [r.due for r in itertools.islice(
            schedule(m, cell, seed, 10), n)]
        assert _dispersion(due, 8.0) == pytest.approx(want, rel=0.2)
    regular = dict(m, chunk=8)
    due = [r.due for r in itertools.islice(schedule(regular, cell, 1, 10), n)]
    assert _dispersion(due, 8.0) < 0.7 * want


def test_primed_slots_hold_what_a_steady_backlog_leaves():
    m = mix("offline")
    cell = dict(CELL, slots=64)
    runs = [primed(m, cell, seed, 1000) for seed in (1, 2, BIG_SEED)]
    work = [sorted((len(r.prompt), r.max_new) for r in reqs) for reqs in runs]
    assert work[0] == work[1] == work[2]            # the seed orders them
    assert all(len(reqs) == 64 for reqs in runs)
    assert {r.index for r in runs[0]} == set(range(-64, 0))
    # a slot is in its request's output part for the share of all
    # request-steps that are output steps
    prompts = quantile_lengths(m["prompt_len"], m["block"])
    outputs = quantile_lengths(m["output_len"], m["block"])
    share = (outputs.sum() - m["block"]) / (prompts.sum() + outputs.sum()
                                             - m["block"])
    decoding = np.mean([len(r.prompt) == 1 for r in runs[0]])
    assert decoding == pytest.approx(share, abs=0.05)
    # and has up to a whole request left
    longest = prompts.max() + outputs.max()
    assert all(len(r.prompt) + r.max_new <= longest for r in runs[0])


def test_backlog_has_no_due_times():
    assert all(r.due is None for r in take(mix("offline"), 3, 20))


def test_a_request_longer_than_the_cell_is_refused():
    with pytest.raises(ValueError, match="max_len"):
        next(schedule(mix("offline"), dict(CELL, max_len=512), 1, 100))
