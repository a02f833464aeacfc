"""The default traffic generator: it reads a mix file and a cell, and a seed.

A mix file (`traffic/<mix>.json`) holds only parameters:

  {"arrival": {"kind": "backlog"} | {"kind": "poisson"}
              | {"kind": "gamma", "cv": 2.0},
   "prompt_len": {"median": 128, "sigma": 0.8, "min": 16, "max": 512},
   "output_len": {"median": 96, "sigma": 0.8, "min": 16, "max": 384},
   "block": 64, "chunk": 8,
   "preroll_s": 10,                     (open loop, optional)
   "prime": "steady",                   (backlog, optional)
   "generator": "<name>"}               (optional, see below)

Lengths are log-normal, clipped to [min, max].  Open-loop arrivals come
at the cell's `rate_req_s`; "backlog" means the harness keeps the queue
at least `slots` deep.  So that the seed changes the order of the work
and not the work itself, every block of `block` requests carries the same
multiset of prompt lengths, output lengths and inter-arrival gaps:
log-normal lengths at the block's quantiles, exponential or Gamma gaps
at theirs, and gaps scaled so each block's mean is exactly 1 / rate.
Each prompt length is paired with an output length by a fixed draw, so
a block's requests are the same pairs for every seed.  The seed permutes
each block and draws the prompt tokens.  The permutation is stratified:
a block falls into block/chunk chunks that each hold one value of every
stratum of sorted values, with nearly equal totals, and the seed orders
the chunks and each chunk's values.  So any run of whole chunks, not only
a whole block, carries nearly the same work whatever the seed.  With
`chunk` equal to `block` the permutation is free: consecutive gaps are
then as independent as draws of the arrival process, and bursts cluster
as they do in it.

The window opens on a loaded engine, as a long-running deployment is:
an open-loop schedule starts `preroll_s` before the window opens, and a
backlog with "prime": "steady" first gives every slot one request of
`primed()`, which leaves the slots part-way through their requests as a
long-running backlog leaves them.

A mix with a "generator" key is read by `generators/<name>.py` instead,
a module with the `schedule` (and, for a primed backlog, `primed`)
functions below: a new arrival structure is a new file.
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist
from typing import Iterator

import numpy as np

# the seed's second stream, for the requests that prime the slots
PRIME_STREAM = 1
# fixed draws (not the run's seed): the sample the Gamma gap quantiles
# are read from, and which output length goes with which prompt length
GAMMA_SET_SEED = 20240117
GAMMA_DRAW = 1 << 20
PAIRING_SEED = 20240118


@dataclasses.dataclass
class Planned:
    """One request of the schedule: when it is due (seconds after the
    window opens; None for backlog traffic), its prompt and how many
    tokens it asks for."""
    index: int
    due: float | None
    prompt: np.ndarray
    max_new: int


def quantile_lengths(dist: dict, n: int) -> np.ndarray:
    """n log-normal lengths at the quantiles (i + 1/2) / n, clipped."""
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    vals = dist["median"] * np.exp(dist["sigma"] * z)
    return np.clip(np.rint(vals), dist["min"], dist["max"]).astype(int)


def gap_set(arrival: dict, n: int) -> np.ndarray:
    """n inter-arrival gaps in units of the mean gap (mean exactly 1)."""
    kind = arrival["kind"]
    if kind == "poisson":
        g = -np.log1p(-(np.arange(n) + 0.5) / n)
    elif kind == "gamma":
        # the Gamma quantiles (i + 1/2) / n, read off one large fixed
        # draw (numpy's legacy generator keeps its stream across versions)
        shape = 1.0 / arrival["cv"] ** 2
        draw = np.random.RandomState(GAMMA_SET_SEED).gamma(
            shape, 1.0, GAMMA_DRAW)
        g = np.quantile(draw, (np.arange(n) + 0.5) / n)
    else:
        raise ValueError(f"arrival kind {kind!r} has no gaps")
    return g * (n / g.sum())


def stratified(n: int, chunk: int, rng) -> np.ndarray:
    """A permutation of range(n) (indices of n sorted values) in which
    every `chunk` consecutive entries hold one index of each of `chunk`
    strata, and all chunks carry nearly the same total: stratum i gives
    chunk j its j-th index in even strata and its j-th from the top in
    odd ones.  The seed orders the chunks and each chunk's entries."""
    m = n // chunk
    if m * chunk != n:
        raise ValueError(f"block {n} is not a multiple of chunk {chunk}")
    strata = np.arange(n).reshape(chunk, m)
    strata[1::2] = strata[1::2, ::-1]
    return np.concatenate([rng.permutation(strata[:, j])
                           for j in rng.permutation(m)])


def _pairs(mix: dict) -> tuple[np.ndarray, np.ndarray]:
    """A block's (prompt, output) length pairs, prompts ascending."""
    block = int(mix["block"])
    prompts = np.sort(quantile_lengths(mix["prompt_len"], block))
    outputs = np.random.default_rng(PAIRING_SEED).permutation(
        quantile_lengths(mix["output_len"], block))
    return prompts, outputs


def schedule(mix: dict, cell: dict, seed: int, vocab: int
             ) -> Iterator[Planned]:
    """The cell's requests in order, without end.  Due times are
    seconds after the schedule starts (None for backlog traffic)."""
    block, chunk = int(mix["block"]), int(mix["chunk"])
    prompts, outputs = _pairs(mix)
    arrival = mix["arrival"]
    backlog = arrival["kind"] == "backlog"
    if not backlog:
        rate = float(cell["rate_req_s"])
        if not rate > 0:
            raise ValueError(f"open-loop mix needs rate_req_s > 0, "
                             f"got {rate}")
        gaps = np.sort(gap_set(arrival, block) / rate)
    if max(prompts) + max(outputs) > cell["max_len"]:
        raise ValueError(
            f"longest request {max(prompts)} + {max(outputs)} tokens "
            f"exceeds the cell's max_len {cell['max_len']}")
    rng = np.random.default_rng(seed % 2 ** 64)
    t, i = 0.0, 0
    while True:
        order = stratified(block, chunk, rng)
        g = None if backlog else gaps[stratified(block, chunk, rng)]
        for j, k in enumerate(order):
            due = None
            if not backlog:
                t += float(g[j])
                due = t
            tokens = rng.integers(0, vocab, size=int(prompts[k]),
                                  dtype=np.int32)
            yield Planned(i, due, tokens, int(outputs[k]))
            i += 1


def primed(mix: dict, cell: dict, seed: int, vocab: int) -> list[Planned]:
    """One request per slot: what is left of the requests a long-running
    backlog holds in its slots at a moment picked at random.

    A request of prompt p and output o holds its slot for p + o - 1
    steps (one token a step; the last prompt token's step gives the
    first output).  At a random moment a slot is at each of the block's
    request-steps alike.  The slots take `slots` of those request-steps
    evenly spaced over all of them (requests in order of prompt length),
    the same for every seed: a slot at step a of (p, o) gets what is
    left, a prompt of p - a tokens and o outputs while a < p, else a
    1-token prompt and p + o - 1 - a outputs.  The seed orders them and
    draws their tokens; their indices are negative."""
    prompts, outputs = _pairs(mix)
    steps = prompts + outputs - 1
    ends = np.cumsum(steps)
    n = int(cell["slots"])
    marks = ((np.arange(n) + 0.5) * ends[-1] / n).astype(int)
    k = np.searchsorted(ends, marks, side="right")
    age = marks - (ends[k] - steps[k])
    in_prompt = age < prompts[k]
    left_prompt = np.where(in_prompt, prompts[k] - age, 1)
    left_out = np.where(in_prompt, outputs[k], steps[k] - age)
    rng = np.random.default_rng((seed % 2 ** 64, PRIME_STREAM))
    return [Planned(-1 - j, None,
                    rng.integers(0, vocab, size=int(left_prompt[i]),
                                 dtype=np.int32), int(left_out[i]))
            for j, i in enumerate(rng.permutation(n))]
