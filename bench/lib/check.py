"""Whether what the timed engine served is right.

Once the window has closed and the program's state is freed, a sample of
the requests it finished, drawn from the seed and always holding the
longest, goes through the family's plain float32 reference, teacher
forced over each prompt and its served tokens.  At every served position
the reference gives the gap by which the served token's logit lies below
its best logit.  The widest gap over the sample is the number compared.
Greedy decoding serves the argmax of the program's own logits, so in a
sound program that gap is rounding; a program off by a precision step
serves tokens the reference ranks well below its best.
"""
from __future__ import annotations

import numpy as np

# the sample: the longest finished request, then others in seed order
# until it holds this many served tokens (or runs out of requests)
SAMPLE_TOKENS = 400
SAMPLE_MAX_REQUESTS = 8


def finished(recs) -> list:
    """Requests the engine finished with every token they asked for."""
    return [r for r in recs if r.req.state == "done"
            and len(r.req.tokens) == r.req.max_new_tokens]


def sample(recs, seed: int) -> list:
    done = finished(recs)
    if not done:
        return []
    size = lambda r: r.req.prompt_len + len(r.req.tokens)   # noqa: E731
    longest = max(done, key=size)
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng(seed % 2 ** 64).permutation(len(rest))
    picked, tokens = [longest], len(longest.req.tokens)
    for i in order:
        if tokens >= SAMPLE_TOKENS or len(picked) >= SAMPLE_MAX_REQUESTS:
            break
        picked.append(rest[i])
        tokens += len(rest[i].req.tokens)
    return picked


def teacher_forced(prompt, served, length: int):
    """Inputs (prompt then all served tokens but the last) and targets
    (-1 except where a served token was produced), padded to `length`."""
    seq = np.concatenate([np.asarray(prompt, np.int32),
                          np.asarray(served, np.int32)[:-1]])
    if len(seq) > length:
        raise ValueError(f"sequence of {len(seq)} exceeds {length}")
    inputs = np.zeros(length, np.int32)
    inputs[:len(seq)] = seq
    targets = np.full(length, -1, np.int32)
    p = len(prompt)
    targets[p - 1:p - 1 + len(served)] = np.asarray(served, np.int32)
    return inputs, targets


def served_gaps(reference, conf: dict, params, picked, length: int
                ) -> np.ndarray:
    """The gap of every served token of `picked` (flat array)."""
    if not picked:
        return np.zeros(0, np.float32)
    pairs = [teacher_forced(r.req.prompt, [int(t) for t in r.req.tokens],
                            length) for r in picked]
    inputs = np.stack([p[0] for p in pairs])
    targets = np.stack([p[1] for p in pairs])
    gaps = np.asarray(reference.logit_gaps(conf, params, inputs, targets))
    return gaps[targets >= 0]


def control_gaps(reference, conf: dict, params, picked, length: int,
                 **control) -> np.ndarray:
    """A control put in the program's place: at every served position of
    `picked`, the float32 reference's gap of the token that the reference
    run with `control` (a lower precision, e.g. state_dtype=bfloat16)
    puts first (flat array)."""
    if not picked:
        return np.zeros(0, np.float32)
    pairs = [teacher_forced(r.req.prompt, [int(t) for t in r.req.tokens],
                            length) for r in picked]
    inputs = np.stack([p[0] for p in pairs])
    targets = np.stack([p[1] for p in pairs])
    ref = reference.logits(conf, params, inputs)
    first = np.argmax(reference.logits(conf, params, inputs, **control), -1)
    gaps = ref.max(-1) - np.take_along_axis(ref, first[..., None], -1)[..., 0]
    return gaps[targets >= 0]


def cache_bits_short(cache, stated: dict) -> int:
    """Bits by which the served cache falls short of the precision the
    configuration states for it ({"state": "float32"}: every cache entry's
    "state" leaf): summed over the stated leaves, and a stated leaf that
    no entry holds counts all its bits."""
    short = 0
    for key, dtype in stated.items():
        want = 8 * np.dtype(dtype).itemsize
        held = [entry[key] for entry in cache if key in entry]
        short += (sum(max(0, want - 8 * leaf.dtype.itemsize)
                      for leaf in held) if held else want)
    return short


def judge(gaps: np.ndarray, limit: float, bits_short: int = 0
          ) -> tuple[bool, dict]:
    """The verdict, and each number compared beside its limit.  No token
    to compare, or a gap that is not finite, is not correct; nor is a
    cache held below the precision the configuration states (limit 0)."""
    ok = bool(gaps.size and np.isfinite(gaps).all())
    widest = float(np.max(gaps)) if ok else None
    return (ok and widest <= limit and bits_short <= 0,
            {"cache_bits_short": {"value": bits_short, "limit": 0},
             "max_gap": {"value": widest, "limit": limit}})
