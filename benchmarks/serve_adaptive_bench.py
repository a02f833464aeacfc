"""Adaptive-planning serving benchmark: hot-swapped vs frozen decode plans.

Drives the continuous-batching engine twice over identical seeded
traffic (reduced CPU smoke configs — the swap mechanism is what's
measured, not TPU throughput):

  * **frozen** — the PR-6 path: one `KernelPlanTable` fixed at core
    build time, one compiled executable;
  * **adaptive (no flip)** — the shape-bucketed `PlanService`
    (repro.core.plan_service) consulted every step over a single-bucket
    lattice matching the core's planning shape, so every lookup returns
    the frozen plan: the engine must stay token-EXACT vs the frozen run
    with zero plan swaps (the adaptive machinery may not perturb
    serving when verdicts agree);
  * **adaptive (forced flip)** — an injected `plan_fn` toggles one
    label's verdict on the bucket's first background refresh: the
    engine must hot-swap (plan_swaps >= 1, verdict_flips >= 1) onto a
    second compiled variant without retracing the first
    (`decode_executables == plan_variants == 2` — one program per
    distinct plan table) and still complete every request.

Timing rows record adaptive vs frozen engine tokens/s (the service's
per-step lookup overhead) and the swap latency stats; gates are purely
deterministic (token equality, swap/executable counts, completion).
Like the gating and traffic benches, a gate-violating run quarantines
to BENCH_serve.json.failed instead of replacing the trusted trajectory
entry, and running the module directly (as CI does) then exits nonzero.
The `adaptive` block *merges* into BENCH_serve.json next to the gating
and `traffic` blocks — the three benches share the file; each owns its
keys.

Run directly:  PYTHONPATH=src python -m benchmarks.serve_adaptive_bench
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

import jax

from repro.configs import ARCHS, RunConfig, reduced
from repro.core.plan_service import BucketLattice, PlanService
from repro.launch.compile_cache import configure_compile_cache
from repro.models import init
from repro.serving import (ContinuousBatchingEngine, DecodeCore,
                           synthetic_requests)

from .sweep_bench import _provenance

ARCH = "mamba2-780m"       # mixed-verdict gated smoke model
N_SLOTS = 3
BLOCK_SIZE = 4
N_REQUESTS = 8
PROMPT_RANGE = (4, 10)
NEW_RANGE = (6, 14)
SEED = 0
REFRESH_EVERY = 4          # forced-flip scenario: re-plan after 4 hits


def _max_len() -> int:
    return PROMPT_RANGE[1] + NEW_RANGE[1] + 2


def _requests(cfg, n: int):
    return synthetic_requests(cfg, n, seed=SEED, prompt_len=PROMPT_RANGE,
                              new_tokens=NEW_RANGE)


def _engine(core, service=None):
    return ContinuousBatchingEngine(core, n_slots=N_SLOTS,
                                    max_len=_max_len(),
                                    block_size=BLOCK_SIZE, seed=SEED,
                                    plan_service=service)


def _tokens_by_rid(engine) -> dict:
    return {r.rid: [int(t) for t in r.tokens] for r in engine.completed}


def make_flipping_plan_fn(service_cfg, flip_after: int = 1):
    """A PlanService plan_fn that returns the real batched-sweep verdicts
    for the first `flip_after` builds of a shape, then toggles the
    lexicographically-first label's gate — the deterministic forced-flip
    harness (shared with tests/test_adaptive_planning.py)."""
    from repro.core.llm_workloads import gemms_of_model
    from repro.core.planner import plan_workload
    builds: dict = {}

    def plan_fn(shape):
        decisions = plan_workload(gemms_of_model(service_cfg, shape),
                                  backend="vectorized")
        n = builds.get(shape.name, 0)
        builds[shape.name] = n + 1
        if n < flip_after:
            return decisions
        flip_label = min(d.gemm.label for d in decisions)
        return [dataclasses.replace(d, use_cim=not d.use_cim)
                if d.gemm.label == flip_label else d for d in decisions]

    return plan_fn


def serve_adaptive(write_json: bool = True,
                   n_requests: int = N_REQUESTS) -> dict:
    cfg = reduced(ARCHS[ARCH])
    rc = RunConfig(attn_impl="naive", remat=False)
    params = init(jax.random.PRNGKey(0), cfg)
    max_len = _max_len()
    single_bucket = BucketLattice((N_SLOTS,), (max_len,))

    def fresh_core():
        return DecodeCore(cfg, rc, params, quantize=True,
                          plan_batch=N_SLOTS, plan_max_len=max_len)

    # --- frozen reference (warmed: jit compile must not skew tokens/s) --
    frozen_core = fresh_core()
    _engine(frozen_core).run(_requests(cfg, 2), None)
    frozen_eng = _engine(frozen_core)
    frozen_t = frozen_eng.run(_requests(cfg, n_requests), None)
    frozen_tokens = _tokens_by_rid(frozen_eng)

    # --- adaptive, no flip: single bucket == the frozen planning shape --
    adaptive_core = fresh_core()
    _engine(adaptive_core).run(_requests(cfg, 2), None)
    service = PlanService(cfg, single_bucket, background=False)
    adaptive_eng = _engine(adaptive_core, service)
    adaptive_t = adaptive_eng.run(_requests(cfg, n_requests), None)
    no_flip_ad = adaptive_t["adaptive"]
    no_flip = {
        "engine_tokens_per_s":
            adaptive_t["aggregate"]["engine_tokens_per_s"],
        "completed": adaptive_t["aggregate"]["completed"],
        "tokens_equal": _tokens_by_rid(adaptive_eng) == frozen_tokens,
        "plan_swaps": no_flip_ad["plan_swaps"],
        "verdict_flips": no_flip_ad["service"]["verdict_flips"],
        "bucket_hit_rate": no_flip_ad["service"]["hit_rate"],
        "decode_executables": adaptive_core.batch_decode_executables,
        "swap_latency_s": no_flip_ad["swap_latency_s"],
        "service": no_flip_ad["service"],
    }

    # --- adaptive, forced flip: the bucket's first refresh toggles one
    # verdict; the engine must swap onto a second compiled variant -------
    flip_core = fresh_core()
    flip_service = PlanService(cfg, single_bucket,
                               refresh_every=REFRESH_EVERY,
                               background=False,
                               plan_fn=make_flipping_plan_fn(cfg))
    flip_eng = _engine(flip_core, flip_service)
    flip_t = flip_eng.run(_requests(cfg, n_requests), None)
    flip_ad = flip_t["adaptive"]
    forced_flip = {
        "engine_tokens_per_s": flip_t["aggregate"]["engine_tokens_per_s"],
        "completed": flip_t["aggregate"]["completed"],
        "plan_swaps": flip_ad["plan_swaps"],
        "verdict_flips": flip_ad["service"]["verdict_flips"],
        "plan_variants": flip_core.plan_variants,
        "decode_executables": flip_core.batch_decode_executables,
        "swap_latency_s": flip_ad["swap_latency_s"],
        "service": flip_ad["service"],
    }

    execs = forced_flip["decode_executables"]
    adaptive = {
        "arch": cfg.name,
        "n_slots": N_SLOTS,
        "block_size": BLOCK_SIZE,
        "requests": n_requests,
        "seed": SEED,
        "refresh_every": REFRESH_EVERY,
        "frozen_tokens_per_s": frozen_t["aggregate"]["engine_tokens_per_s"],
        "no_flip": no_flip,
        "forced_flip": forced_flip,
        "gates": {
            # verdict agreement => the adaptive path may not perturb
            # serving at all: identical tokens, zero swaps
            "no_flip_token_parity": bool(no_flip["tokens_equal"]),
            "no_flip_zero_swaps": no_flip["plan_swaps"] == 0,
            # a flip must actually swap...
            "flip_swapped": (forced_flip["plan_swaps"] >= 1
                             and forced_flip["verdict_flips"] >= 1),
            # ...onto exactly one compiled program per distinct plan,
            # never retracing the active variant
            "flip_no_retrace": (execs is None
                                or execs == forced_flip["plan_variants"]
                                == 2),
            "all_completed": (frozen_t["aggregate"]["completed"]
                              == no_flip["completed"]
                              == forced_flip["completed"]
                              == n_requests),
        },
        "provenance": _provenance(),
    }
    ok = all(adaptive["gates"].values())
    if write_json:
        out = os.environ.get("BENCH_SERVE_OUT", "BENCH_serve.json")
        merged = {}
        if os.path.exists(out):
            try:
                with open(out) as f:
                    merged = json.load(f)
            except (json.JSONDecodeError, OSError):
                merged = {}
        merged["adaptive"] = adaptive
        if not ok:
            # quarantine: a gate-violating run must not replace the
            # trusted trajectory entry
            out += ".failed"
        with open(out, "w") as f:
            json.dump(merged, f, indent=1)
    return adaptive


if __name__ == "__main__":
    ap = argparse.ArgumentParser(
        description="Adaptive-planning serving benchmark (hot-swapped vs "
                    "frozen decode plans).",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter)
    ap.add_argument("--requests", type=int, default=N_REQUESTS,
                    help="requests per scenario")
    cli = ap.parse_args()
    configure_compile_cache()
    adaptive = serve_adaptive(n_requests=cli.requests)
    print(json.dumps(adaptive, indent=1))
    gates = adaptive["gates"]
    if not gates["no_flip_token_parity"]:
        sys.exit("adaptive parity regression: agreeing verdicts changed "
                 "the served tokens vs the frozen-plan engine")
    if not gates["no_flip_zero_swaps"]:
        sys.exit("adaptive stability regression: the engine swapped "
                 "plans although no verdict flipped")
    if not gates["flip_swapped"]:
        sys.exit("adaptive swap regression: a forced verdict flip did "
                 "not hot-swap the decode plan")
    if not gates["flip_no_retrace"]:
        sys.exit("retrace regression: plan hot-swap compiled more than "
                 "one program per distinct plan table")
    if not gates["all_completed"]:
        sys.exit("adaptive completeness regression: requests were lost")
