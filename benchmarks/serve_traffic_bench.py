"""Continuous-batching traffic benchmark: throughput vs latency curves.

Drives the slot-scheduled, paged-KV request engine
(repro.serving.ContinuousBatchingEngine) with seeded synthetic ragged
requests arriving as an open-loop Poisson process at several rates, and
records one throughput-vs-latency row per rate (TTFT p50/p95, engine
tokens/s, queue depth, slot occupancy, evictions).  Reduced CPU smoke
configs — the scheduling mechanism is what's measured, not TPU
throughput; the curves' *shape* (TTFT rising with arrival rate while
engine tokens/s saturates) is the trajectory signal.

Three gate families protect the numbers:

  * **parity** — for each parity arch, every request served through the
    continuous engine must produce exactly the tokens the legacy
    fixed-batch `ServeSession(batch=1)` produces for it alone, and the
    first-token logits must match within kernel-numerics tolerance
    (PARITY_ATOL shared with serve_gating_bench via launch.serve).  mamba2-780m is the
    mixed-verdict gated case; mistral-nemo-12b exercises the paged KV
    path across block boundaries.
  * **no-retrace** — after all traffic at all rates,
    `decode_executables == 1`: every ragged pattern hit one compiled
    masked step.
  * **trend** — engine tokens/s per rate and the fixed-batch anchor vs
    the committed BENCH_serve.json baseline must not drop beyond the
    SERVE_TREND_RTOL band (benchmarks.trend); deltas land in the GitHub
    job summary when CI provides one.

Like the gating bench, a run violating any gate is quarantined to
BENCH_serve.json.failed instead of replacing the trusted trajectory
entry, and running the module directly (as CI does) then exits nonzero.
The traffic block *merges* into the existing BENCH_serve.json next to
the gating block — the two benches share the file; each owns its keys.

Run directly:  PYTHONPATH=src python -m benchmarks.serve_traffic_bench
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS, RunConfig, reduced
from repro.launch.compile_cache import configure_compile_cache
from repro.launch.serve import PARITY_ATOL, steady_decode_tokens_per_s
from repro.models import init
from repro.serving import (ContinuousBatchingEngine, DecodeCore,
                           ServeSession, poisson_arrivals,
                           synthetic_requests)

from .sweep_bench import _provenance
from .trend import (committed_baseline, emit_job_summary, render_markdown,
                    trend_report)

# open-loop arrival rates (req/s): under-, near-, and over-saturated
# relative to the smoke engine's service rate (~25ms per tiny request,
# 4 slots) — three points draw the throughput-vs-latency knee: at the
# top rate occupancy passes 0.8 and the admission queue backs up, so
# TTFT percentiles lift off the flat low-rate floor
RATES = (4.0, 32.0, 256.0)
N_REQUESTS = 10            # requests per rate
N_SLOTS = 4
BLOCK_SIZE = 4             # small so smoke prompts cross block edges
PROMPT_RANGE = (4, 10)
NEW_RANGE = (6, 14)
SEED = 0
TRAFFIC_ARCH = "mamba2-780m"      # mixed-verdict gated smoke model
PARITY_ARCHS = ("mamba2-780m", "mistral-nemo-12b")


def _max_len() -> int:
    return PROMPT_RANGE[1] + NEW_RANGE[1] + 2


def _parity_case(arch: str) -> dict:
    """Serve a small batch through the continuous engine and through the
    legacy per-request session; require token equality + first-logits
    agreement."""
    cfg = reduced(ARCHS[arch])
    rc = RunConfig(attn_impl="naive", remat=False)
    params = init(jax.random.PRNGKey(0), cfg)
    max_len = _max_len()
    core = DecodeCore(cfg, rc, params, quantize=True,
                      plan_batch=3, plan_max_len=max_len)
    engine = ContinuousBatchingEngine(core, n_slots=3, max_len=max_len,
                                      block_size=BLOCK_SIZE, seed=SEED,
                                      record_logits=True)
    reqs = synthetic_requests(cfg, 4, seed=SEED,
                              prompt_len=PROMPT_RANGE,
                              new_tokens=NEW_RANGE)
    engine.run(reqs, None)

    legacy = ServeSession(cfg, rc, params, max_len=max_len, batch=1,
                          quantize=True)
    tokens_equal, max_logit_diff = True, 0.0
    for r in sorted(engine.completed, key=lambda r: r.rid):
        prompt = np.asarray(r.prompt)[None]
        legacy.reset()
        ref_logits = legacy.prefill(prompt).astype(jnp.float32)
        legacy.reset()
        ref = legacy.generate(prompt, n_new=r.max_new_tokens)
        got = np.asarray(r.tokens).reshape(-1)
        want = np.asarray(jax.device_get(ref)).reshape(-1)
        tokens_equal &= bool(np.array_equal(got, want))
        d = float(jnp.max(jnp.abs(
            jnp.asarray(r.first_logits, jnp.float32)
            - ref_logits[0, -1])))
        max_logit_diff = max(max_logit_diff, d)
    all_done = len(engine.completed) == len(reqs)
    return {"arch": cfg.name,
            "requests": len(reqs),
            "all_completed": all_done,
            "tokens_equal": tokens_equal,
            "first_logits_max_abs_diff": round(max_logit_diff, 5),
            "parity_ok": bool(tokens_equal and all_done
                              and max_logit_diff <= PARITY_ATOL),
            "decode_executables": engine.decode_executables}


def serve_traffic(write_json: bool = True, rates=RATES,
                  n_requests: int = N_REQUESTS) -> dict:
    cfg = reduced(ARCHS[TRAFFIC_ARCH])
    rc = RunConfig(attn_impl="naive", remat=False)
    params = init(jax.random.PRNGKey(0), cfg)
    max_len = _max_len()
    # fixed-batch anchor FIRST, while the process is fresh: the legacy
    # lockstep session at batch=N_SLOTS on the same weights, timed by
    # the shared helper (warmed, best-of).  Measured after the engine
    # curves it inherits their allocator/cache drag and reads up to 35%
    # low (CPU runs; in-process allocator/cache interference).
    ref_sess = ServeSession(cfg, rc, params, max_len=max_len,
                            batch=N_SLOTS, quantize=True)
    ref_prompt = jax.random.randint(jax.random.PRNGKey(1),
                                    (N_SLOTS, PROMPT_RANGE[1]), 0,
                                    cfg.vocab)
    (ref_tps,) = steady_decode_tokens_per_s([ref_sess], ref_prompt,
                                            NEW_RANGE[1], repeats=5,
                                            warmup=2)
    del ref_sess

    core = DecodeCore(cfg, rc, params, quantize=True,
                      plan_batch=N_SLOTS, plan_max_len=max_len)

    # warm the one executable (jit compile must not pollute the first
    # rate's TTFT) — a throwaway engine over the same core
    warm = ContinuousBatchingEngine(core, n_slots=N_SLOTS,
                                    max_len=max_len,
                                    block_size=BLOCK_SIZE, seed=SEED)
    warm.run(synthetic_requests(cfg, 2, seed=SEED,
                                prompt_len=PROMPT_RANGE,
                                new_tokens=NEW_RANGE), None)

    curves, all_completed = [], True
    executables = set()
    for rate in rates:
        engine = ContinuousBatchingEngine(core, n_slots=N_SLOTS,
                                          max_len=max_len,
                                          block_size=BLOCK_SIZE,
                                          seed=SEED)
        reqs = synthetic_requests(cfg, n_requests, seed=SEED,
                                  prompt_len=PROMPT_RANGE,
                                  new_tokens=NEW_RANGE)
        arrivals = poisson_arrivals(n_requests, rate, seed=SEED)
        t = engine.run(reqs, arrivals)
        agg = t["aggregate"]
        all_completed &= agg["completed"] == n_requests
        executables.add(agg["decode_executables"])
        curves.append({
            "arrival_rate_req_per_s": rate,
            "completed": agg["completed"],
            "ttft_p50_s": agg["ttft_p50_s"],
            "ttft_p95_s": agg["ttft_p95_s"],
            "ttft_mean_s": agg["ttft_mean_s"],
            "engine_tokens_per_s": agg["engine_tokens_per_s"],
            "request_tokens_per_s_mean": agg["request_tokens_per_s_mean"],
            "queue_depth_mean": agg["queue_depth_mean"],
            "queue_depth_max": agg["queue_depth_max"],
            "slot_occupancy_mean": agg["slot_occupancy_mean"],
            "evictions": agg["evictions"],
            "kv_blocks_peak_in_use": agg["kv_blocks"]["peak_in_use"],
            "kv_donation_ok": agg["kv_donation_ok"],
            "decode_step_breakdown": agg["decode_step_breakdown"],
        })

    parity = [_parity_case(a) for a in PARITY_ARCHS]
    retrace_ok = all(e in (1, None) for e in executables) and all(
        p["decode_executables"] in (1, None) for p in parity)

    # perf-trend lane: engine throughput per rate + the fixed-batch
    # anchor vs the committed baseline's traffic block
    base = (committed_baseline() or {}).get("traffic", {})
    base_curves = {c["arrival_rate_req_per_s"]: c
                   for c in base.get("curves", [])}
    pairs = [(f"rate {c['arrival_rate_req_per_s']} engine tokens/s",
              base_curves.get(c["arrival_rate_req_per_s"], {})
              .get("engine_tokens_per_s"),
              c["engine_tokens_per_s"]) for c in curves]
    pairs.append(("fixed-batch reference tokens/s",
                  base.get("fixed_batch_reference_tokens_per_s"), ref_tps))
    trend = trend_report(pairs)
    emit_job_summary(render_markdown("serve_traffic_bench trend", trend))

    traffic = {
        "arch": cfg.name,
        "n_slots": N_SLOTS,
        "block_size": BLOCK_SIZE,
        "requests_per_rate": n_requests,
        "seed": SEED,
        "curves": curves,
        "fixed_batch_reference_tokens_per_s": round(ref_tps, 1),
        "parity": parity,
        "parity_atol": PARITY_ATOL,
        "trend": trend,
        "gates": {
            "parity_ok": all(p["parity_ok"] for p in parity),
            "all_completed": all_completed,
            "decode_executables_ok": retrace_ok,
            "trend_ok": trend["ok"],
        },
        "provenance": _provenance(),
    }
    ok = all(traffic["gates"].values())
    if write_json:
        out = os.environ.get("BENCH_SERVE_OUT", "BENCH_serve.json")
        merged = {}
        if os.path.exists(out):
            try:
                with open(out) as f:
                    merged = json.load(f)
            except (json.JSONDecodeError, OSError):
                merged = {}
        merged["traffic"] = traffic
        if not ok:
            # quarantine: a gate-violating run must not replace the
            # trusted trajectory entry
            out += ".failed"
        with open(out, "w") as f:
            json.dump(merged, f, indent=1)
    return traffic


if __name__ == "__main__":
    ap = argparse.ArgumentParser(
        description="Continuous-batching open-loop traffic benchmark.",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter)
    ap.add_argument("--requests", type=int, default=N_REQUESTS,
                    help="requests per arrival rate")
    ap.add_argument("--rates", type=float, nargs="+", default=list(RATES),
                    help="open-loop Poisson arrival rates (req/s)")
    cli = ap.parse_args()
    configure_compile_cache()
    traffic = serve_traffic(rates=tuple(cli.rates),
                            n_requests=cli.requests)
    print(json.dumps(traffic, indent=1))
    gates = traffic["gates"]
    if not gates["parity_ok"]:
        sys.exit("traffic parity regression: continuous-batching decode "
                 "disagrees with the legacy per-request session")
    if not gates["all_completed"]:
        sys.exit("traffic completeness regression: requests were lost")
    if not gates["decode_executables_ok"]:
        sys.exit("retrace regression: ragged traffic compiled more than "
                 "one masked decode executable")
    if not gates["trend_ok"]:
        sys.exit("perf-trend regression: engine tokens/s dropped beyond "
                 "the SERVE_TREND_RTOL band vs the committed baseline")
