"""Serving CLI: batched generation with KV caches (deliverable b).

  PYTHONPATH=src python -m repro.launch.serve --arch mistral-nemo-12b \
      --smoke --batch 4 --prompt-len 16 --new-tokens 32

--quantize runs the planner-gated INT8 session (verdicts routed into the
jitted decode step) and prints the per-label route report plus
gated-vs-ungated decode tokens/s.

--requests N switches to the continuous-batching traffic mode: N
synthetic ragged requests (seeded by --seed, so runs are reproducible)
arrive as an open-loop Poisson process at --arrival-rate req/s and are
served by the slot-scheduled, paged-KV request engine
(repro.serving.ContinuousBatchingEngine); the report carries per-request
TTFT / queue wait / tokens/s plus engine-level queue depth, slot
occupancy, KV-block usage and eviction counts.  All defaults are
documented in --help.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp

from ..configs import ARCHS, RunConfig, reduced
from ..models import init
from ..serving import (CIM_ROUTE, ContinuousBatchingEngine, DecodeCore,
                       ServeSession, cim_fraction, poisson_arrivals,
                       synthetic_requests)
from ..serving.engine import _token_struct
from .compile_cache import configure_compile_cache

# gated vs ungated logits differ only by kernel numerics (Pallas vs XLA
# f32 accumulation order); logits are O(1) scale.  Calibrated on the
# reduced (4-layer) configs: at full width the bf16 activations of the
# model itself move logits further (chip_smoke.py bounds the gated
# route's error against an f32 reference by the ungated route's + this)
PARITY_ATOL = 0.05


def steady_decode_tokens_per_s(sessions, prompt, n_tokens: int,
                               repeats: int = 3,
                               warmup: int = 0) -> list[float]:
    """Steady-state decode throughput per session, best of `repeats`
    timed samples of `n_tokens` decode steps each.

    Each session's prefill warms its one jitted executable and fills the
    cache, so every timed token is a pure decode step — first-run jit
    compile never pollutes the number (gated and ungated programs
    compile differently, so timing generate() cold would mostly compare
    compilers).  `warmup` extra *untimed* decode steps per session after
    prefill soak residual first-call overhead (allocator warm-up, dtype
    promotion caches) for callers that want even flatter samples.
    Samples ALTERNATE across the sessions so transient machine
    contention degrades all of them symmetrically: timing back-to-back
    once recorded a 2.7x split between two byte-identical programs.

    The timing loop behind the fixed-batch report's gated and ungated
    decode tokens/s (`run_fixed_batch`)."""
    if n_tokens < 1:
        raise ValueError(f"n_tokens must be >= 1, got {n_tokens}")
    if repeats < 1:
        raise ValueError(f"repeats must be >= 1, got {repeats}")
    for s in sessions:
        s.reset()
        s.prefill(prompt)
    cfg = sessions[0].cfg
    tok = jnp.zeros(_token_struct(cfg, prompt.shape[0]).shape, jnp.int32)

    def sample(s, n):
        t0 = time.perf_counter()
        for _ in range(n):
            logits, s.cache = s._step(s.params, s.cache, tok,
                                      jnp.int32(s.pos))
        jax.block_until_ready(logits)
        return time.perf_counter() - t0

    if warmup:
        for s in sessions:
            sample(s, warmup)
    best = [float("inf")] * len(sessions)
    for _ in range(repeats):
        for i, s in enumerate(sessions):
            best[i] = min(best[i], sample(s, n_tokens))
    return [prompt.shape[0] * n_tokens / b for b in best]


def run_traffic(cfg, rc, params, args) -> dict:
    """Continuous-batching traffic mode: synthetic open-loop arrivals
    through the slot-scheduled paged-KV engine; returns the serve report
    dict."""
    max_len = args.max_len or (args.prompt_len + args.new_tokens + 1)
    core = DecodeCore(cfg, rc, params, quantize=args.quantize,
                      plan_batch=args.slots, plan_max_len=max_len)
    engine = ContinuousBatchingEngine(
        core, n_slots=args.slots, max_len=max_len,
        block_size=args.block_size, n_kv_blocks=args.kv_blocks,
        seed=args.seed)
    reqs = synthetic_requests(
        cfg, args.requests, seed=args.seed,
        prompt_len=(max(1, args.prompt_len // 2), args.prompt_len),
        new_tokens=(max(1, args.new_tokens // 2), args.new_tokens),
        temperature=args.temperature)
    arrivals = poisson_arrivals(args.requests, args.arrival_rate,
                                seed=args.seed)
    telemetry = engine.run(reqs, arrivals)
    report = {
        "arch": cfg.name,
        "mode": "continuous-batching",
        "requests": args.requests,
        "arrival_rate_req_per_s": args.arrival_rate,
        "seed": args.seed,
        "traffic": telemetry,
        "planner_cache": core.plan_cache_telemetry,
    }
    if args.quantize:
        routes = core.route_report(args.slots, engine.max_len)
        report["gating"] = {
            "routes": routes,
            "cim_routed": sum(r["route"] == CIM_ROUTE
                              for r in routes.values()),
            "cim_routed_fraction": cim_fraction(routes),
        }
    return report


def build_parser() -> argparse.ArgumentParser:
    """The serve CLI's options; `serve(build_parser().parse_args(argv))`
    is what `main` runs, so an in-process caller (chip_smoke.py) drives
    exactly the CLI's path."""
    ap = argparse.ArgumentParser(
        description="Serve a model: fixed-batch demo (default) or "
                    "continuous-batching synthetic traffic "
                    "(--requests N).",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter)
    ap.add_argument("--arch", default="mistral-nemo-12b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16,
                    help="prompt length (traffic mode: the max of the "
                         "ragged range [prompt-len/2, prompt-len])")
    ap.add_argument("--new-tokens", type=int, default=32,
                    help="tokens to generate (traffic mode: the max of "
                         "the ragged range [new-tokens/2, new-tokens])")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--kv-cache-dtype", default="bfloat16")
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds weights AND the synthetic traffic "
                         "(request shapes, arrival process, sampling) — "
                         "same seed, same run")
    ap.add_argument("--quantize", action="store_true",
                    help="INT8 weights + planner-gated kernel routing "
                         "inside the jitted decode step")
    # --- continuous-batching traffic mode ---
    ap.add_argument("--requests", type=int, default=0,
                    help="synthetic traffic mode: number of requests to "
                         "serve through the continuous-batching engine "
                         "(0 = legacy fixed-batch demo)")
    ap.add_argument("--arrival-rate", type=float, default=8.0,
                    help="open-loop Poisson arrival rate in requests/s "
                         "(0 = all requests arrive at t=0)")
    ap.add_argument("--slots", type=int, default=4,
                    help="decode slots (the fixed jitted batch size the "
                         "scheduler packs requests into)")
    ap.add_argument("--block-size", type=int, default=8,
                    help="paged-KV block size in tokens")
    ap.add_argument("--kv-blocks", type=int, default=None,
                    help="KV pool capacity in blocks (default: full "
                         "provisioning, slots * ceil(max-len/block-"
                         "size))")
    ap.add_argument("--max-len", type=int, default=0,
                    help="per-request length cap in traffic mode "
                         "(0 = prompt-len + new-tokens + 1)")
    return ap


def run_fixed_batch(cfg, rc, params, args) -> dict:
    """Fixed-batch mode: one lockstep `ServeSession`; returns the serve
    report dict.  With --quantize the report's "gating" block carries
    the per-label executed routes, gated-vs-ungated logits parity and
    decode tokens/s, and how many Mosaic kernels the gated decode step
    lowered to."""
    nimg = cfg.vision.n_image_tokens if cfg.family == "vlm" else 0
    max_len = args.prompt_len + args.new_tokens + 1
    sess = ServeSession(cfg, rc, params, max_len=max_len,
                        batch=args.batch, n_image_tokens=nimg,
                        quantize=args.quantize)
    key = jax.random.PRNGKey(args.seed)
    if cfg.family == "audio":
        prompt = jax.random.randint(
            key, (args.batch, args.prompt_len, cfg.audio.n_codebooks),
            0, cfg.vocab)
    else:
        prompt = jax.random.randint(
            key, (args.batch, args.prompt_len), 0, cfg.vocab)
    t0 = time.perf_counter()
    out = sess.generate(prompt, n_new=args.new_tokens,
                        temperature=args.temperature, seed=args.seed)
    out = jax.device_get(out)
    dt = time.perf_counter() - t0
    plan = sess.kernel_plan
    report = {
        "arch": cfg.name, "generated_shape": list(out.shape),
        "tokens_per_s": args.batch * args.new_tokens / dt,
        "tokens_in_vocab": bool(((out >= 0) & (out < cfg.vocab)).all()),
        "sample_row": [int(x) for x in out[0].reshape(-1)[:16]],
        # what/when/where gates + planner-cache hit/miss telemetry (LRU
        # sizing is driven by these counters under production traffic).
        # The engine block inside carries the streaming-chunk accounting
        # and, on a multi-host mesh, the per-process shard balance.
        "kernel_plan": {lab: bool(d.use_cim) for lab, d in plan.items()},
        "planner_cache": sess.plan_cache_telemetry,
    }
    if jax.process_count() > 1:
        # pod-scale run: record which host printed this report and the
        # process topology next to the per-host cache counters above
        from . import distributed as dist
        report["distributed"] = dist.distributed_info()
    if args.quantize:
        # per-label executed routes + gated-vs-ungated parity and decode
        # throughput: the ungated session keeps the same INT8 weights,
        # so any delta is purely the verdict-driven kernel routing (both
        # sessions are warmed; jit compile is excluded from tokens/s)
        routes = sess.route_report()
        ungated = ServeSession(cfg, rc, params, max_len=max_len,
                               batch=args.batch, n_image_tokens=nimg,
                               quantize=True, gated=False)
        sess.reset()
        lg = sess.prefill(prompt).astype(jnp.float32)
        lu = ungated.prefill(prompt).astype(jnp.float32)
        parity = float(jnp.max(jnp.abs(lg - lu)))
        finite = bool(jnp.isfinite(lg).all() & jnp.isfinite(lu).all())
        lowered = sess.core._step.lower(
            sess.params, sess.cache, prompt[:, :1], jnp.int32(sess.pos))
        tps_g, tps_u = steady_decode_tokens_per_s(
            (sess, ungated), prompt, args.new_tokens)
        report["gating"] = {
            "routes": routes,
            "cim_routed": sum(r["route"] == CIM_ROUTE
                              for r in routes.values()),
            "cim_routed_fraction": cim_fraction(routes),
            # Mosaic kernels in the gated decode step (0 in CPU
            # interpret mode, where Pallas lowers to plain HLO)
            "decode_step_tpu_custom_calls":
                lowered.as_text().count("tpu_custom_call"),
            "parity_max_abs_diff": parity,
            "logits_finite": finite,
            "decode_executables": sess.decode_executables,
            "tokens_per_s_gated": tps_g,
            "tokens_per_s_ungated": tps_u,
        }
    return report


def serve(args) -> dict:
    """The serve CLI from parsed options to its report dict: builds the
    model from --arch (--smoke: `reduced` widths) with weights drawn
    from --seed, then runs traffic mode (--requests N) or the
    fixed-batch mode."""
    cfg = ARCHS[args.arch]
    if args.smoke:
        cfg = reduced(cfg)
    rc = RunConfig(attn_impl="naive", remat=False,
                   kv_cache_dtype=args.kv_cache_dtype)
    params = init(jax.random.PRNGKey(args.seed), cfg)
    if args.requests > 0:
        return run_traffic(cfg, rc, params, args)
    return run_fixed_batch(cfg, rc, params, args)


def main(argv=None):
    ap = build_parser()
    args = ap.parse_args(argv)
    configure_compile_cache()
    print(json.dumps(serve(args), indent=1))


if __name__ == "__main__":
    main()
