"""Planner-gated serving benchmark: gated vs ungated INT8 decode.

For each benchmarked arch (reduced CPU smoke configs — the mechanism is
what's measured, not TPU throughput) it builds two quantized
ServeSessions over identical weights:

  * gated   — the What/When/Where verdicts close the jitted decode step,
              so CiM-gated projection labels lower to the weight-
              stationary INT8 Pallas kernel;
  * ungated — same INT8 weights, every label forced onto the standard
              XLA path (KernelPlanTable.ungated()).

and records decode tokens/s for both, the % of projections the gated
program routed to the CiM path, and a logits-parity check (routing must
not change the math beyond kernel numerics).  Three gates protect the
trajectory entry (ROADMAP "make the gated path win"):

  * **parity**   — gated and ungated logits agree within PARITY_ATOL;
  * **gated-not-slower** — on every arch where the planner actually
    routes projections to CiM (cim_routed_pct > 0), the gated program
    must not decode slower than the ungated one (beyond the
    GATED_NOT_SLOWER_RTOL timing-noise band);
  * **trend**    — tokens/s vs the committed BENCH_serve.json baseline
    must not drop beyond the SERVE_TREND_RTOL band (benchmarks.trend);
    deltas are reported in the GitHub job summary when CI provides one.

Like sweep_bench, a run failing any gate is quarantined to
BENCH_serve.json.failed instead of replacing the trusted trajectory
entry, and running the module directly (as CI does) then exits nonzero.

Every arch is measured in this one process: a chip belongs to one
process at a time, so a parent that has initialised JAX cannot hand the
device to per-arch children.  Sessions alternate their timed samples
(repro.launch.serve.steady_decode_tokens_per_s), so contention hits the
gated and ungated programs of one arch alike; numbers taken on the CPU
after another arch are still order-sensitive and are not device
measurements.

Run directly:  PYTHONPATH=src python -m benchmarks.serve_gating_bench
(--new-tokens/--repeats/--warmup tune the shared timing helper,
repro.launch.serve.steady_decode_tokens_per_s).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, RunConfig, reduced
from repro.launch.compile_cache import configure_compile_cache
from repro.launch.serve import PARITY_ATOL, steady_decode_tokens_per_s
from repro.models import init
from repro.serving import ServeSession, cim_fraction

from .sweep_bench import _provenance
from .trend import (committed_baseline, emit_job_summary, render_markdown,
                    trend_report)

# arch -> decode batch.  mamba2 at batch 8 is the mixed-verdict case
# (ssm-BCdt gates on, the rest stay standard); the attention archs'
# smoke-size decode GEMVs are all "don't CiM" — the paper's M=1
# pathology — so their gated program must equal the ungated one.
BENCH_ARCHS = (("mamba2-780m", 8), ("mistral-nemo-12b", 8),
               ("qwen2-moe-a2.7b", 8))
PROMPT_LEN = 6
NEW_TOKENS = 16
# gated-not-slower noise band: when the true gated/ungated difference is
# ~0 (the paper's answer on the attention archs IS "don't CiM at decode",
# so the programs are near-identical), CPU smoke timing jitters +-1-2%
# and a strict >= gate coin-flips.  2% lets noise through but still
# catches any real slowdown (the donation mis-default cost 20%).
GATED_NOT_SLOWER_RTOL = 0.02


def _measure_arch(arch: str, batch: int, new_tokens: int,
                  repeats: int, warmup: int) -> dict:
    """One arch's gated-vs-ungated measurement."""
    rc = RunConfig(attn_impl="naive", remat=False)
    cfg = reduced(ARCHS[arch])
    params = init(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(1),
                                (batch, PROMPT_LEN), 0, cfg.vocab)
    max_len = PROMPT_LEN + new_tokens + 2
    gated = ServeSession(cfg, rc, params, max_len=max_len,
                         batch=batch, quantize=True)
    ungated = ServeSession(cfg, rc, params, max_len=max_len,
                           batch=batch, quantize=True, gated=False)

    # parity first (prefill on fresh caches), then throughput
    lg = gated.prefill(prompt).astype(jnp.float32)
    lu = ungated.prefill(prompt).astype(jnp.float32)
    max_diff = float(jnp.max(jnp.abs(lg - lu)))
    parity_ok = max_diff <= PARITY_ATOL

    # interleaved sampling (launch.serve helper): contention hits
    # gated and ungated symmetrically, jit compile excluded
    tps_g, tps_u = steady_decode_tokens_per_s(
        (gated, ungated), prompt, new_tokens,
        repeats=repeats, warmup=warmup)
    routes = gated.route_report()
    row = {"arch": cfg.name, "batch": batch,
           "tokens_per_s_gated": round(tps_g, 1),
           "tokens_per_s_ungated": round(tps_u, 1),
           "cim_routed_pct": round(100.0 * cim_fraction(routes), 1),
           "parity_max_abs_diff": round(max_diff, 5),
           "parity_ok": parity_ok}
    return {
        **row, "routes": {lab: r["route"] for lab, r in routes.items()},
        # None when the private jit-cache probe is unavailable (the
        # retrace gate below then skips rather than false-failing)
        "decode_executables": gated.decode_executables}


def serve_gating_speed(write_json: bool = True, new_tokens: int = NEW_TOKENS,
                       repeats: int = 3, warmup: int = 0):
    rows, per_arch = [], {}
    all_parity_ok = True
    for arch, batch in BENCH_ARCHS:
        entry = _measure_arch(arch, batch, new_tokens, repeats, warmup)
        all_parity_ok &= entry["parity_ok"]
        rows.append({k: entry[k] for k in
                     ("arch", "batch", "tokens_per_s_gated",
                      "tokens_per_s_ungated", "cim_routed_pct",
                      "parity_max_abs_diff", "parity_ok")})
        per_arch[entry["arch"]] = entry

    # gated-not-slower: wherever the planner routed anything to CiM the
    # gated program must win (or tie, within the timing-noise band) —
    # the whole point of the gate
    gated_not_slower = all(
        r["tokens_per_s_gated"] >=
        r["tokens_per_s_ungated"] * (1.0 - GATED_NOT_SLOWER_RTOL)
        for r in rows if r["cim_routed_pct"] > 0)

    # perf-trend lane: deltas vs the committed baseline's archs block
    base_archs = (committed_baseline() or {}).get("archs", {})
    pairs = []
    for r in rows:
        prev = base_archs.get(r["arch"], {})
        for key in ("tokens_per_s_gated", "tokens_per_s_ungated"):
            pairs.append((f"{r['arch']} {key}", prev.get(key), r[key]))
    trend = trend_report(pairs)
    emit_job_summary(render_markdown("serve_gating_bench trend", trend))

    derived = {
        "archs": per_arch,
        "parity_ok": all_parity_ok,
        "parity_atol": PARITY_ATOL,
        "new_tokens": new_tokens,
        "gates": {
            "parity_ok": all_parity_ok,
            "gated_not_slower_ok": gated_not_slower,
            "trend_ok": trend["ok"],
        },
        "trend": trend,
        "provenance": _provenance(),
    }
    all_ok = all(derived["gates"].values())
    if write_json:
        out = os.environ.get("BENCH_SERVE_OUT", "BENCH_serve.json")
        # preserve the traffic and adaptive benches' blocks if already
        # recorded (the three benches share the file; each owns its keys)
        if os.path.exists(out):
            try:
                with open(out) as f:
                    prev = json.load(f)
                for key in ("traffic", "adaptive"):
                    if key in prev:
                        derived[key] = prev[key]
            except (json.JSONDecodeError, OSError):
                pass
        if not all_ok:
            # quarantine: a gate-violating run must not replace the
            # trusted trajectory entry
            out += ".failed"
        with open(out, "w") as f:
            json.dump(derived, f, indent=1)
    return rows, derived


if __name__ == "__main__":
    ap = argparse.ArgumentParser(
        description="Planner-gated serving benchmark (gated vs ungated "
                    "INT8 decode).",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter)
    ap.add_argument("--new-tokens", type=int, default=NEW_TOKENS,
                    help="decode steps per timed sample")
    ap.add_argument("--repeats", type=int, default=3,
                    help="timed samples per session (best is kept)")
    ap.add_argument("--warmup", type=int, default=0,
                    help="untimed decode steps per session after prefill")
    cli = ap.parse_args()
    configure_compile_cache()
    _, derived = serve_gating_speed(new_tokens=cli.new_tokens,
                                    repeats=cli.repeats, warmup=cli.warmup)
    print(json.dumps(derived, indent=1))
    if not derived["parity_ok"]:
        sys.exit("gating parity regression: gated and ungated INT8 decode "
                 "disagree beyond kernel-numerics tolerance")
    if not derived["gates"]["gated_not_slower_ok"]:
        sys.exit("gating speed regression: a CiM-routed arch decoded "
                 "slower gated than ungated")
    if not derived["gates"]["trend_ok"]:
        sys.exit("perf-trend regression: tokens/s dropped beyond the "
                 "SERVE_TREND_RTOL band vs the committed baseline")
    bad_retrace = [a for a, d in derived["archs"].items()
                   if d["decode_executables"] not in (1, None)]
    if bad_retrace:
        sys.exit(f"retrace regression: {bad_retrace} compiled more than "
                 "one decode executable")
