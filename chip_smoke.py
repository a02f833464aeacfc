"""Smoke check of the system's main path on a TPU, in one process.

  python chip_smoke.py              # one chip: planner, serving, kernels
  python chip_smoke.py --chips 4    # four chips: the row-sharded planner

One chip runs four phases after the device check:

  planner  the What/When/Where planner over the full 223-GEMM LLM set,
           on the XLA (`vectorized`) and compiled Pallas (`pallas`) row
           kernels of a one-device SweepEngine, checked against the
           scalar reference;
  serve    `launch.serve` in-process (the CLI's own `serve()`) on
           mamba2-780m at full published width with planner-gated INT8
           weights: a fixed batch of 8, then 8 synthetic requests
           through the continuous-batching engine on 4 slots;
  kernels  the INT8 GEMM that a "use CiM" verdict routes to, in both
           dataflows, against the jnp reference at every routed
           full-width shape, for decode and prefill row counts.

`--chips 4` runs only the 223-GEMM plan on a 4-device row mesh (both row
kernels, under shard_map) against the one-device engine on the same grid.

Every phase prints one line; the full results go to <out>/chip_smoke.json.
Times printed are smoke times of a cold process, not benchmark numbers.
Weights, prompts and traffic are drawn from --seed.  The last line of
stdout is {"ok": true, "device": {...}} only when every phase passed; the
script exits non-zero without it when a phase fails or JAX finds no TPU.
Nothing here starts a child process: a chip belongs to one process.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))

ARCH = "mamba2-780m"
BATCH, PROMPT_LEN, NEW_TOKENS = 8, 16, 32
SERVE_ARGS = ["--arch", ARCH, "--quantize", "--batch", str(BATCH),
              "--prompt-len", str(PROMPT_LEN),
              "--new-tokens", str(NEW_TOKENS)]
N_REQUESTS, SLOTS = 8, 4
TRAFFIC_ARGS = SERVE_ARGS + ["--requests", str(N_REQUESTS),
                             "--slots", str(SLOTS), "--arrival-rate", "0"]
# INT8 GEMM rows checked per shape: the decode batch and a prefill block
KERNEL_ROWS = (8, 128)
# x is bf16 and the weights are int8, both exact in the MXU's bf16
# operands, so the kernel differs from the f32 "highest" reference only
# by f32 accumulation order: far below this bound, relative to max|y|
KERNEL_RTOL = 1e-3
# pallas vs vectorized option metrics: the same cost spec through two
# compilers; the property suite holds them to 1e-5 on the CPU
PLANNER_RTOL = 1e-3


class SmokeFailure(RuntimeError):
    """A check of the smoke failed."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def rel_diff(a: float, b: float) -> float:
    return abs(a - b) / max(abs(a), abs(b), 1e-30)


def verdict_mismatches(got, want) -> int:
    return sum(a.use_cim != b.use_cim or a.best_energy != b.best_energy
               for a, b in zip(got, want))


def max_option_diff(got, want) -> float:
    """Largest relative difference of any option's energy or time."""
    worst = 0.0
    for a, b in zip(got, want):
        for name, m in a.options.items():
            o = b.options[name]
            worst = max(worst, rel_diff(m.energy_pj, o.energy_pj),
                        rel_diff(m.time_ns, o.time_ns))
        worst = max(worst, rel_diff(a.baseline.energy_pj,
                                    b.baseline.energy_pj))
    return worst


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


# --- phases --------------------------------------------------------------

def phase_planner(args, res):
    from repro.core.llm_workloads import llm_gemm_set
    from repro.core.planner import plan_workload
    from repro.core.sweep import SweepEngine, plan_workload_batched
    from repro.kernels.sweep_eval import pallas_status

    status = pallas_status()
    check(status["mode"] == "compiled",
          f"pallas_status mode {status['mode']!r}, want 'compiled'")
    gemms = llm_gemm_set()
    engine = SweepEngine(mesh=None)
    vec, t_vec = timed(lambda: plan_workload_batched(
        gemms, engine=engine, backend="vectorized"))
    pal, t_pal = timed(lambda: plan_workload_batched(
        gemms, engine=engine, backend="pallas"))
    ref, t_ref = timed(lambda: plan_workload(gemms, backend="scalar"))
    res.update({
        "pallas_mode": status["mode"],
        "gemms": len(gemms),
        "configs": len(vec[0].options),
        "cim_verdicts": sum(d.use_cim for d in vec),
        "vectorized_vs_scalar_mismatches": verdict_mismatches(vec, ref),
        "pallas_vs_scalar_mismatches": verdict_mismatches(pal, ref),
        "pallas_vs_vectorized_mismatches": verdict_mismatches(pal, vec),
        "pallas_vs_vectorized_max_rel_diff": max_option_diff(pal, vec),
        "vectorized_vs_scalar_max_rel_diff": max_option_diff(vec, ref),
        "smoke_s": {"vectorized": t_vec, "pallas": t_pal,
                    "scalar": t_ref},
    })
    line = (f"{res['gemms']} GEMMs x {res['configs']} configs, "
            f"pallas {status['mode']}; verdicts agreeing with scalar: "
            f"vectorized {res['gemms'] - res['vectorized_vs_scalar_mismatches']}"
            f"/{res['gemms']}, pallas "
            f"{res['gemms'] - res['pallas_vs_scalar_mismatches']}"
            f"/{res['gemms']}; max rel diff pallas vs vectorized "
            f"{res['pallas_vs_vectorized_max_rel_diff']!r}")
    check(res["vectorized_vs_scalar_mismatches"] == 0,
          "vectorized verdicts differ from scalar: " + line)
    check(res["pallas_vs_scalar_mismatches"] == 0,
          "pallas verdicts differ from scalar: " + line)
    check(res["pallas_vs_vectorized_max_rel_diff"] <= PLANNER_RTOL,
          "pallas metrics differ from vectorized: " + line)
    return line


def phase_sharded_planner(args, devices, res):
    from repro.core.llm_workloads import llm_gemm_set
    from repro.core.sweep import SweepEngine, plan_workload_batched
    from repro.kernels.sweep_eval import pallas_status
    from repro.launch.mesh import row_mesh

    status = pallas_status()
    check(status["mode"] == "compiled",
          f"pallas_status mode {status['mode']!r}, want 'compiled'")
    gemms = llm_gemm_set()
    mesh = row_mesh(devices)
    res.update(devices=mesh.size, gemms=len(gemms))
    parts = []
    for backend in ("vectorized", "pallas"):
        sharded = SweepEngine(mesh=mesh)
        check(sharded.n_shards == len(devices),
              f"row mesh has {sharded.n_shards} shards")
        one, t_one = timed(lambda: plan_workload_batched(
            gemms, engine=SweepEngine(mesh=None), backend=backend))
        got, t_got = timed(lambda: plan_workload_batched(
            gemms, engine=sharded, backend=backend))
        res[backend] = {
            "verdict_mismatches": verdict_mismatches(got, one),
            "max_rel_diff": max_option_diff(got, one),
            "smoke_s": {"one_device": t_one, "sharded": t_got}}
        parts.append(f"{backend}: {res[backend]['verdict_mismatches']} "
                     f"verdict mismatches, max rel diff "
                     f"{res[backend]['max_rel_diff']!r}")
    line = (f"{len(gemms)} GEMMs on a {mesh.size}-device row mesh vs one "
            f"device; " + "; ".join(parts))
    for backend in ("vectorized", "pallas"):
        check(res[backend]["verdict_mismatches"] == 0,
              "sharded verdicts differ from one device: " + line)
    return line


def reference_parity(seed: int) -> dict:
    """Prefill logits of the gated and the ungated INT8 session (the
    serve CLI's weights and prompt for `seed`) against an f32 reference:
    the same int8 weights dequantized, run in f32 at "highest" matmul
    precision.  Gated and ungated differ by more than PARITY_ATOL at full
    width because the model's own bf16 activations move the logits; what
    the Pallas route must not do is add error beyond the XLA route's."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    from repro.configs import ARCHS, RunConfig
    from repro.models import init
    from repro.quant import quantize_model_params
    from repro.quant.int8 import dequantize_weight
    from repro.serving import ServeSession

    cfg = ARCHS[ARCH]
    rc = RunConfig(attn_impl="naive", remat=False)
    key = jax.random.PRNGKey(seed)
    params = init(key, cfg)
    prompt = jax.random.randint(key, (BATCH, PROMPT_LEN), 0, cfg.vocab)
    max_len = PROMPT_LEN + NEW_TOKENS + 1
    logits = {}
    for name, gated in (("gated", True), ("ungated", False)):
        sess = ServeSession(cfg, rc, params, max_len=max_len, batch=BATCH,
                            quantize=True, gated=gated)
        logits[name] = sess.prefill(prompt).astype(jnp.float32)
        del sess
    is_q = lambda t: isinstance(t, dict) and "q" in t       # noqa: E731
    f32 = jax.tree.map(
        lambda t: (dequantize_weight(t["q"], t["scale"]) if is_q(t)
                   else t.astype(jnp.float32)),
        quantize_model_params(params), is_leaf=is_q)
    del params
    cfg32 = dataclasses.replace(cfg, param_dtype="float32",
                                compute_dtype="float32")
    with jax.default_matmul_precision("highest"):
        ref = ServeSession(cfg32, rc, f32, max_len=max_len, batch=BATCH)
        lr = ref.prefill(prompt).astype(jnp.float32)
    lg, lu = logits["gated"], logits["ungated"]
    amax = lambda a: float(jnp.max(jnp.abs(a)))                # noqa: E731
    return {"gated_vs_f32": amax(lg - lr), "ungated_vs_f32": amax(lu - lr),
            "f32_max_abs": amax(lr),
            "finite": bool(jnp.isfinite(lg).all() & jnp.isfinite(lu).all()
                           & jnp.isfinite(lr).all())}


def phase_serve(args, res):
    from repro.configs import ARCHS
    from repro.launch.serve import PARITY_ATOL, build_parser, serve
    from repro.serving import CIM_ROUTE

    cfg = ARCHS[ARCH]
    seed = ["--seed", str(args.seed)]
    rep = serve(build_parser().parse_args(SERVE_ARGS + seed))
    res["report"] = rep
    g = rep["gating"]
    routed = {lab: r["shapes"] for lab, r in g["routes"].items()
              if r["route"] == CIM_ROUTE}
    res["cim_routed_shapes"] = routed
    traffic = serve(build_parser().parse_args(TRAFFIC_ARGS + seed))
    res["traffic_report"] = traffic
    par = res["reference"] = reference_parity(args.seed)
    agg = traffic["traffic"]["aggregate"]
    executables = agg["phase_gating"]["executables"]
    line = (f"{rep['arch']} (d_model {cfg.d_model}, "
            f"{cfg.n_layers} layers, vocab {cfg.vocab}, "
            f"{cfg.param_count() / 1e9:.2f} B params), batch 8: "
            f"{len(routed)}/{len(g['routes'])} labels -> {CIM_ROUTE} "
            f"{sorted(routed)}; {g['decode_step_tpu_custom_calls']} "
            f"tpu_custom_call in the decode step; max |dlogit| "
            f"gated/ungated {g['parity_max_abs_diff']!r}, against the f32 "
            f"reference (max |logit| {par['f32_max_abs']!r}) gated "
            f"{par['gated_vs_f32']!r} vs ungated {par['ungated_vs_f32']!r} "
            f"(+ atol {PARITY_ATOL}); "
            f"logits finite {g['logits_finite']}, tokens in vocab "
            f"{rep['tokens_in_vocab']}; decode_executables "
            f"{g['decode_executables']}; traffic {agg['completed']}/"
            f"{N_REQUESTS} requests on {SLOTS} slots, executables per phase "
            f"plan {executables}, kv_donation_ok {agg['kv_donation_ok']}")
    check(rep["arch"] == cfg.name and traffic["arch"] == cfg.name,
          f"served {rep['arch']!r}, not {cfg.name!r} at full width")
    check(routed, f"no label routes to {CIM_ROUTE}: " + line)
    check(g["decode_step_tpu_custom_calls"] > 0,
          "no tpu_custom_call in the lowered decode step: " + line)
    check(par["gated_vs_f32"] <= par["ungated_vs_f32"] + PARITY_ATOL,
          "the Pallas route adds error beyond the XLA route's: " + line)
    check(g["logits_finite"] and par["finite"]
          and rep["tokens_in_vocab"],
          "non-finite logits or out-of-vocab tokens: " + line)
    check(g["decode_executables"] == 1,
          "fixed-batch decode step retraced: " + line)
    check(agg["completed"] == N_REQUESTS,
          "traffic requests lost: " + line)
    check(executables.get("decode") == 1
          and all(n == 1 for n in executables.values()),
          "continuous-batching step retraced: " + line)
    check(agg["kv_donation_ok"] is True,
          "KV cache was not donated in place: " + line)
    return line


def kernel_shapes(serve_res) -> list[tuple[int, int]]:
    """(N, K) of every INT8 GEMM to check: the label shapes the serve
    phase routed to the Pallas kernel, plus the planner's full-width
    mamba2-780m projection GEMMs (so this phase stands on its own), plus
    one shape whose K has no 128-multiple divisor (zero-padded K) and
    whose N has none either (ragged last N block)."""
    from repro.configs import ARCHS, SHAPES
    from repro.core.llm_workloads import gemms_of_model
    nk = {(g.N, g.K) for g in gemms_of_model(ARCHS[ARCH],
                                             SHAPES["decode_32k"])}
    for mnk in serve_res.get("cim_routed_shapes", {}).values():
        nk |= {(n, k) for _, n, k in mnk}
    nk.add((1000, 1601))
    return sorted(nk)


def phase_kernels(args, serve_res, res):
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, ref
    from repro.kernels.autotune import int8_gemm_blocks

    key = jax.random.PRNGKey(args.seed)
    shapes = kernel_shapes(serve_res)
    cases = [(m, n, k, df, None) for n, k in shapes
             for m in KERNEL_ROWS for df in ("os", "ws")]
    # ws with several M blocks and several K blocks: the psums of each M
    # block must survive the trip through the other M blocks
    cases.append((128, 3072, 1536, "ws", (32, 512, 512)))
    rows, worst = [], 0.0
    for m, n, k, df, blocks in cases:
        kx, kw, ks, key = jax.random.split(key, 4)
        x = jax.random.normal(kx, (m, k), jnp.bfloat16)
        w = jax.random.randint(kw, (k, n), -127, 128, jnp.int8)
        s = jax.random.uniform(ks, (n,), jnp.float32, 0.01, 0.1)
        blk = blocks or int8_gemm_blocks(m, n, k)
        got = ops.int8_matmul(x, w, s, dataflow=df, block_m=blk[0],
                              block_n=blk[1], block_k=blk[2])
        with jax.default_matmul_precision("highest"):
            want = ref.int8_gemm_ref(x, w, s)
        err = float(jnp.max(jnp.abs(got - want))
                    / jnp.maximum(jnp.max(jnp.abs(want)), 1e-30))
        ok = bool(jnp.isfinite(got).all()) and err <= KERNEL_RTOL
        rows.append({"m": m, "n": n, "k": k, "dataflow": df,
                     "blocks": list(blk), "grid_m": -(-m // blk[0]),
                     "grid_k": -(-k // blk[2]), "rel_err": err,
                     "ok": ok})
        worst = max(worst, err)
    bad = [r for r in rows if not r["ok"]]
    line = (f"{len(rows)} int8_matmul calls ({len(rows) - len(bad)} ok) "
            f"over {len(shapes)} (N, K) shapes x M "
            f"{list(KERNEL_ROWS)} x os/ws (+ ws at 4 M x 3 K blocks); "
            f"max err / max|ref| {worst!r} (bound {KERNEL_RTOL})")
    res.update(cases=rows, max_rel_err=worst)
    check(not bad, f"{line}; failing: {bad}")
    return line


# --- driver --------------------------------------------------------------

def run_phase(name, fn, results) -> bool:
    """Run one phase, fn(res) -> summary line, filling `res` as it goes
    so a failing phase still records what it measured."""
    t0 = time.perf_counter()
    res = {}
    try:
        line = fn(res)
    except Exception as e:
        dt = time.perf_counter() - t0
        results[name] = {"ok": False, "smoke_s": dt,
                         "error": f"{type(e).__name__}: {e}",
                         "traceback": traceback.format_exc(), **res}
        print(f"[{name}] FAIL after {dt:.1f} s: {type(e).__name__}: {e}",
              flush=True)
        return False
    dt = time.perf_counter() - t0
    results[name] = {"ok": True, "smoke_s": dt, **res}
    print(f"[{name}] ok in {dt:.1f} s: {line}", flush=True)
    return True


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Drive the planner and planner-gated INT8 serving "
                    "once on a TPU and check the results.")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: planner, serve and kernel phases on one "
                         "chip; 4: only the row-sharded planner on four")
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds weights, prompts, traffic and kernel "
                         "inputs")
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out"),
                    help="directory for chip_smoke.json")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    print(f"[device] platform {dev.platform}, kind {dev.device_kind!r}, "
          f"count {len(devices)}", flush=True)
    if dev.platform != "tpu":
        print(f"[device] FAIL: JAX found no TPU (platform "
              f"{dev.platform!r})", flush=True)
        return 1
    if len(devices) < args.chips:
        print(f"[device] FAIL: --chips {args.chips} needs {args.chips} "
              f"devices, JAX sees {len(devices)}", flush=True)
        return 1

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import configure_compile_cache
    cache_dir = configure_compile_cache()

    results = {"device": {"platform": dev.platform,
                          "kind": dev.device_kind,
                          "count": len(devices),
                          "used": args.chips},
               "compile_cache": cache_dir, "seed": args.seed}
    t0 = time.perf_counter()
    if args.chips == 4:
        ok = run_phase("sharded-planner", lambda res: phase_sharded_planner(
            args, devices[:4], res), results)
    else:
        ok = run_phase("planner", lambda res: phase_planner(args, res),
                       results)
        ok &= run_phase("serve", lambda res: phase_serve(args, res),
                        results)
        ok &= run_phase("kernels", lambda res: phase_kernels(
            args, results["serve"], res), results)
    results["ok"] = ok
    results["smoke_s"] = time.perf_counter() - t0
    os.makedirs(args.out, exist_ok=True)
    path = os.path.join(args.out, "chip_smoke.json")
    with open(path, "w") as f:
        json.dump(results, f, indent=1, default=str)
    print(f"[done] {'all phases passed' if ok else 'FAILED'} in "
          f"{results['smoke_s']:.1f} s; details in {path}", flush=True)
    if not ok:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
