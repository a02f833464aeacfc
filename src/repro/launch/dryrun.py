"""Multi-pod dry-run driver (deliverable e).

Lowers + compiles train_step / serve_step for every (arch x input-shape x
mesh) cell against the production meshes — 16x16 single pod and 2x16x16
multi-pod — using ShapeDtypeStruct stand-ins (no allocation).  Prints
memory_analysis (fits?) and cost_analysis (FLOPs/bytes for §Roofline),
parses the partitioned HLO for collective bytes, and writes one JSON per
cell so an interrupted sweep resumes where it stopped.

Cost accounting: XLA's cost_analysis counts a while-loop body once, so the
scanned layer stack under-reports FLOPs/bytes/collectives.  Each cell
therefore gets (a) the official scanned compile — the deployment program,
proves lowering + memory — and (b) two partial-unroll compiles whose costs
extrapolate linearly to the full layer count (see _unroll_points).

Usage:
  python -m repro.launch.dryrun --arch qwen2-7b --shape train_4k \
      --mesh single --out results/dryrun
  python -m repro.launch.dryrun --all --out results/dryrun
"""
import os

# 512 placeholder host devices for the production meshes, appended to
# whatever XLA_FLAGS the caller set (read when the backend starts)
_HOST_DEVICES = "--xla_force_host_platform_device_count=512"
if _HOST_DEVICES not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = " ".join(
        f for f in (os.environ.get("XLA_FLAGS"), _HOST_DEVICES) if f)

import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp

from ..configs import ARCHS, SHAPES, RunConfig
from ..configs.base import ModelConfig, ShapeConfig
from ..models import forward
from ..models.model import n_periods
from ..optim import make_optimizer
from ..serving.engine import make_serve_step
from ..sharding.rules import (batch_specs, cache_specs, param_specs,
                              to_named)
from ..train.loop import make_train_step
from . import specs as S
from .hlo_analysis import collective_stats, op_census
from .mesh import make_production_mesh, single_pod_mesh_from
from .roofline import Roofline, analytic_hbm_bytes, model_flops

from jax.sharding import NamedSharding, PartitionSpec as P


def run_config_for(cfg: ModelConfig, shape: ShapeConfig,
                   overrides: dict | None = None) -> RunConfig:
    """Per-cell runtime policy (recorded in the cell JSON)."""
    params = cfg.param_count()
    opt = "adafactor" if params > 100e9 else "adamw"
    micro = 4 if (shape.kind == "train" and cfg.d_model >= 5120) else 1
    # int8 KV cache when a bf16 cache would not fit per-device HBM
    kv_dtype = "bfloat16"
    if shape.kind == "decode":
        n_attn = (cfg.n_layers // cfg.attn_every
                  if cfg.family == "hybrid" else cfg.n_layers)
        if cfg.family == "ssm":
            n_attn = 0
        cache_bytes = (2 * n_attn * shape.global_batch * shape.seq_len
                       * cfg.n_kv_heads * cfg.head_dim() * 2)
        if cache_bytes / 256 > 6e9:
            kv_dtype = "int8"
    rc = RunConfig(optimizer=opt, microbatches=micro, remat=True,
                   fsdp=True, kv_cache_dtype=kv_dtype,
                   attn_impl="flash_jnp", attn_chunk=2048)
    if overrides:
        rc = dataclasses.replace(rc, **overrides)
    return rc


def _mesh(kind: str):
    if kind == "multi":
        return make_production_mesh(multi_pod=True), 512
    # single pod: 16x16 slice of the 512 host devices
    return single_pod_mesh_from(jax.devices()), 256


def _build(cfg, shape, mesh, rc):
    """Returns (jitted_fn, abstract_args) for this cell."""
    pshapes = S.param_shapes(cfg)
    pspecs = param_specs(pshapes, cfg, rc)
    psh = to_named(mesh, pspecs, pshapes)

    if shape.kind == "train":
        opt_init, _ = make_optimizer(rc.optimizer)
        oshapes = jax.eval_shape(opt_init, pshapes)
        ospecs = param_specs(oshapes, cfg, rc)
        osh = to_named(mesh, ospecs, oshapes)
        binput = S.train_input_specs(cfg, shape)
        bsh = to_named(mesh, batch_specs(binput, mesh), binput)
        step = make_train_step(cfg, rc)
        jitted = jax.jit(
            step,
            in_shardings=(psh, osh, bsh, NamedSharding(mesh, P())),
            out_shardings=(psh, osh, None))
        return jitted, (pshapes, oshapes, binput,
                        jax.ShapeDtypeStruct((), jnp.int32))
    if shape.kind == "prefill":
        binput = S.prefill_input_specs(cfg, shape)
        bsh = to_named(mesh, batch_specs(binput, mesh), binput)

        def prefill(params, batch):
            logits, _ = forward(params, batch["tokens"], cfg, rc,
                                image_embeds=batch.get("image_embeds"))
            return logits
        out_sh = None
        if rc.shard_loss:
            # keep served logits batch+vocab sharded — out_shardings=None
            # replicates the (b, s, V) tensor to every device (§Perf)
            ba = tuple(a for a in rc.batch_axes.split(",") if a)
            ba = ba if len(ba) > 1 else ba[0]
            spec = (P(ba, None, None, "model") if cfg.family == "audio"
                    else P(ba, None, "model"))
            out_sh = NamedSharding(mesh, spec)
        jitted = jax.jit(prefill, in_shardings=(psh, bsh),
                         out_shardings=out_sh)
        return jitted, (pshapes, binput)
    # decode
    dins = S.decode_input_specs(cfg, rc, shape)
    csh = to_named(mesh, cache_specs(dins["cache"], mesh, cfg),
                   dins["cache"])
    tsh = to_named(mesh, batch_specs({"t": dins["tokens"]}, mesh))["t"]
    step = make_serve_step(cfg, rc)
    jitted = jax.jit(
        step,
        in_shardings=(psh, csh, tsh, NamedSharding(mesh, P())),
        out_shardings=(None, csh))
    return jitted, (pshapes, dins["cache"], dins["tokens"], dins["pos"])


def _unroll_points(L: int) -> list[int]:
    """Layer-scan unroll factors for the cost-extrapolation compiles."""
    if L <= 4:
        return [L]
    divs = [d for d in range(1, L + 1) if L % d == 0]
    k1 = max(d for d in divs if d <= 8)
    smaller = [d for d in divs if d < k1 and d <= max(1, k1 // 2)]
    k2 = max(smaller) if smaller else 1
    return [k1, k2] if k1 > k2 else [k1]


def _extrapolate(measures: list, L: int) -> dict:
    """measured(k) = fixed + k*body => true(L)."""
    if len(measures) == 1:
        k, m = measures[0]
        if k == L:
            return dict(m)
        return {key: v * (L / max(1, k)) for key, v in m.items()}
    (k1, m1), (k2, m2) = measures
    out = {}
    for key in m1:
        body = (m1[key] - m2[key]) / (k1 - k2)
        out[key] = max(m1[key], m2[key] + (L - k2) * body)
    return out


def _compile_costs(cfg, shape, mesh, rc):
    jitted, args = _build(cfg, shape, mesh, rc)
    with mesh:
        compiled = jitted.lower(*args).compile()
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    coll = collective_stats(hlo)
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll_bytes": coll["collective_bytes"],
    }, coll["by_type"], op_census(hlo)


def _planner_telemetry(cfg: ModelConfig, shape: ShapeConfig,
                       rc: RunConfig) -> dict:
    """What/when/where verdict summary + sweep-cache telemetry + executed
    kernel routes for a decode cell: the serving engine consults the same
    batched planner on every ServeSession.kernel_plan build, so the
    hit/miss delta recorded here is exactly what production traffic over
    this cell's shapes would see (LRU sizing signal).  The routes block
    traces the plan-gated quantized decode step abstractly
    (serving.decode_routes) and records which projections would lower to
    the CiM INT8 Pallas path vs the standard XLA matmul."""
    from ..core.llm_workloads import gemms_of_model
    from ..core.planner import plan_workload, summarize
    from ..core.sweep import measured_cache_delta
    from ..quant import KernelPlanTable
    from ..serving import cim_fraction, decode_routes
    decisions, tel = measured_cache_delta(
        lambda: plan_workload(gemms_of_model(cfg, shape),
                              backend="vectorized"))
    table = KernelPlanTable.from_decisions(decisions,
                                           model_name=cfg.name)
    nimg = cfg.vision.n_image_tokens if cfg.family == "vlm" else 0
    routes = decode_routes(cfg, rc, table, batch=shape.global_batch,
                           max_len=shape.seq_len, n_image_tokens=nimg)
    return {"summary": summarize(decisions),
            "plan_hits": tel["plan_hits"],
            "plan_misses": tel["plan_misses"],
            "cache": tel["engine"],
            "routes": routes,
            "cim_routed_fraction": cim_fraction(routes)}


def lower_cell(arch: str, shape_name: str, mesh_kind: str,
               rc_overrides: dict | None = None,
               skip_cost_passes: bool = False):
    cfg = ARCHS[arch]
    shape = SHAPES[shape_name]
    if not S.cell_is_runnable(cfg, shape):
        return {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                "status": "skipped",
                "reason": "full-attention arch; long_500k requires "
                          "sub-quadratic attention (DESIGN.md §5)"}
    mesh, chips = _mesh(mesh_kind)
    rc = run_config_for(cfg, shape, rc_overrides)

    # --- official pass: the deployable scanned program -------------------
    t0 = time.time()
    jitted, args = _build(cfg, shape, mesh, rc)
    with mesh:
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    mem = compiled.memory_analysis()
    mem_info = {}
    if mem is not None:
        for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes"):
            if hasattr(mem, attr):
                mem_info[attr] = int(getattr(mem, attr))

    # --- cost-extrapolation passes ----------------------------------------
    L = n_periods(cfg)
    measures, coll_types, census = [], {}, {}
    t1 = time.time()
    if not skip_cost_passes:
        for k in _unroll_points(L):
            rc_k = dataclasses.replace(rc, scan_unroll=k, microbatches=1)
            m, coll_types, census = _compile_costs(cfg, shape, mesh, rc_k)
            measures.append((k, m))
        costs = _extrapolate(measures, L)
    else:
        m, coll_types, census = _compile_costs(cfg, shape, mesh, rc)
        costs = m
    t_cost = time.time() - t1

    rf = Roofline(
        arch=arch, shape=shape_name, mesh=mesh_kind, chips=chips,
        hlo_flops=costs["flops"], hlo_bytes=costs["bytes"],
        collective_bytes=costs["coll_bytes"],
        model_flops_total=model_flops(cfg, shape),
        hbm_bytes=analytic_hbm_bytes(
            cfg, shape, chips, optimizer=rc.optimizer,
            microbatches=rc.microbatches,
            kv_cache_bytes_per_el=1 if rc.kv_cache_dtype == "int8" else 2))

    res = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "status": "ok", "chips": chips,
        "run_config": {"optimizer": rc.optimizer,
                       "microbatches": rc.microbatches,
                       "kv_cache_dtype": rc.kv_cache_dtype,
                       "fsdp": rc.fsdp, **(rc_overrides or {})},
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "cost_pass_s": round(t_cost, 1),
        "unroll_points": [k for k, _ in measures],
        "memory_analysis": mem_info,
        "cost_analysis": {"flops": costs["flops"],
                          "bytes_accessed": costs["bytes"]},
        "collectives": {"collective_bytes": costs["coll_bytes"],
                        "by_type_at_last_unroll": coll_types},
        "op_census": census,
        "roofline": rf.row(),
    }
    if shape.kind == "decode":
        res["planner"] = _planner_telemetry(cfg, shape, rc)
    return res


def all_cells():
    for arch in ARCHS:
        for shape in SHAPES:
            yield arch, shape


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--fast", action="store_true",
                    help="skip the cost-extrapolation compiles")
    ap.add_argument("--rc", default="",
                    help="JSON RunConfig overrides (perf iterations)")
    ap.add_argument("--tag", default="", help="suffix for variant runs")
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)
    overrides = json.loads(args.rc) if args.rc else None

    cells = (list(all_cells()) if args.all
             else [(args.arch, args.shape)])
    meshes = (["single", "multi"] if args.all else [args.mesh])
    for arch, shape in cells:
        for mesh_kind in meshes:
            tag = f"-{args.tag}" if args.tag else ""
            path = os.path.join(args.out,
                                f"{arch}.{shape}.{mesh_kind}{tag}.json")
            if os.path.exists(path) and not args.force:
                print(f"[skip-cached] {path}")
                continue
            print(f"[dryrun] {arch} x {shape} x {mesh_kind} ...",
                  flush=True)
            try:
                res = lower_cell(arch, shape, mesh_kind, overrides,
                                 skip_cost_passes=args.fast)
            except Exception as e:       # record the failure, keep going
                res = {"arch": arch, "shape": shape, "mesh": mesh_kind,
                       "status": "error", "error": repr(e),
                       "traceback": traceback.format_exc()[-4000:]}
            with open(path, "w") as f:
                json.dump(res, f, indent=1)
            status = res["status"]
            extra = ""
            if status == "ok":
                r = res["roofline"]
                extra = (f" bottleneck={r['bottleneck']}"
                         f" frac={r['roofline_fraction']:.3f}"
                         f" compile={res['compile_s']}s"
                         f"+{res.get('cost_pass_s', 0)}s")
            print(f"[done] {arch} x {shape} x {mesh_kind}: "
                  f"{status}{extra}", flush=True)


if __name__ == "__main__":
    main()
