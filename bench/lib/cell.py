"""One run of one cell: set-up, the measured window, the metrics and the
comparison with the plain reference.

Set-up (all counted in setup_s): the device check, the config and the
weights drawn on the device from the seed, the plan-gated INT8 core
(`DecodeCore(quantize=True)`, which builds the planner's per-phase
tables) and the continuous-batching engine, both built as the serve
CLI's traffic mode builds them, and a warm-up through the engine that
compiles (or loads from the persistent cache) every program the cell
runs, and the priming or pre-roll that brings the engine to a loaded
state (`window.run`, before its window opens).  Then the window, then,
with the engine freed, the reference comparison, which is not counted
anywhere.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import math
import os
import shutil
import time

import jax
import numpy as np

from . import check, flops, trace as trace_mod, weights, window
from .spec import REPO_ROOT, Bench

# the traced run traces the last TRACE_SHARE of its window, at most
# TRACE_MAX_S seconds
TRACE_SHARE = 0.5
TRACE_MAX_S = 8.0
# device ops of the INT8 GEMM kernel in a TPU trace: the Pallas call's
# custom-call takes the name of its jitted wrapper, ops.int8_matmul
KERNEL_PATTERN = r"^int8_matmul(\.\d+)*$"
RECORD_DIR = REPO_ROOT / "chiprun_out" / "bench"


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


def device_info(chips: int, require_tpu: bool = True) -> dict:
    devs = jax.devices()
    dev = devs[0]
    if require_tpu and dev.platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {dev.platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}


def percentile(values, q: float) -> float | None:
    return float(np.percentile(values, q)) if len(values) else None


@dataclasses.dataclass
class Run:
    """Everything a metric reader may read about one run."""
    conf: dict
    cell: dict
    mix: dict
    peaks: dict
    seconds: float
    setup_s: float
    window: window.Window
    peak_bytes: int | None
    kernel_least_s: dict         # phase -> least time of one step's calls
    trace: dict | None = None    # trace.reduce() + steps, phase_steps,
                                 # model_flops of the traced window

    def due_in_window(self) -> list:
        w = self.window
        return [r for r in w.recs if w.t_open <= r.due < w.t_close]

    def tokens_in_window(self) -> int:
        w = self.window
        return sum(w.t_open <= t < w.t_close for r in w.recs for t in r.times)

    def host_ms_per_step(self) -> float | None:
        c = self.window.counters
        if not c.steps:
            return None
        return 1e3 * (c.dispatch_s + c.telemetry_s) / c.steps


def _build(cfg, conf, cell, params, precision: str, seed: int):
    """The core and engine as `launch.serve.run_traffic` builds them."""
    from repro.configs import RunConfig
    from repro.serving import ContinuousBatchingEngine, DecodeCore
    rc = RunConfig(attn_impl="naive", remat=False,
                   kv_cache_dtype="bfloat16")
    core = DecodeCore(cfg, rc, params, quantize=True, precision=precision,
                      plan_batch=cell["slots"], plan_max_len=cell["max_len"])
    engine = ContinuousBatchingEngine(
        core, n_slots=cell["slots"], max_len=cell["max_len"],
        block_size=cell["block_size"], seed=seed)
    return rc, core, engine


def kernel_calls(core, cfg, rc, cell, params, peaks) -> dict:
    """Per phase plan: the route of every projection label and the least
    time of one step's INT8 GEMM kernel calls, each call's (M, N, K) as
    the step lowers it (an abstract trace, nothing runs)."""
    from repro.models import decode_step
    from repro.models.layers import CIM_ROUTE, route_trace
    from repro.models.model import init_paged_cache, n_periods
    slots = cell["slots"]
    max_blocks = math.ceil(cell["max_len"] / cell["block_size"])
    cache = jax.eval_shape(lambda: init_paged_cache(
        cfg, rc, slots, slots * max_blocks, cell["block_size"]))
    sds = jax.ShapeDtypeStruct
    args = (params, cache, sds((slots, 1), np.int32),
            sds((slots,), np.int32), sds((slots,), np.bool_),
            sds((slots, max_blocks), np.int32))
    out = {}
    for phase, table in (("decode", core.plan_table),
                         ("prefill", core.prefill_plan_table)):
        with route_trace() as recs:
            jax.eval_shape(
                lambda p, c, t, q, a, b: decode_step(
                    p, c, t, q, cfg, rc, plan=table, active=a,
                    block_tables=b), *args)
        least = 0.0
        for r in recs:
            if r["route"] == CIM_ROUTE and r["shape"] is not None:
                count = 1 if r["label"] == "lm_head" else n_periods(cfg)
                least += count * flops.least_time(
                    *flops.int8_gemm_cost(*r["shape"]), peaks)
        out[phase] = {"routes": {r["label"]: r["route"] for r in recs},
                      "least_s": least}
    return out


class _Tracer:
    """Traces the last part of the window and counts the model FLOPs of
    the steps inside it.  The "bench.trace" span closes with the window;
    the profiler stops only after the run (stopping it writes the trace,
    which would stall the loop)."""

    def __init__(self, engine, conf, seconds: float, directory):
        self.engine, self.conf = engine, conf
        self.seconds = seconds
        self.start = seconds - min(TRACE_MAX_S, TRACE_SHARE * seconds)
        self.dir = str(directory)
        self.state = "before"
        self.ann = None
        self.flops = 0.0
        self.snap = None

    def _counts(self):
        return self.engine.steps, dict(self.engine.phase_steps)

    def __call__(self, now: float, t_open: float) -> None:
        if self.state == "before" and now >= t_open + self.start:
            shutil.rmtree(self.dir, ignore_errors=True)
            jax.profiler.start_trace(self.dir)
            self.ann = jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN)
            self.ann.__enter__()
            self.snap = self._counts()
            self.state = "on"
        elif self.state == "on":
            lanes = [s for s in self.engine.slots if s is not None]
            self.flops += flops.step_model_flops(
                self.conf, len(lanes), sum(s.pos for s in lanes))
            if now >= t_open + self.seconds:
                self.ann.__exit__(None, None, None)
                steps, phases = self._counts()
                self.steps = steps - self.snap[0]
                self.phase_steps = {k: v - self.snap[1].get(k, 0)
                                    for k, v in phases.items()}
                self.state = "closed"

    def finish(self) -> bool:
        """Stop the profiler; True when a whole traced span was taken."""
        if self.state == "before":
            return False
        if self.state == "on":
            self.__call__(float("inf"), 0.0)
        jax.profiler.stop_trace()
        return True


def run_cell(bench: Bench, workload: str, seed: int, seconds: float,
             trace: bool, t_start: float, require_tpu: bool = True,
             precision: str = "int8", controls: dict | None = None
             ) -> dict:
    """One run; returns the result line's object.  `precision` other
    than the configured int8 runs the program's own lower-precision path
    (the control), still compared against the int8 reference.
    `controls` ({name: options of the family reference's `logits`}) reads,
    on the same sample, the widest gap of each reference control put in
    the program's place (run["control_gaps"]); the benchmark's own runs
    pass none."""
    w = bench.workload(workload)
    conf = bench.config(w["config"])
    mix = bench.traffic(w["traffic"])
    cell = bench.cell(workload)
    device = device_info(w["chips"], require_tpu)
    peaks = bench.peaks(device["kind"])
    parts = {"start": time.perf_counter() - t_start}
    cfg = weights.model_config(conf)
    params = weights.make_params(cfg, seed)
    served = (params if precision == "int8"
              else weights.requantized(params, precision))
    jax.block_until_ready(served)
    parts["weights"] = time.perf_counter() - t_start
    rc, core, engine = _build(cfg, conf, cell, served, precision, seed)
    del served
    calls = kernel_calls(core, cfg, rc, cell, params, peaks)
    parts["core"] = time.perf_counter() - t_start
    window.warm(engine, cell["slots"], cfg.vocab)
    parts["warm"] = time.perf_counter() - t_start
    gen = bench.generator(mix)
    sched = gen.schedule(mix, cell, seed, cfg.vocab)
    backlog = mix["arrival"]["kind"] == "backlog"
    primed = (gen.primed(mix, cell, seed, cfg.vocab)
              if mix.get("prime") == "steady" else ())
    tracer = None
    if trace:
        tracer = _Tracer(engine, conf, seconds,
                         RECORD_DIR / "trace" / workload)
    # set-up ends when the window opens: after the priming step or the
    # pre-roll that bring the engine to a loaded state
    win = window.run(engine, sched, seconds, cell["slots"], backlog,
                     tracer=tracer, drain_s=0.0 if backlog
                     else window.DRAIN_S,
                     preroll_s=float(mix.get("preroll_s", 0.0)),
                     primed=primed)
    setup_s = win.t_open - t_start
    parts["loaded"] = setup_s
    traced = tracer is not None and tracer.finish()
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    reduced = None
    if traced:
        reduced = trace_mod.reduce(
            trace_mod.extract(trace_mod.latest_xplane(tracer.dir)),
            KERNEL_PATTERN)
        reduced.update(steps=tracer.steps, phase_steps=tracer.phase_steps,
                       model_flops=tracer.flops)
        shutil.rmtree(tracer.dir, ignore_errors=True)
    run = Run(conf, cell, mix, peaks, seconds, setup_s, win, peak,
              {ph: c["least_s"] for ph, c in calls.items()}, reduced)
    metrics = {}
    for m in bench.metrics(workload, trace):
        value = bench.reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    executables = engine.telemetry()["aggregate"]["phase_gating"]
    bits_short = check.cache_bits_short(engine.cache, conf["cache_dtypes"])
    # free the program's state before the reference runs
    del engine, core, tracer
    gc.collect()
    picked = check.sample(win.recs, seed)
    reference = bench.reference(conf["family"])
    gaps = check.served_gaps(reference, conf, params, picked,
                             cell["max_len"])
    correct, checks = check.judge(gaps, cell["limits"]["max_gap"],
                                  bits_short)
    control = {name: float(np.max(check.control_gaps(
        reference, conf, params, picked, cell["max_len"], **opts)))
        for name, opts in (controls or {}).items() if picked}
    attempted = (len(win.recs) if backlog
                 else len(run.due_in_window()))
    failed = (sum(not r.times for r in win.recs if r.req.state == "done")
              if backlog else sum(not r.times
                                  for r in run.due_in_window()))
    late = [r.submitted - r.due for r in win.recs]
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics,
           "device": dict(device, memory_peak_bytes=peak)}
    if reduced is not None:
        out["device"].update(busy_s=reduced["busy_s"],
                             window_s=reduced["window_s"])
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    out["run"] = {
        "workload": workload, "seed": seed, "seconds": seconds,
        "trace": trace, "precision": precision,
        "setup_parts_s": parts,
        "compiles_in_window": win.compiles,
        "longest_steps_s": win.longest_steps,
        "gc_in_window_s": {"count": len(win.gc_s), "sum": sum(win.gc_s),
                           "max": max(win.gc_s, default=0.0)},
        "requests_submitted": len(win.recs),
        "requests_primed": len(primed),
        "requests_finished": len(check.finished(win.recs)),
        "steps_in_window": win.counters.steps,
        "host_ms_per_step": run.host_ms_per_step(),
        "phase_steps_in_window": win.counters.phase_steps,
        "generator_late_p95_ms": 1e3 * (percentile(late, 95) or 0.0),
        "drain_s": win.t_end - win.t_close,
        "sample_requests": len(picked),
        "tokens_compared": int(gaps.size),
        "control_gaps": control,
        "phase_gating": executables,
        "routes": {ph: c["routes"] for ph, c in calls.items()},
        "kernel_least_ms_per_step": {ph: 1e3 * c["least_s"]
                                     for ph, c in calls.items()},
        "trace_summary": (None if reduced is None else {
            k: reduced[k] for k in ("kernel_s", "steps", "phase_steps",
                                    "model_flops", "devices")}),
    }
    out["requests"] = [
        [round(r.due - win.t_open, 4), round(r.submitted - r.due, 4),
         None if r.req.t_admit is None else round(
             r.submitted + r.req.t_admit - r.req.t_submit - r.due, 4),
         None if not r.times else round(r.times[0] - r.due, 4),
         r.req.prompt_len, len(r.times)] for r in win.recs]
    out["checks"] = checks
    return out


def write_record(result: dict) -> str:
    """Keep the whole result under the ignored chiprun_out/; the printed
    line leaves out the per-request rows ([due, submitted late, queue
    wait, TTFT, prompt tokens, tokens] in seconds from the window's
    opening)."""
    r = result["run"]
    path = RECORD_DIR / r["workload"] / (
        f"{r['seed']}-trace{int(r['trace'])}-{r['precision']}.json")
    os.makedirs(path.parent, exist_ok=True)
    with open(path, "w") as f:
        json.dump(result, f, indent=1)
    return str(path)
