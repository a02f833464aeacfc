"""Find an open-loop cell's knee: the highest offered rate that the engine
keeps up with.  Run once, when a cell is defined; the cell file then
fixes its rate at about four fifths of the knee.

  python3 bench/knee.py --workload <cell> --seconds 30 \
      --rates 1,1.5,2,2.5 --seeds 5,6,7

One process builds the cell once (weights, core, engine, warm-up), then
for each rate and seed serves the cell's mix at that rate, with the
mix's pre-roll, for --seconds, and serves the engine idle before the
next.  One line per rate and seed: the queue depth over the first and
last thirds of the window, the share of the requests due in the window
that reached a slot by its close, output tokens per second against the
output tokens the due requests ask for per second, and TTFT percentiles.
A rate keeps up when, on every seed, the queue does not grow (last third
within one request of the first), at least 99% of the due requests are
admitted, and the tokens served are at least 95% of those asked for.
The last line names the highest rate that keeps up.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the TPU runtime would log to a fixed /tmp path
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def keeps_up(row: dict) -> bool:
    return (row["queue_last_third"] <= row["queue_first_third"] + 1.0
            and row["admitted_share"] >= 0.99
            and row["output_tokens_per_s"] >= 0.95 * row["asked_tokens_per_s"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seeds", default="5")
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import numpy as np
    from bench.lib import weights, window
    from bench.lib.cell import RECORD_DIR, _build, device_info
    from bench.lib.spec import Bench
    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    bench = Bench()
    w = bench.workload(args.workload)
    conf, mix = bench.config(w["config"]), bench.traffic(w["traffic"])
    gen = bench.generator(mix)
    cell = bench.cell(args.workload)
    device_info(w["chips"])
    seeds = [int(s) for s in args.seeds.split(",")]
    cfg = weights.model_config(conf)
    params = weights.make_params(cfg, seeds[0])
    _, _, engine = _build(cfg, conf, cell, params, "int8", seeds[0])
    window.warm(engine, cell["slots"], cfg.vocab)
    rows = []
    for rate in (float(r) for r in args.rates.split(",")):
        for seed in seeds:
            sched = gen.schedule(mix, dict(cell, rate_req_s=rate), seed,
                                 cfg.vocab)
            first = len(engine.queue_depth_samples)
            win = window.run(engine, sched, args.seconds, cell["slots"],
                             backlog=False, drain_s=0.0,
                             preroll_s=float(mix.get("preroll_s", 0.0)))
            depth = engine.queue_depth_samples[first:]
            depth = depth[len(depth) - win.counters.steps:]
            third = max(1, len(depth) // 3)
            due = [r for r in win.recs if win.t_open <= r.due < win.t_close]
            admitted = [r for r in due if r.req.t_admit is not None
                        and r.submitted + r.req.t_admit - r.req.t_submit
                        < win.t_close]
            ttft = [r.times[0] - r.due for r in due if r.times]
            tokens = sum(win.t_open <= t < win.t_close
                         for r in win.recs for t in r.times)
            row = {"rate_req_s": rate, "seed": seed, "due": len(due),
                   "admitted_share": len(admitted) / max(1, len(due)),
                   "queue_first_third": float(np.mean(depth[:third])),
                   "queue_last_third": float(np.mean(depth[-third:])),
                   "output_tokens_per_s": tokens / args.seconds,
                   "asked_tokens_per_s": sum(r.req.max_new_tokens
                                             for r in due) / args.seconds,
                   "ttft_p50_ms": 1e3 * float(np.percentile(ttft, 50)),
                   "ttft_p95_ms": 1e3 * float(np.percentile(ttft, 95)),
                   "steps": win.counters.steps}
            row["keeps_up"] = keeps_up(row)
            rows.append(row)
            print(json.dumps(row), flush=True)
            window.drain(engine)
    kept = sorted({r["rate_req_s"] for r in rows}
                  - {r["rate_req_s"] for r in rows if not r["keeps_up"]})
    knee = kept[-1] if kept else None
    print(json.dumps({"knee_req_s": knee,
                      "rate_req_s": None if knee is None else 0.8 * knee}),
          flush=True)
    os.makedirs(RECORD_DIR, exist_ok=True)
    with open(RECORD_DIR / f"knee-{args.workload}.json", "w") as f:
        json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
