"""Readings that a cell's correctness limit is set from, in one process.

  python3 bench/calibrate.py --workload mamba2_offline --seconds 30 \
      --seeds 11,12,...,22 --control-seeds 91,92,93

For every seed it makes a whole run of the cell as `run.py` does (set-up,
the window at the cell's own load, the reference comparison) and records
the widest gap; then the same for the control: the program with its own
lower-precision weight path switched on (INT4, the step below the INT8
the configuration states), still compared against the INT8 weights'
float32 reference.  Where the family's module under families/ names
reference controls (`STATE_CONTROLS`: the ssm and hybrid families'
float32 SSM state rounded to bfloat16 after every update), each program
run also reads them on the same sample, each put in the program's
place (the gap of the token it puts first).  The limit goes above the
program's largest reading and below
the controls' smallest.  The benchmark's own runs never run a control.
Prints one line per run and writes them all to
chiprun_out/bench/calibrate-<workload>.json.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the TPU runtime would log to a fixed /tmp path
os.environ.setdefault("TPU_LOG_DIR", "disabled")
# the program's own weight path one precision step below the INT8 the
# configurations state
CONTROL = "int4"


def state_controls(name: str) -> dict:
    """The reference controls that family `name` names in its families/
    module ({control: options of its reference's `logits`}), or none."""
    from bench.lib.spec import family
    return getattr(family(name), "STATE_CONTROLS", {})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench.lib.cell import RECORD_DIR, run_cell
    from bench.lib.spec import Bench
    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    bench = Bench()
    controls = state_controls(
        bench.config(bench.workload(args.workload)["config"])["family"])
    seeds = lambda s: [int(x) for x in s.split(",") if x]   # noqa: E731
    rows = []
    for precision, group in (("int8", seeds(args.seeds)),
                             (CONTROL, seeds(args.control_seeds))):
        for seed in group:
            r = run_cell(bench, args.workload, seed, args.seconds, False,
                         time.perf_counter(), precision=precision,
                         controls=(controls if precision == "int8"
                                   else None))
            run = r["run"]
            row = {"precision": precision, "seed": seed,
                   "max_gap": r["checks"]["max_gap"]["value"],
                   "controls": run["control_gaps"],
                   "tokens_compared": run["tokens_compared"],
                   "sample_requests": run["sample_requests"],
                   "metrics": {k: v["value"]
                               for k, v in r["metrics"].items()}}
            rows.append(row)
            print(json.dumps(row), flush=True)
            del r
            gc.collect()
    os.makedirs(RECORD_DIR, exist_ok=True)
    with open(RECORD_DIR / f"calibrate-{args.workload}.json", "w") as f:
        json.dump(rows, f, indent=1)
    readings = {p: [r["max_gap"] for r in rows if r["precision"] == p]
                for p in ("int8", CONTROL)}
    for name in controls:
        readings[name] = [r["controls"][name] for r in rows
                          if name in r["controls"]]
    for name, gaps in readings.items():
        if gaps:
            print(f"{name}: max_gap over {len(gaps)} seeds: min "
                  f"{min(gaps)!r}, max {max(gaps)!r}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
