"""Run one cell of the benchmark on the chip this process holds.

  python3 bench/run.py --workload mamba2_offline --seed 7 --seconds 30 \
      --trace 0

The cell (`BENCHMARK.json` `workloads`) names a configuration
(bench/configs/), a traffic mix (bench/traffic/) and its own engine size
and rate (bench/cells/).  The run draws weights and traffic from --seed,
serves the traffic through the plan-gated INT8 continuous-batching
engine for --seconds, compares a sample of what it served with the plain
float32 reference, and prints one JSON line last on stdout: the cell's
end-to-end metrics (--trace 0) or its per-layer metrics from a profiler
trace of part of the window (--trace 1).  The numbers compared with the
reference are the last lines of stderr.  The whole result also goes to
chiprun_out/bench/<workload>/.

It exits non-zero, printing no result, when JAX finds no TPU, fewer
chips than the cell asks for, or a device_kind that the peaks table
(bench/peaks.json) does not list.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the TPU runtime would log to a fixed /tmp path
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench.lib.cell import NoChip, run_cell, write_record
    from bench.lib.spec import Bench, UnknownDevice
    from repro.launch.compile_cache import configure_compile_cache

    bench = Bench()
    configure_compile_cache()
    try:
        result = run_cell(bench, args.workload, args.seed, args.seconds,
                          bool(args.trace), T_START)
    except (NoChip, UnknownDevice) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    write_record(result)
    r = result["run"]
    print(f"bench: set-up {r['setup_parts_s']}; window: "
          f"{r['steps_in_window']} steps, {r['requests_submitted']} "
          f"requests submitted, {r['requests_finished']} finished; "
          f"{r['tokens_compared']} tokens compared", file=sys.stderr)
    if result["run"]["compiles_in_window"]:
        print(f"bench: {result['run']['compiles_in_window']} programs "
              f"traced or compiled inside the window", file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"{name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    del result["requests"]
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
