"""Benchmark runner (deliverable d): one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV to stdout and writes full row CSVs
to results/bench/.
"""
from __future__ import annotations

import csv
import json
import os
import time

from . import paper_benches as P
from . import llm_planner_bench as L
from . import sweep_bench as S
from . import serve_gating_bench as G
from . import campaign_bench as C
from repro.launch.compile_cache import configure_compile_cache

BENCHES = [
    ("fig2_gemm_landscape", P.fig2_gemm_landscape),
    ("fig7_table2_mapping_vs_heuristic", P.fig7_table2_mapping_vs_heuristic),
    ("fig9_primitive_scatter", P.fig9_primitive_scatter),
    ("fig10_dimension_sweeps", P.fig10_dimension_sweeps),
    ("fig11_12_memory_levels", P.fig11_12_memory_levels),
    ("fig13_square_gemms", P.fig13_square_gemms),
    ("table6_workload_characteristics", P.table6_workload_characteristics),
    ("llm_planner_decisions", L.planner_decisions),
    ("planner_sweep_speed", S.planner_sweep_speed),
    ("campaign_speed", C.campaign_speed),
    ("serve_gating_speed", G.serve_gating_speed),
]


def main() -> None:
    configure_compile_cache()
    outdir = os.path.join("results", "bench")
    os.makedirs(outdir, exist_ok=True)
    print("name,us_per_call,derived")
    for name, fn in BENCHES:
        t0 = time.perf_counter()
        rows, derived = fn()
        dt = time.perf_counter() - t0
        us = 1e6 * dt / max(1, len(rows))
        with open(os.path.join(outdir, f"{name}.csv"), "w", newline="") as f:
            if rows:
                w = csv.DictWriter(f, fieldnames=list(rows[0].keys()))
                w.writeheader()
                w.writerows(rows)
        with open(os.path.join(outdir, f"{name}.derived.json"), "w") as f:
            json.dump(derived, f, indent=1, default=str)
        print(f"{name},{us:.1f},{json.dumps(derived, default=str)!r}")


if __name__ == "__main__":
    main()
