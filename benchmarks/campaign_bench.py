"""Design-space campaign benchmark: streaming frontier determinism gates.

Runs a fixed mid-size campaign grid (mistral-nemo-12b x {train_4k,
decode_32k} x 4 prototypes x 3 precisions (INT8/INT4/FP8) x 3 levels x
2 scales x 2 order modes, 2880 points, grouped per GEMM so cross-chunk
front merging is load-bearing) and gates the properties the frontier
artifacts rest on:

  * determinism — two back-to-back runs on fresh engines must produce
    **byte-identical** frontier CSVs (the golden front test and the
    results/ artifacts assume repr-stable float32 metrics and
    enumeration-order-canonical emission; any nondeterminism shows up
    here first),
  * chunk parity — a chunk-streaming engine (chunk_rows=512, >= 2
    device chunks) must reproduce the whole-batch CSV byte for byte,
  * backend parity — the pallas sweep kernel must reproduce the
    vectorized CSV byte for byte,
  * certification — each workload cell's energy champion must pass the
    bitwise re-evaluation gate through the planner (certify_front).

Timings record the streaming run (points/s through the chunked engine)
and the reduction overhead so the trajectory tracks campaign throughput
PR over PR.

Results merge into BENCH_planner.json under the `campaign` block
(sweep_bench owns the other keys and preserves this one; $BENCH_PLANNER_OUT
overrides the path).  A run failing any gate is quarantined to *.failed
— the trusted trajectory entry is left untouched — and running this
module directly (as the CI `campaign-bench` job does) then exits
nonzero.

Run directly:  PYTHONPATH=src python -m benchmarks.campaign_bench
"""
from __future__ import annotations

import hashlib
import json
import os
import socket
import subprocess
import sys
import time
from datetime import datetime, timezone

import jax

from repro.core.campaign import (CampaignSpec, Constraint, certify_front,
                                 run_campaign)
from repro.core.sweep import SweepEngine
from repro.launch.compile_cache import configure_compile_cache

# same grid family as tests/golden/campaign_front.csv: big enough that
# the chunked run streams >= 2 chunks, small enough for a CI job
SPEC = CampaignSpec(
    workloads=(("mistral-nemo-12b", "train_4k"),
               ("mistral-nemo-12b", "decode_32k")),
    prototypes=("Analog-6T", "Analog-8T", "Digital-6T", "Digital-8T"),
    precisions=("int8", "int4", "fp8"),
    levels=("RF", "SMEM-A", "SMEM-B"),
    scales=(1.0, 4.0),
    order_modes=("exact", "greedy"),
)
CONTRACTS = (Constraint("area_bytes", "<=", 1e8),)
CHUNK_ROWS = 512
BLOCK_POINTS = 256


def _provenance() -> dict:
    try:
        # --dirty marks artifacts produced by uncommitted code: the bare
        # sha alone would claim a commit that cannot reproduce the run
        sha = subprocess.check_output(
            ["git", "describe", "--always", "--dirty"], text=True,
            stderr=subprocess.DEVNULL).strip()
    except Exception:
        sha = "unknown"
    return {"git_sha": sha,
            "host": socket.gethostname(),
            "timestamp_utc": datetime.now(timezone.utc)
            .strftime("%Y-%m-%dT%H:%M:%SZ"),
            "jax": jax.__version__,
            "device": jax.devices()[0].platform}


def _run(backend: str = "vectorized", chunk_rows: int | None = None):
    """(csv text, sha256, stats, seconds) of one fresh-engine run."""
    engine = SweepEngine(mesh=None, chunk_rows=chunk_rows)
    t0 = time.perf_counter()
    result = run_campaign(SPEC, CONTRACTS, engine=engine,
                          backend=backend, block_points=BLOCK_POINTS,
                          group_by="gemm")
    seconds = time.perf_counter() - t0
    text = result.csv_text()
    sha = hashlib.sha256(text.encode()).hexdigest()
    return result, text, sha, seconds


def campaign_speed(write_json: bool = True):
    # --- determinism gate: two cold runs, byte-identical CSVs
    res_a, text_a, sha_a, s_a = _run()
    _, text_b, sha_b, s_b = _run()
    determinism_ok = text_a == text_b

    # --- chunk parity: the streaming engine reproduces the whole batch
    res_c, text_c, sha_c, s_c = _run(chunk_rows=CHUNK_ROWS)
    chunk_tel = res_c.stats["engine_chunks"]
    chunk_parity_ok = text_c == text_a
    chunks_streamed_ok = chunk_tel["evaluated"] >= 2

    # --- backend parity: pallas == vectorized, byte for byte (on
    # platforms without a pallas lowering the engine falls back to the
    # XLA kernel, which must still reproduce the CSV)
    _, text_p, _, s_p = _run(backend="pallas")
    pallas_parity_ok = text_p == text_a

    # --- certification gate: every cell's energy champion re-evaluates
    # bitwise through the planner and still meets the contracts
    t0 = time.perf_counter()
    cert = certify_front(res_a, objectives=("energy_pj",))
    cert_s = time.perf_counter() - t0
    certification_ok = cert["ok"]

    gates = {
        "determinism_ok": determinism_ok,
        "chunk_parity_ok": chunk_parity_ok,
        "chunks_streamed_ok": chunks_streamed_ok,
        "pallas_parity_ok": pallas_parity_ok,
        "certification_ok": certification_ok,
    }
    for name, ok in gates.items():
        if not ok:
            print(f"WARNING: campaign bench gate {name} failed — "
                  f"quarantining this run", file=sys.stderr)

    n_points = res_a.stats["n_points"]
    block = {
        "grid": {"n_points": n_points,
                 "digest": SPEC.digest(),
                 "contracts": [c.spec() for c in CONTRACTS],
                 "group_by": "gemm"},
        "front_rows": len(res_a.front),
        "frontier_sha256": sha_a,
        "run_s": round(s_a, 3),
        "rerun_s": round(s_b, 3),
        "chunked_s": round(s_c, 3),
        "pallas_s": round(s_p, 3),
        "certify_s": round(cert_s, 3),
        "points_per_s": round(n_points / s_c, 1),
        "chunks": chunk_tel,
        "certified_points": len(cert["points"]),
        "gates": gates,
        "provenance": _provenance(),
    }
    rows = [{"backend": "campaign_vectorized", "seconds": round(s_a, 4)},
            {"backend": f"campaign_streamed_{chunk_tel['evaluated']}"
                        f"chunks_{CHUNK_ROWS}rows",
             "seconds": round(s_c, 4)},
            {"backend": "campaign_pallas", "seconds": round(s_p, 4)},
            {"backend": "campaign_certify", "seconds": round(cert_s, 4)}]

    if write_json:
        out = os.environ.get("BENCH_PLANNER_OUT", "BENCH_planner.json")
        # merge into the shared trajectory file: sweep_bench owns every
        # other key and preserves `campaign` symmetrically
        merged = {}
        if os.path.exists(out):
            try:
                with open(out) as f:
                    merged = json.load(f)
            except (json.JSONDecodeError, OSError):
                merged = {}
        merged["campaign"] = block
        if not all(gates.values()):
            # quarantine: leave the trusted entry untouched, park the
            # failing run (with its gate flags) next to it
            out += ".failed"
        with open(out, "w") as f:
            json.dump(merged, f, indent=1)
    return rows, block


if __name__ == "__main__":
    configure_compile_cache()
    _, block = campaign_speed()
    print(json.dumps(block, indent=1))
    # CI runs this module directly: a determinism or parity break must
    # turn the job red, not just ship a quarantined artifact
    failed = [g for g, ok in block["gates"].items() if not ok]
    if failed:
        sys.exit(f"campaign bench gates failed: {', '.join(failed)} — "
                 f"artifact quarantined to *.failed (two back-to-back "
                 f"runs must produce byte-identical frontier CSVs, "
                 f"chunked + pallas runs must match them, and champions "
                 f"must certify bitwise)")
