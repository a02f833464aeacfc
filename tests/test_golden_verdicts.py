"""Golden verdict fingerprint for the full planner grid.

tests/golden/planner_verdicts.csv pins the What/When/Where verdict of
every GEMM in the full llm_workloads set under the standard configs,
widened over every axis the planner decides on: all assigned archs x
(train_4k + decode_32k + the prefill/decode serving-phase workloads) x
every supported precision (INT8/INT4/FP8).  The standard configs span
all four Table-IV prototypes (analog and digital), so one row's verdict
already reflects the full What axis; precision and phase multiply the
row grid itself.  Any backend or cost-model change that silently drifts
a verdict fails here with a per-row diff — naming the GEMM, the golden
verdict and the new one — instead of shipping a quiet behavioural
change.  Both batched backends (vectorized XLA and the fused Pallas
kernel) are asserted against the same file, which also gates the
acceptance criterion that plan_workload(backend="pallas") matches the
vectorized backend on the full grid.

Intentional verdict changes regenerate the file:

    PYTHONPATH=src python tests/test_golden_verdicts.py

and the diff lands in review along with the change that caused it.
"""
import csv
import os

from repro.configs import ARCHS, SHAPES
from repro.core.campaign import parse_precision
from repro.core.llm_workloads import gemms_of_model, phase_gemms_of_model
from repro.core.planner import plan_workload

GOLDEN = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "golden", "planner_verdicts.csv")
GRID_SHAPES = ("train_4k", "decode_32k")
# the serving-phase grid: the shapes DecodeCore plans per phase (prefill
# at M = seq_len, decode at M = batch) — phase verdicts are pinned here
# so a cost-model change that flips a phase gate shows up as a row diff.
PHASE_SEQ_LEN, PHASE_BATCH = 2048, 8
PRECISIONS = ("int8", "int4", "fp8")
FIELDS = ("arch", "shape", "precision", "label", "M", "N", "K",
          "best_energy", "best_throughput", "use_cim", "where")
N_GRID = 1746


def _grid():
    for arch, mc in ARCHS.items():
        workloads = [(sname, gemms_of_model(mc, SHAPES[sname]))
                     for sname in GRID_SHAPES]
        phases = phase_gemms_of_model(mc, PHASE_SEQ_LEN, PHASE_BATCH)
        workloads += [(f"phase-{ph}", gs) for ph, gs in phases.items()]
        for sname, gemms in workloads:
            for g in gemms:
                for tok in PRECISIONS:
                    bits, fp, _ = parse_precision(tok)
                    yield (arch, sname, tok,
                           g if (g.bits == bits and g.fp == fp)
                           else g.scaled(bits=bits, fp=fp))


def _verdict_rows(backend: str = "vectorized", plan=None) -> list[dict]:
    """Verdict rows of the full grid, in golden-CSV field conventions.

    `plan` overrides how the decisions are produced (gemms -> decisions)
    — the distributed parity worker routes through its multi-host engine
    here, so the formatting the bitwise comparison depends on has
    exactly one definition."""
    entries = list(_grid())
    gemms = [g for _, _, _, g in entries]
    decisions = (plan(gemms) if plan is not None
                 else plan_workload(gemms, backend=backend))
    return [{"arch": arch, "shape": sname, "precision": prec,
             "label": g.label,
             "M": str(g.M), "N": str(g.N), "K": str(g.K),
             "best_energy": d.best_energy,
             "best_throughput": d.best_throughput,
             "use_cim": str(int(d.use_cim)), "where": d.where}
            for (arch, sname, prec, g), d in zip(entries, decisions)]


def _assert_matches_golden(backend: str) -> None:
    with open(GOLDEN) as f:
        golden = list(csv.DictReader(f))
    got = _verdict_rows(backend)
    assert len(golden) == N_GRID, (
        f"golden file has {len(golden)} rows, expected {N_GRID} — "
        f"regenerate it (see module docstring)")
    assert len(got) == N_GRID, (
        f"workload grid produced {len(got)} GEMMs, expected {N_GRID} — "
        f"llm_workloads changed; regenerate the golden file")
    diffs = []
    for i, (want, have) in enumerate(zip(golden, got)):
        delta = [f"{k}: golden={want[k]!r} got={have[k]!r}"
                 for k in FIELDS if want[k] != have[k]]
        if delta:
            diffs.append(f"  row {i} [{want['arch']}/{want['shape']}/"
                         f"{want['precision']}/{want['label']}]: "
                         + "; ".join(delta))
    assert not diffs, (
        f"{backend} backend drifted from the golden verdicts on "
        f"{len(diffs)}/{N_GRID} rows:\n" + "\n".join(diffs[:25])
        + ("\n  ..." if len(diffs) > 25 else "")
        + "\nIf the drift is intentional, regenerate tests/golden/"
          "planner_verdicts.csv (see module docstring).")


def test_golden_verdicts_vectorized():
    _assert_matches_golden("vectorized")


def test_golden_verdicts_pallas():
    """The full-grid pallas gate: identical What/When/Where verdicts to
    the committed fingerprint (and therefore to the vectorized backend)
    on every (arch, shape/phase, precision) row of the widened grid."""
    _assert_matches_golden("pallas")


if __name__ == "__main__":
    rows = _verdict_rows("vectorized")
    os.makedirs(os.path.dirname(GOLDEN), exist_ok=True)
    with open(GOLDEN, "w", newline="") as f:
        writer = csv.DictWriter(f, FIELDS)
        writer.writeheader()
        writer.writerows(rows)
    print(f"wrote {len(rows)} verdict rows to {GOLDEN}")
