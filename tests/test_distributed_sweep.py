"""Multi-host distributed sweep engine: parity, streaming, telemetry.

The fast tier covers everything that runs in one process: the streaming
chunk enumerator (bitwise parity against the whole-batch path, group
splitting across tiles, telemetry accounting), `launch.distributed`'s
init/env plumbing, and the report-layer rendering of the new telemetry
blocks.

The @slow test is the acceptance gate modeled on PR 2's 4-device
subprocess test: it spawns 2 real OS processes that initialize
`jax.distributed` over localhost (env-var driven, CPU gloo collectives),
build ONE global row mesh spanning both processes' devices, and plan the
full 1542-row golden workload grid through the chunked distributed
engine.  Both processes must reproduce tests/golden/planner_verdicts.csv
bitwise — the same fingerprint the single-process backends are pinned to
— with the grid forced through >= 2 streaming chunks.
"""
import csv
import json
import os
import socket
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.core import GEMM
from repro.core.planner import standard_configs
from repro.core.sweep import SweepEngine, _iter_chunks
from repro.launch import distributed as dist

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = standard_configs()
GEMMS = [GEMM(512, 1024, 1024), GEMM(1, 4096, 4096), GEMM(17, 100, 300)]


# --- streaming chunk enumerator (single process) ---------------------------


def test_chunked_engine_bitwise_parity():
    """chunk_rows bounds every device call without changing a single bit:
    rows are elementwise and the per-group reductions keep first-index
    tie-breaks across tiles.  chunk_rows=7 is deliberately awkward — it
    splits candidate-mapping groups mid-group and leaves ragged tails."""
    eu = SweepEngine(mesh=None)
    ec = SweepEngine(mesh=None, chunk_rows=7)
    pairs = [(g, CONFIGS[n]) for g in GEMMS
             for n in ("Digital-6T@RF", "Digital-6T@SMEM-B",
                       "Analog-8T@SMEM-A")]
    for om in ("exact", "greedy"):
        for a, b in zip(eu.cim_metrics(pairs, om),
                        ec.cim_metrics(pairs, om)):
            assert a.energy_pj == b.energy_pj     # bitwise, not approx
            assert a.time_ns == b.time_ns
            assert a.dram_bytes == b.dram_bytes
    for a, b in zip(eu.baseline_metrics(GEMMS[:2]),
                    ec.baseline_metrics(GEMMS[:2])):
        assert a.energy_pj == b.energy_pj
        assert a.time_ns == b.time_ns
    info = ec.cache_info()
    assert info["chunks"]["chunk_rows"] == 7
    assert info["chunks"]["evaluated"] >= 2       # grid really streamed
    assert info["chunks"]["rows"] > 0
    assert info["distributed"] is None            # single-host mesh


def test_iter_chunks_segments_cover_groups_exactly():
    """Every group row lands in exactly one tile segment, in order, and
    group offsets let a consumer reassemble per-group indices."""
    groups = [("a", {"x": np.arange(5.0)}),
              ("b", {"x": np.arange(100.0, 103.0)}),
              ("c", {"x": np.arange(200.0, 212.0)})]
    seen: dict = {}
    for batch, segs in _iter_chunks(iter(groups), chunk_rows=4):
        n = len(batch["x"])
        assert n <= 4
        for gid, off, lo, hi in segs:
            assert 0 <= lo < hi <= n
            seen.setdefault(gid, []).extend(
                (off + j, batch["x"][lo + j]) for j in range(hi - lo))
    for gid, cols in groups:
        idx, vals = zip(*seen[gid])
        assert list(idx) == list(range(len(cols["x"])))      # no gaps
        assert np.array_equal(np.asarray(vals), cols["x"])
    # chunk_rows=None degenerates to one tile holding everything
    tiles = list(_iter_chunks(iter(groups), chunk_rows=None))
    assert len(tiles) == 1 and len(tiles[0][0]["x"]) == 20


def test_chunk_rows_validation_and_cache_clear_resets_accounting():
    with pytest.raises(ValueError, match="chunk_rows"):
        SweepEngine(mesh=None, chunk_rows=0)
    eng = SweepEngine(mesh=None, chunk_rows=8)
    eng.cim_metrics([(GEMMS[0], CONFIGS["Digital-6T@RF"])])
    assert eng.cache_info()["chunks"]["evaluated"] >= 1
    eng.cache_clear()
    c = eng.cache_info()["chunks"]
    assert c["evaluated"] == c["rows"] == c["padded_rows"] == 0
    assert c["chunk_rows"] == 8                   # config survives clear


# --- launch.distributed plumbing (single process) --------------------------


def test_initialize_is_noop_when_unconfigured(monkeypatch):
    for var in (dist.ENV_COORDINATOR, dist.ENV_NUM_PROCESSES,
                dist.ENV_PROCESS_ID):
        monkeypatch.delenv(var, raising=False)
    assert dist.initialize() is False
    assert dist.is_initialized() is False


def test_initialize_rejects_partial_configuration(monkeypatch):
    monkeypatch.setenv(dist.ENV_COORDINATOR, "127.0.0.1:1")
    monkeypatch.delenv(dist.ENV_NUM_PROCESSES, raising=False)
    monkeypatch.delenv(dist.ENV_PROCESS_ID, raising=False)
    with pytest.raises(ValueError, match="num_processes/process_id"):
        dist.initialize()


def test_multihost_detection_and_shard_balance():
    from repro.launch.mesh import row_mesh
    mesh = row_mesh(jax.devices()[:1])
    assert dist.is_multihost(None) is False
    assert dist.is_multihost(mesh) is False       # all devices local
    assert dist.shard_balance(8, mesh) == {"0": 8}
    info = dist.distributed_info()
    assert info["processes"] == 1
    assert info["global_devices"] >= info["local_devices"] >= 1


def test_global_row_mesh_spans_all_devices():
    mesh = dist.global_row_mesh()
    assert mesh.size == jax.device_count()
    assert mesh.axis_names == ("rows",)


def test_host_local_to_global_round_trip():
    """On a single-host mesh the global-array builder is an exact
    identity: per-device slices reassemble to the input columns.  (The
    cross-host case is exercised end to end by the @slow subprocess
    test.)"""
    from repro.launch.mesh import row_mesh
    mesh = row_mesh(jax.devices()[:1])
    batch = {"a": np.arange(8, dtype=np.float32),
             "b": np.arange(8, 16, dtype=np.float32)}
    gb = dist.host_local_to_global(batch, mesh)
    for k, v in batch.items():
        assert np.array_equal(np.asarray(gb[k]), v)
        assert gb[k].sharding.mesh.size == 1


# --- report rendering ------------------------------------------------------


def _cell(engine_cache: dict) -> dict:
    return {"status": "ok", "arch": "a", "shape": "s", "mesh": "single",
            "planner": {"summary": {"cim_fraction": 0.5,
                                    "energy_gain_x": 2.0},
                        "plan_hits": 3, "plan_misses": 4,
                        "cache": engine_cache}}


def test_report_renders_chunk_and_shard_telemetry():
    """launch.report: the planner-cache table appends the streaming-tile
    accounting, and shard_balance_table renders the per-host cache + row
    balance of distributed cells (skipping single-host/legacy cells)."""
    from repro.launch.report import planner_cache_table, shard_balance_table
    distributed = {"processes": 2, "process_index": 0,
                   "global_devices": 2, "local_devices": 1,
                   "mesh_devices": 2,
                   "shard_balance": {"0": 2304, "1": 2304}}
    cache = {"hits": 7, "misses": 9, "size": 16,
             "chunks": {"chunk_rows": 512, "evaluated": 9,
                        "rows": 4403, "padded_rows": 205},
             "distributed": distributed}
    table = planner_cache_table([_cell(cache)])
    assert "chunks=9@512rows" in table
    balance = shard_balance_table([_cell(cache)])
    assert "p0/2" in balance and "p0:2304 p1:2304" in balance
    assert "7h/9m" in balance
    # single-host cells (distributed None) and legacy cells (no chunks
    # field at all) render without the new columns and without crashing
    legacy = {"hits": 1, "misses": 2, "size": 3}
    assert "size=3" in planner_cache_table([_cell(legacy)])
    assert "no distributed sweep telemetry" in shard_balance_table(
        [_cell(legacy), _cell({**cache, "distributed": None})])


# --- the multi-process acceptance gate -------------------------------------


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@pytest.mark.slow
def test_distributed_engine_matches_golden_fingerprint(tmp_path):
    """2 OS processes x jax.distributed x global row mesh x streaming
    chunks reproduce the single-process golden verdict fingerprint
    bitwise (tests/golden/planner_verdicts.csv — the full widened
    arch x shape/phase x precision grid), on every host."""
    nproc = 2
    out_base = str(tmp_path / "worker_out.json")
    env = dict(os.environ)
    env.update({
        "PYTHONPATH": os.path.join(REPO, "src"),
        "JAX_PLATFORMS": "cpu",
        dist.ENV_COORDINATOR: f"127.0.0.1:{_free_port()}",
        dist.ENV_NUM_PROCESSES: str(nproc),
        "WORKER_OUT": out_base,
        "WORKER_CHUNK_ROWS": "512",   # 1542-GEMM grid => >= 2 chunks/kind
    })
    worker = os.path.join(REPO, "tests", "_distributed_worker.py")
    procs = []
    try:
        for i in range(nproc):
            penv = dict(env)
            penv[dist.ENV_PROCESS_ID] = str(i)
            procs.append(subprocess.Popen(
                [sys.executable, worker], env=penv, cwd=REPO,
                stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True))
        outs = [p.communicate(timeout=540) for p in procs]
        for p, (so, se) in zip(procs, outs):
            assert p.returncode == 0, f"worker failed:\n{se[-2000:]}"
            assert "WORKER-OK" in so
    finally:
        # a hung worker (e.g. initialize() blocking on a runner without
        # CPU collectives) must not leak past the test: TimeoutExpired
        # or a mid-loop assert would otherwise leave both processes
        # alive holding the coordinator port
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()

    with open(os.path.join(REPO, "tests", "golden",
                           "planner_verdicts.csv")) as f:
        golden = list(csv.DictReader(f))
    payloads = []
    for i in range(nproc):
        with open(f"{out_base}.{i}") as f:
            payloads.append(json.load(f))
    for pay in payloads:
        assert pay["processes"] == nproc
        assert pay["global_devices"] >= nproc     # mesh spans both hosts
        assert pay["local_devices"] < pay["global_devices"]
        # the grid really streamed: >= 2 chunks, rows accounted for
        assert pay["chunks"]["evaluated"] >= 2
        assert pay["chunks"]["rows"] > 512
        d = pay["distributed"]
        assert d is not None and d["processes"] == nproc
        # shard balance covers every process and sums to the padded rows
        assert set(d["shard_balance"]) == {str(j) for j in range(nproc)}
        assert (sum(d["shard_balance"].values())
                == pay["chunks"]["rows"] + pay["chunks"]["padded_rows"])
        # THE gate: bitwise golden fingerprint, every field of every row
        assert len(pay["rows"]) == len(golden) == 1542
        for want, have in zip(golden, pay["rows"]):
            assert want == have, (want, have)
    # SPMD: both hosts computed the identical plan
    assert payloads[0]["rows"] == payloads[1]["rows"]
