"""Worker process for tests/test_distributed_sweep.py.

Each of the N cooperating OS processes runs this module: env-driven
`launch.distributed.initialize()` (REPRO_COORDINATOR / _NUM_PROCESSES /
_PROCESS_ID — the exact path a pod launcher uses), a `distributed_engine`
over the global row mesh with a chunk size forced small enough that the
golden grid streams through several tiles, then the full 1542-row
workload plan.  Every process writes its verdict rows + engine telemetry
to $WORKER_OUT.<process_index> so the driver can assert (a) bitwise
verdict equality with tests/golden/planner_verdicts.csv and (b) that all
hosts computed identical plans (SPMD: same grid, same reduction).

Standalone sanity run (single process, no coordinator → plain engine):

    PYTHONPATH=src:tests WORKER_OUT=/tmp/w python tests/_distributed_worker.py
"""
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.dirname(__file__))


def main() -> None:
    from repro.launch import distributed as dist
    multi = dist.initialize()          # env-driven; no-op when unconfigured

    import jax
    assert multi == (jax.process_count() > 1), (multi, jax.process_count())

    chunk_rows = int(os.environ.get("WORKER_CHUNK_ROWS", "512"))
    engine = dist.distributed_engine(chunk_rows=chunk_rows)
    assert engine.n_shards == jax.device_count()

    from test_golden_verdicts import FIELDS, _verdict_rows
    from repro.core.sweep import plan_workload_batched

    # one definition of the golden row conventions (test_golden_verdicts)
    # with the decisions produced by THIS process's distributed engine
    rows = _verdict_rows(
        plan=lambda gemms: plan_workload_batched(gemms, engine=engine))
    assert all(set(r) == set(FIELDS) for r in rows)

    info = engine.cache_info()
    payload = {"process_index": jax.process_index(),
               "processes": jax.process_count(),
               "global_devices": jax.device_count(),
               "local_devices": jax.local_device_count(),
               "chunks": info["chunks"],
               "distributed": info["distributed"],
               "rows": rows}
    out = os.environ["WORKER_OUT"]
    with open(f"{out}.{jax.process_index()}", "w") as f:
        json.dump(payload, f)
    print("WORKER-OK", flush=True)


if __name__ == "__main__":
    main()
