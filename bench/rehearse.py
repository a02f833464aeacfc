"""Compile each cell's batch step at its real size for a described TPU
v5e, without a chip, and print what the compiler says of its memory.

  JAX_PLATFORMS=cpu python bench/rehearse.py [--workload NAME ...]

For every cell (default: all in BENCHMARK.json) and each of its phase
plans (decode, prefill; one when they coincide) it builds the plan-gated
INT8 core from parameter shapes alone, lowers the continuous-batching
step with the cell's slots, paged cache and block tables onto one chip
of a described v5e:2x2, compiles it with the TPU compiler, and prints
one line: the argument, output, temporary and aliased bytes of
`memory_analysis()` and the number of Mosaic kernels (tpu_custom_call)
in the program.  A program that does not fit, or a kernel the compiler
refuses, raises here instead of on the chip.  Nothing runs.
"""
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import argparse  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def rehearse(bench, workload: str, one_chip) -> list[str]:
    import jax
    import numpy as np
    from bench.lib.weights import model_config, served_shapes
    from repro.configs import RunConfig
    from repro.models.model import init_paged_cache
    from repro.serving import DecodeCore

    w = bench.workload(workload)
    conf, cell = bench.config(w["config"]), bench.cell(workload)
    cfg = model_config(conf)
    slots, bs = cell["slots"], cell["block_size"]
    max_blocks = math.ceil(cell["max_len"] / bs)
    rc = RunConfig(attn_impl="naive", remat=False,
                   kv_cache_dtype="bfloat16")

    def placed(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one_chip), tree)

    params = placed(served_shapes(cfg))
    core = DecodeCore(cfg, rc, params, quantize=True, donate=True,
                      plan_batch=slots, plan_max_len=cell["max_len"])
    cache = placed(jax.eval_shape(lambda: init_paged_cache(
        cfg, rc, slots, slots * max_blocks, bs)))
    args = placed((jax.ShapeDtypeStruct((slots, 1), np.int32),
                   jax.ShapeDtypeStruct((slots,), np.int32),
                   jax.ShapeDtypeStruct((slots,), np.bool_),
                   jax.ShapeDtypeStruct((slots, max_blocks), np.int32)))
    tables = {"decode": core.plan_table}
    if core.prefill_plan_table != core.plan_table:
        tables["prefill"] = core.prefill_plan_table
    lines = []
    for phase, table in tables.items():
        t0 = time.perf_counter()
        compiled = core.batch_step_for(table).lower(
            params, cache, *args).compile()
        m = compiled.memory_analysis()
        gib = lambda b: f"{b / 2 ** 30:.2f}"               # noqa: E731
        lines.append(
            f"{workload} {phase}: arguments {gib(m.argument_size_in_bytes)}"
            f" GiB, outputs {gib(m.output_size_in_bytes)} GiB, temporaries "
            f"{gib(m.temp_size_in_bytes)} GiB, aliased "
            f"{gib(m.alias_size_in_bytes)} GiB; "
            f"{compiled.as_text().count('tpu_custom_call')} tpu_custom_call;"
            f" compiled in {time.perf_counter() - t0:.1f} s")
    return lines


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", action="append",
                    help="cell to rehearse (repeatable; default: all)")
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import repro.kernels.ops as ops
    from bench.lib.spec import Bench

    # the CPU backend would pick Pallas interpret mode: compile Mosaic
    ops._on_cpu = lambda: False
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    bench = Bench()
    names = args.workload or [w["name"] for w in bench.spec["workloads"]]
    for name in names:
        for line in rehearse(bench, name, one_chip):
            print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
