"""Compiles of the main-path Pallas kernels for a described TPU v5e.

No chip is attached: `jax.experimental.topologies` describes a v5e:2x2
host and the TPU compiler (Mosaic for Pallas) compiles for one of its
chips.  That refuses what interpret mode accepts — block shapes off the
(8, 128) tiling, operand layouts Mosaic cannot match, more VMEM than a
kernel may claim — so these tests guard the kernels a "use CiM" verdict
routes mamba2-780m's full-width projections to, the fused planner
sweep kernel, and the in-place cache update of the donating batch step,
at no chip time.  Nothing runs; results are checked on
the chip (chip_smoke.py).

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library.
"""
import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import ARCHS, SHAPES, RunConfig
from repro.core.llm_workloads import gemms_of_model, is_projection_label
from repro.kernels.autotune import int8_gemm_blocks, int8_gemm_vmem_bytes
from repro.kernels.int8_gemm import int8_gemm

_M2 = ARCHS["mamba2-780m"]
_D = _M2.d_model
_DI = _M2.ssm.d_inner(_D)
# (N, K) of every projection the planner gates onto the Pallas route at
# full width, as the model executes it (B, C and dt are separate calls)
ROUTED_NK = {
    "ssm-z/x": (_DI, _D),
    "ssm-B/C": (_M2.ssm.n_groups * _M2.ssm.d_state, _D),
    "ssm-dt": (_M2.ssm.n_ssm_heads(_D), _D),
    "ssm-out": (_D, _DI),
    "lm_head": (_M2.vocab, _D),
}
# N with no 128-multiple divisor (ragged last N block), and K with none
# (zero-padded K)
EDGE_NK = {"ragged-n": (6448, 1536), "padded-k": (1000, 1601)}
DECODE_M, PREFILL_M = 8, 128


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile_int8_gemm(one_chip, m, n, k, dataflow, blocks=None):
    bm, bn, bk = blocks or int8_gemm_blocks(m, n, k)
    fn = jax.jit(functools.partial(
        int8_gemm, block_m=bm, block_n=bn, block_k=bk, dataflow=dataflow,
        interpret=False))
    args = (jax.ShapeDtypeStruct((m, k), jnp.bfloat16, sharding=one_chip),
            jax.ShapeDtypeStruct((k, n), jnp.int8, sharding=one_chip),
            jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip))
    return fn.lower(*args).compile()


@pytest.mark.parametrize("dataflow", ["os", "ws"])
@pytest.mark.parametrize("m", [DECODE_M, PREFILL_M])
@pytest.mark.parametrize("label", sorted(ROUTED_NK) + sorted(EDGE_NK))
def test_int8_gemm_compiles_for_v5e(one_chip, label, m, dataflow):
    n, k = {**ROUTED_NK, **EDGE_NK}[label]
    compiled = _compile_int8_gemm(one_chip, m, n, k, dataflow)
    assert "tpu_custom_call" in compiled.as_text()


def test_int8_gemm_ws_multiblock_compiles_for_v5e(one_chip):
    """ws with several M and K blocks: the case whose psums must stay in
    the VMEM-resident output window across the M stream."""
    compiled = _compile_int8_gemm(one_chip, PREFILL_M, _DI, _D, "ws",
                                  blocks=(32, 512, 512))
    assert "tpu_custom_call" in compiled.as_text()


def _decode_projection_shapes():
    shapes = set()
    for cfg in ARCHS.values():
        for shape in ("decode_32k", "long_500k"):
            shapes |= {(g.M, g.N, g.K)
                       for g in gemms_of_model(cfg, SHAPES[shape])
                       if is_projection_label(g.label)}
    return sorted(shapes)


def test_int8_gemm_compiles_every_decode_projection(one_chip):
    """The serving route's blocks compile for every decode projection
    of the registered archs (M = 128 and M = 1)."""
    for m, n, k in _decode_projection_shapes():
        compiled = _compile_int8_gemm(one_chip, m, n, k, "os")
        assert "tpu_custom_call" in compiled.as_text(), (m, n, k)


def test_sweep_eval_compiles_for_v5e(one_chip):
    from repro.core.vectorized import FLAT_FIELDS
    from repro.kernels.sweep_eval import sweep_eval
    batch = {f: jax.ShapeDtypeStruct((8192,), jnp.float32,
                                     sharding=one_chip)
             for f in FLAT_FIELDS}
    compiled = jax.jit(functools.partial(
        sweep_eval, interpret=False)).lower(batch).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _all_gemm_shapes():
    return sorted({(g.M, g.N, g.K) for cfg in ARCHS.values()
                   for shape in SHAPES.values()
                   for g in gemms_of_model(cfg, shape)})


def test_int8_gemm_blocks_tiling_legal_all_archs():
    """Pure Python: for every GEMM of the registered archs at every shape, the
    chosen blocks are the full dim or a multiple of the TPU tile (8 on
    the sublane axis M, 128 on the lane axes N and K), divide every dim
    that has such a divisor, and fit the VMEM budget."""
    from repro.core.tpu_adapter import VMEM_BUDGET
    shapes = _all_gemm_shapes()
    assert len(shapes) > 200
    for m, n, k in shapes:
        bm, bn, bk = int8_gemm_blocks(m, n, k)
        for dim, b, align in ((m, bm, 8), (n, bn, 128), (k, bk, 128)):
            assert b == dim or (b % align == 0 and b < dim), (m, n, k)
            if b != dim and any(dim % d == 0
                                for d in range(align, b + 1, align)):
                assert dim % b == 0, (m, n, k, b)
        assert int8_gemm_vmem_bytes(bm, bn, bk) <= VMEM_BUDGET, (m, n, k)


def test_int8_gemm_ragged_and_padded_blocks_match_reference():
    """The ragged last N block and the zero-padded K tail give the
    reference result (interpret mode here; compiled on the chip by
    chip_smoke.py), in both dataflows."""
    from repro.kernels import ops, ref
    key = jax.random.PRNGKey(0)
    for n, k in EDGE_NK.values():
        k1, k2, k3, key = jax.random.split(key, 4)
        x = jax.random.normal(k1, (24, k), jnp.float32)
        w = jax.random.randint(k2, (k, n), -127, 128, jnp.int8)
        s = jax.random.uniform(k3, (n,), jnp.float32, 0.01, 0.1)
        want = np.asarray(ref.int8_gemm_ref(x, w, s))
        for dataflow in ("os", "ws"):
            got = np.asarray(ops.int8_matmul(x, w, s, dataflow=dataflow,
                                             block_m=16, block_n=512,
                                             block_k=512))
            # f32 sums of ~1.6k products of magnitude up to ~400:
            # accumulation order alone moves them by ~1e-6 of max|y|
            np.testing.assert_allclose(
                got, want, rtol=0, atol=1e-5 * np.abs(want).max())


_COPY = re.compile(r"= \w+\[([\d,]*)\]\{[^}]*\} copy\(")


@pytest.mark.parametrize("arch,n_layers,slots,max_len", [
    ("mamba2-780m", 4, 64, 1536),
    # NeMo's pipeline-stage depth: its attention alone needs ~1.3 GiB of
    # temp (the gathered strip repeated to 32 heads in f32), more than a
    # 2-layer cache holds
    ("mistral-nemo-12b", 10, 32, 2048),
])
def test_batch_step_updates_stacked_cache_in_place(one_chip, arch, n_layers,
                                                   slots, max_len):
    """The donating continuous-batching step at full width writes each
    layer's rows into the stacked cache it carries through the layer
    scan: the compiled program copies no stacked cache leaf and no
    layer's slice of one, and needs less temp than the cache holds
    (a scan over the cache as xs/ys copies every layer's slice out and
    back and the whole stack after the loop)."""
    from repro.models import init, init_paged_cache
    from repro.serving import DecodeCore
    cfg = dataclasses.replace(ARCHS[arch], n_layers=n_layers)
    rc = RunConfig(attn_impl="naive", remat=False)
    block = 16
    params = jax.eval_shape(lambda: init(jax.random.PRNGKey(0), cfg))
    cache = jax.eval_shape(lambda: init_paged_cache(
        cfg, rc, slots, slots * max_len // block, block))

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)
    step = DecodeCore(cfg, rc, params, donate=True).batch_step
    compiled = step.lower(
        on_chip(params), on_chip(cache),
        *on_chip((jax.ShapeDtypeStruct((slots, 1), jnp.int32),
                  jax.ShapeDtypeStruct((slots,), jnp.int32),
                  jax.ShapeDtypeStruct((slots,), jnp.bool_),
                  jax.ShapeDtypeStruct((slots, max_len // block),
                                       jnp.int32)))).compile()
    leaves = jax.tree.leaves(cache)
    cache_shapes = set()
    for a in leaves:
        cache_shapes |= {a.shape, a.shape[1:], (1,) + a.shape[1:]}
    copies = [line.strip()[:160]
              for line in compiled.as_text().splitlines()
              if (m := _COPY.search(line)) and tuple(
                  int(d) for d in m.group(1).split(",") if d)
              in cache_shapes]
    assert not copies, copies
    cache_bytes = sum(a.size * a.dtype.itemsize for a in leaves)
    assert compiled.memory_analysis().temp_size_in_bytes < cache_bytes


# what JAX reports a v5e chip may hold (16 GiB of HBM less the runtime's)
V5E_USABLE_BYTES = int(15.75 * 2 ** 30)


def test_granite_cell_batch_step_fits_one_chip(one_chip, monkeypatch):
    """The granite_h_offline cell's batch step as the benchmark serves it
    (INT8, plan-gated, 64 slots over 1536 positions, 10 layers at the
    published widths with 9 of 72 experts held) compiles for one v5e
    with its gated projections on the Pallas kernel, and its arguments
    and temporaries fit the chip beside the second copy of the SSM state
    that a slot's admission makes (the eager reset)."""
    import math
    import os

    import repro.kernels.ops as ops
    from repro.models import init_paged_cache
    from repro.serving import DecodeCore
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.syspath_prepend(root)
    from bench.lib.spec import Bench
    from bench.lib.weights import model_config, served_shapes
    monkeypatch.setattr(ops, "_on_cpu", lambda: False)   # compile Mosaic

    bench = Bench()
    cell = bench.cell("granite_h_offline")
    cfg = model_config(bench.config(
        bench.workload("granite_h_offline")["config"]))
    slots, bs = cell["slots"], cell["block_size"]
    blocks = math.ceil(cell["max_len"] / bs)
    rc = RunConfig(attn_impl="naive", remat=False, kv_cache_dtype="bfloat16")

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)
    params = on_chip(served_shapes(cfg))
    core = DecodeCore(cfg, rc, params, quantize=True, donate=True,
                      plan_batch=slots, plan_max_len=cell["max_len"])
    cache = on_chip(jax.eval_shape(lambda: init_paged_cache(
        cfg, rc, slots, slots * blocks, bs)))
    args = on_chip((jax.ShapeDtypeStruct((slots, 1), jnp.int32),
                    jax.ShapeDtypeStruct((slots,), jnp.int32),
                    jax.ShapeDtypeStruct((slots,), jnp.bool_),
                    jax.ShapeDtypeStruct((slots, blocks), jnp.int32)))
    state = sum(e["state"].size * 4 for e in cache if "state" in e)
    for table in {core.plan_table, core.prefill_plan_table}:
        compiled = core.batch_step_for(table).lower(
            params, cache, *args).compile()
        assert "tpu_custom_call" in compiled.as_text()
        m = compiled.memory_analysis()
        used = m.argument_size_in_bytes + m.temp_size_in_bytes + state
        assert used < V5E_USABLE_BYTES, used / 2 ** 30
