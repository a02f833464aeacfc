"""Fused Pallas kernel for the planner sweep inner loop.

The sweep engine's hot path scores flattened (GEMM, config, mapping) rows
— all 6 unrolled DRAM loop orders, revisit/coverage factors, greedy-mask
order selection and the in-kernel argmin over orders — through
`vectorized.evaluate_flat`, relying on XLA to fuse the ~200-op elementwise
graph.  This kernel runs the SAME backend-shared cost spec
(vectorized.cim_cast / cim_row_terms / cim_best_order / cim_outputs)
inside one hand-written `pl.pallas_call`: every intermediate lives in
VMEM for the whole pass, one grid step per block of rows, so nothing
round-trips to HBM between the 6 order evaluations (the ROADMAP's
"measure whether hand-written Pallas beats XLA fusion at large batch").

Layout: the B rows are stacked as a (len(FLAT_FIELDS), B) float32 matrix
— fields on the sublane axis, rows on the lane axis — so a block is a
(F, block_rows) tile and each field is one (1, block_rows) row slice.
Outputs come back as a (len(SWEEP_OUT_FIELDS), B) matrix, unpacked to the
same dict `evaluate_flat` returns (bit-identical semantics; `valid` is
carried as 0/1 float32 through the kernel and re-boolified outside).

Platform handling mirrors kernels/ops.py: interpret mode on CPU (tests,
CI containers), compiled Mosaic on TPU.  `pallas_status()` probes the
lowering once per process and raises where an accelerator cannot
compile the kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from ..core.cost_model import DRAM_STREAM_EFFICIENCY
from ..core.loopnest import check_order_mode
from ..core.vectorized import (FLAT_FIELDS, cim_best_order, cim_cast,
                               cim_outputs, cim_row_terms)

# Kernel output rows, in stacking order — the same keys (and per-row
# values) evaluate_flat returns.
SWEEP_OUT_FIELDS = ("valid", "energy_pj", "time_ns", "tops_per_w",
                    "gflops", "utilization", "compute_ns", "dram_ns",
                    "smem_ns", "dram_bytes", "smem_bytes")

# Reference rows-per-grid-step.  VMEM footprint is (len(FLAT_FIELDS) +
# len(SWEEP_OUT_FIELDS)) * block * 4B ≈ 1 MB at 8192 plus intermediates —
# comfortably under the ~16 MB/core budget, and big enough that the
# full-workload planner batch (~8k rows) runs in a single grid step.
# The default is now autotuned per batch (kernels.autotune
# .sweep_block_rows): small batches take the smallest single-grid-step
# ladder entry, campaign-scale batches stream at the largest
# VMEM-fitting block.
_BLOCK_ROWS = 8192


def _sweep_kernel(in_ref, out_ref, *, order_mode: str, dram_eff: float):
    """One block: fields are (1, block) row slices of the input tile; the
    whole cost spec — terms, 6-order unroll, selection, outputs — runs on
    VMEM-resident values."""
    cols = {f: in_ref[i:i + 1, :] for i, f in enumerate(FLAT_FIELDS)}
    pre = cim_row_terms(cim_cast(cols))
    best_energy, best_dram = cim_best_order(pre, order_mode)
    out = cim_outputs(pre, best_energy, best_dram, dram_eff)
    for j, name in enumerate(SWEEP_OUT_FIELDS):
        out_ref[j:j + 1, :] = out[name].astype(jnp.float32)


def sweep_eval(batch: dict, order_mode: str = "exact",
               dram_eff: float = DRAM_STREAM_EFFICIENCY,
               block_rows: int | None = None,
               interpret: bool | None = None) -> dict:
    """Pallas-fused equivalent of `vectorized.evaluate_flat`.

    batch: dict of (B,) arrays for every name in FLAT_FIELDS; returns the
    same dict of (B,) arrays (valid as bool).  Rows are padded (edge
    replication) to a multiple of `block_rows` and the padding is sliced
    off before returning.  block_rows=None autotunes it from the batch
    size and the VMEM budget (kernels.autotune.sweep_block_rows); block
    choice never changes the values, only the grid decomposition.
    """
    check_order_mode(order_mode)
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    rows = jnp.stack([jnp.asarray(batch[f]).astype(jnp.float32)
                      for f in FLAT_FIELDS])
    b = rows.shape[1]
    if block_rows is None:
        from .autotune import sweep_block_rows
        block_rows = sweep_block_rows(b, len(FLAT_FIELDS),
                                      len(SWEEP_OUT_FIELDS))
    blk = min(block_rows, max(1, b))
    m = -(-b // blk) * blk
    if m != b:
        rows = jnp.pad(rows, ((0, 0), (0, m - b)), mode="edge")
    out = pl.pallas_call(
        functools.partial(_sweep_kernel, order_mode=order_mode,
                          dram_eff=dram_eff),
        grid=(m // blk,),
        in_specs=[pl.BlockSpec((len(FLAT_FIELDS), blk), lambda i: (0, i))],
        out_specs=pl.BlockSpec((len(SWEEP_OUT_FIELDS), blk),
                               lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((len(SWEEP_OUT_FIELDS), m),
                                       jnp.float32),
        interpret=interpret,
    )(rows)
    res = {name: out[j, :b] for j, name in enumerate(SWEEP_OUT_FIELDS)}
    res["valid"] = res["valid"] > 0.5
    return res


# --- platform probe ----------------------------------------------------------

_STATUS: dict | None = None


def pallas_status() -> dict:
    """How this process runs the sweep kernel, probed once:

      {"mode": "interpret" | "compiled", "reason": ...}

    CPU always takes interpret mode (the repo-wide Pallas convention, see
    kernels/ops.py — the kernel logic is exercised, execution is emulated).
    Accelerators compile and run an 8-row probe; a Mosaic lowering or
    runtime failure there raises RuntimeError carrying the error, so a
    broken kernel is never replaced by the XLA path behind the caller's
    back.
    """
    global _STATUS
    if _STATUS is None:
        platform = jax.default_backend()
        if platform == "cpu":
            _STATUS = {"mode": "interpret",
                       "reason": "cpu: compiled Mosaic lowering is "
                                 "TPU-only; kernel runs via interpret "
                                 "mode"}
        else:
            probe = {f: np.ones(8, np.float32) for f in FLAT_FIELDS}
            try:
                out = jax.jit(functools.partial(
                    sweep_eval, interpret=False))(probe)
                jax.block_until_ready(out["energy_pj"])
            except Exception as e:
                raise RuntimeError(
                    f"sweep_eval does not compile on {platform}: "
                    f"{type(e).__name__}: {e}") from e
            _STATUS = {"mode": "compiled", "reason": None}
    return _STATUS


def _reset_status_for_tests() -> None:
    """Drop the memoized probe result (test hook only)."""
    global _STATUS
    _STATUS = None
