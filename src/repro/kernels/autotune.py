"""Block-size autotune table for the Pallas kernels.

`choose_blocks` (core.tpu_adapter) derives block sizes analytically from
the WWW mapping priorities.  This module layers a small *pinned* table of
block configurations for the GEMM shape classes the serving stack
actually hits — decode GEMVs/micro-batches, skinny down-projections,
prefill-scale GEMMs — because the analytic choice optimizes the weight
tile in isolation while the measured winners also balance grid-step
count (interpret-mode cost on CPU, DMA/compute overlap on TPU).

Every table entry is a *cap*, not a demand: it is legalized to the TPU
tiling (`core.tpu_adapter.legal_block`: the full dim, or a multiple of
128 on lanes and 8 on sublanes, dividing the dim where one does) and
the whole configuration is checked against the VMEM budget before
use.  A shape no entry matches — or whose pinned entry would bust the
budget — falls back to the analytic `choose_blocks`, so the table can
only ever replace a config with another *valid* one.

`sweep_block_rows` plays the same role for the fused sweep kernel
(kernels.sweep_eval): rows-per-grid-step from a power-of-two ladder,
preferring a single grid step for planner-sized batches while keeping
the per-step field matrices inside the VMEM budget for campaign-scale
batches.
"""
from __future__ import annotations

from ..core.tpu_adapter import (MXU, PSUM_BYTES, SUBLANE, VMEM_BUDGET,
                                choose_blocks, legal_block)

# (name, predicate(M, N, K), (block_m, block_n, block_k)) — first match
# wins; values are caps, tiling-legalized + VMEM-checked before use.
INT8_GEMM_TABLE = (
    # decode GEMV / micro-batch: M is tiny — keep all of M resident and
    # maximize the stationary weight tile, K-deep first (the paper's
    # in-array reduction priority)
    ("decode-gemv", lambda M, N, K: M <= 16, (16, 512, 1024)),
    # batched decode: M fits one MXU pass, weight tile still the point
    ("decode-batch", lambda M, N, K: M <= 128, (128, 512, 1024)),
    # skinny outputs (down-projections): N is small, stream deep K
    ("skinny-n", lambda M, N, K: N <= 256, (256, 256, 2048)),
    # prefill / large-M: balanced tiles, psum pressure bounds block_m
    ("prefill-wide", lambda M, N, K: True, (256, 512, 512)),
)


def int8_gemm_vmem_bytes(bm: int, bn: int, bk: int, act_bytes: int = 2,
                         w_bytes: int = 1) -> int:
    """VMEM claim of one int8-GEMM grid step: activation (bm x bk) +
    weight tile (bk x bn) + f32 output window and scratch accumulator
    (2 x bm x bn)."""
    return (bm * bk * act_bytes + bk * bn * w_bytes
            + 2 * bm * bn * PSUM_BYTES)


def int8_gemm_blocks(M: int, N: int, K: int,
                     vmem: int = VMEM_BUDGET) -> tuple[int, int, int]:
    """(block_m, block_n, block_k) for `kernels.int8_gemm` from the
    autotune table, analytic `choose_blocks` as the fallback."""
    for _name, pred, (bm, bn, bk) in INT8_GEMM_TABLE:
        if pred(M, N, K):
            bm = legal_block(M, bm, SUBLANE)
            bn = legal_block(N, bn, MXU)
            bk = legal_block(K, bk, MXU)
            if int8_gemm_vmem_bytes(bm, bn, bk) <= vmem:
                return bm, bn, bk
            break       # pinned entry busts the budget on this shape
    return choose_blocks(M, N, K, vmem=vmem)


def autotune_report(shapes=((8, 512, 256), (8, 256, 2048),
                            (1024, 1024, 1024), (4096, 128, 512))
                    ) -> list[dict]:
    """Table decisions for exemplar GEMM shapes (docs / tests surface):
    which entry matched, the legalized blocks, and their VMEM claim."""
    rows = []
    for M, N, K in shapes:
        entry = next((n for n, pred, _ in INT8_GEMM_TABLE
                      if pred(M, N, K)), None)
        bm, bn, bk = int8_gemm_blocks(M, N, K)
        rows.append({"shape": (M, N, K), "entry": entry,
                     "blocks": (bm, bn, bk),
                     "vmem_kib": int8_gemm_vmem_bytes(bm, bn, bk) // 1024,
                     "grid_steps": (-(-M // bm)) * (-(-N // bn))
                     * (-(-K // bk))})
    return rows


# Rows-per-grid-step ladder for the fused sweep kernel.
SWEEP_ROW_LADDER = (1024, 2048, 4096, 8192, 16384)


def sweep_block_rows(n_rows: int, n_fields: int, n_out_fields: int,
                     vmem: int = 2 * VMEM_BUDGET) -> int:
    """Rows per `sweep_eval` grid step: the smallest ladder entry that
    covers the batch in ONE grid step, capped so the per-step field
    matrix + output matrix + ~2x elementwise temporaries (all f32) stay
    inside the VMEM budget.  Batches beyond the cap stream in multiple
    grid steps of the largest fitting block."""
    per_row = 4 * (n_fields + n_out_fields) * 3
    cap = max(SWEEP_ROW_LADDER[0], vmem // per_row)
    best = SWEEP_ROW_LADDER[0]
    for r in SWEEP_ROW_LADDER:
        if r > cap:
            break
        best = r
        if r >= n_rows:
            break
    return best
