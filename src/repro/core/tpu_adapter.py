"""WWW mapping algorithm re-targeted at the TPU memory hierarchy.

The paper chooses how much weight to hold stationary in a CiM array given
its capacity and Rp/Cp/Rh/Ch geometry.  On TPU the analogous decision is
the Pallas BlockSpec: how large a (bk x bn) INT8 weight tile to hold
resident in VMEM while activations stream through the MXU.

Mapping of concepts:
  CiM array capacity    -> VMEM weight-tile budget
  Rp (parallel rows)    -> MXU contraction extent (128 sublanes)
  Cp (parallel cols)    -> MXU lane extent (128)
  Rh x Ch serial MACs   -> grid steps per resident tile
  SMEM A/Z buffering    -> VMEM activation + accumulator blocks
  "K within reduction"  -> psums must stay in VMEM scratch (never HBM)

`choose_blocks` runs the same priority logic as core.mapping: maximize the
stationary weight tile (priority 1/2), then size the M stream so the
activation + accumulator blocks fit the remaining VMEM (priority 3 /
Algorithm 1).
"""
from __future__ import annotations

from .loopnest import ceil_div

MXU = 128                       # MXU systolic extent = lane width
SUBLANE = 8                     # f32 sublane tile height
VMEM_BUDGET = 8 * 1024 * 1024   # bytes we allow a kernel instance to claim
PSUM_BYTES = 4                  # f32 accumulator


def _round_down_mult(x: int, m: int) -> int:
    return max(m, (x // m) * m)


def choose_blocks(M: int, N: int, K: int, vmem: int = VMEM_BUDGET,
                  act_bytes: int = 2, w_bytes: int = 1
                  ) -> tuple[int, int, int]:
    """Pick (block_m, block_n, block_k) for the int8 GEMM kernel.

    Priority 1 (weight-stationary): grow the (bk x bn) weight tile toward
    half the VMEM budget, MXU-aligned, K first (the paper maps K to rows
    and prioritizes in-array reduction depth).
    Priority 3 (Algorithm 1): the M block then takes what fits alongside
    the activation (bm x bk) and accumulator (bm x bn) blocks.
    """
    w_budget = vmem // 2
    bk = min(_round_down_mult(K, MXU) if K >= MXU else K, 2048)
    bn = min(_round_down_mult(N, MXU) if N >= MXU else N, 1024)
    # shrink until the weight tile fits its budget (K last — reduction depth
    # is the paper's priority)
    while bk * bn * w_bytes > w_budget and bn > MXU:
        bn //= 2
    while bk * bn * w_bytes > w_budget and bk > MXU:
        bk //= 2

    rem = vmem - bk * bn * w_bytes
    # bm x (bk act + bn psum) must fit the remainder
    per_row = bk * act_bytes + bn * PSUM_BYTES
    bm = max(8, min(512, rem // per_row))
    bm = min(bm, M)
    # legalize to the TPU tiling: lane dims (bn, bk) a multiple of 128,
    # the sublane dim bm a multiple of 8, or the full dim
    return (legal_block(M, bm, SUBLANE), legal_block(N, bn, MXU),
            legal_block(K, bk, MXU))


def legal_block(dim: int, cap: int, align: int) -> int:
    """Largest block <= cap along one array dim that Mosaic can tile: the
    whole dim when it fits, else a multiple of `align` (128 on a lane
    axis, 8 on a sublane axis).  A multiple that divides `dim` is
    preferred; when none exists the largest aligned block is returned
    and the kernel pads (M, K) or leaves a ragged last block (N)."""
    if dim <= cap:
        return dim
    top = max(align, cap // align * align)
    for b in range(top, 0, -align):
        if dim % b == 0:
            return b
    return top


def grid_steps(M: int, N: int, K: int, blocks: tuple[int, int, int]) -> int:
    bm, bn, bk = blocks
    return ceil_div(M, bm) * ceil_div(N, bn) * ceil_div(K, bk)
