"""Weight-stationary INT8 GEMM Pallas TPU kernel — the paper's CiM insight
adapted to the TPU memory hierarchy.

CiM analogue on TPU:
  * the (bk x bn) INT8 weight tile is the "CiM array": resident in VMEM,
    reused across the whole M stream (weight-stationary, K->sublanes,
    N->lanes);
  * the MXU plays the Rp x Cp parallel MAC grid;
  * partial sums accumulate in f32 VMEM across K steps (the paper's
    in-array K reduction / temporal psum accumulation);
  * block sizes come from the WWW mapping algorithm re-targeted at VMEM
    capacity (kernels.autotune.int8_gemm_blocks).

Grid: (M/bm, N/bn, K/bk), K innermost so each output tile's psums stay in
a VMEM scratch (never spill to HBM — the paper's "K must fit the
reduction capability" takeaway, enforced structurally).

dataflow="ws" flips the grid to (N/bn, K/bk, M/bm): M becomes the
innermost loop exactly as the paper's compute order (M < K < N), holding
each weight tile stationary across the entire M stream.  The output
window spans all M rows of the current N block, so it stays resident in
VMEM across the whole (k, m) sub-grid and the psums of every M block are
read back from VMEM, never from HBM (a TPU output block is written back
when its index changes and is never re-read, so an (m, n) output window
revisited non-consecutively would lose its psums).

Blocks are taken as given; the TPU tiling rule (last two block dims
divisible by (8, 128) or equal to the array dims) is the caller's
contract, met by `int8_gemm_blocks`.  M and K are zero-padded up to a
multiple of their blocks (activations are small; a K remainder would
otherwise add garbage into the reduction), while a ragged last N block
is left to the pipeline: its out-of-range columns are never written.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel_os(x_ref, w_ref, s_ref, o_ref, acc_ref, *, n_k: int):
    """Output-stationary: grid (m, n, k), psums in VMEM scratch."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    acc_ref[...] += jax.lax.dot(x, w, preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _done():
        o_ref[...] = (acc_ref[...] * s_ref[...]).astype(o_ref.dtype)


def _kernel_ws(x_ref, w_ref, s_ref, o_ref, *, n_k: int, bm: int):
    """Weight-stationary (paper order M<K<N): grid (n, k, m); the weight
    tile stays put while M streams; the psums of M block m live in rows
    [m*bm, (m+1)*bm) of the VMEM-resident (M, bn) output window."""
    k = pl.program_id(1)
    rows = pl.ds(pl.multiple_of(pl.program_id(2) * bm, bm), bm)

    @pl.when(k == 0)
    def _init():
        o_ref[rows, :] = jnp.zeros((bm, o_ref.shape[1]), o_ref.dtype)

    x = x_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    o_ref[rows, :] += jax.lax.dot(x, w, preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _scale():
        o_ref[rows, :] = o_ref[rows, :] * s_ref[...]


def int8_gemm(x, w_q, w_scale, *, block_m: int = 256, block_n: int = 256,
              block_k: int = 512, dataflow: str = "os",
              interpret: bool = False):
    """y = x @ dequant(w_q)  with per-output-channel scales, in f32.

    x: (M, K) bf16/f32; w_q: (K, N) int8; w_scale: (N,) f32.
    The scale enters the kernel as a (1, N) row with (1, bn) blocks and
    is applied on the last K step (valid because it is per output
    channel, constant over K).
    """
    M, K = x.shape
    K2, N = w_q.shape
    if K != K2 or w_scale.shape != (N,):
        raise ValueError(f"int8_gemm: x {x.shape}, w_q {w_q.shape}, "
                         f"w_scale {w_scale.shape} do not agree")
    bm, bn, bk = min(block_m, M), min(block_n, N), min(block_k, K)
    mp, kp = pl.cdiv(M, bm) * bm, pl.cdiv(K, bk) * bk
    if (mp, kp) != (M, K):
        x = jnp.pad(x, ((0, mp - M), (0, kp - K)))
    if kp != K:
        w_q = jnp.pad(w_q, ((0, kp - K), (0, 0)))
    scale = w_scale.astype(jnp.float32).reshape(1, N)
    n_m, n_n, n_k = mp // bm, pl.cdiv(N, bn), kp // bk
    out_shape = jax.ShapeDtypeStruct((mp, N), jnp.float32)

    if dataflow == "os":
        y = pl.pallas_call(
            functools.partial(_kernel_os, n_k=n_k),
            grid=(n_m, n_n, n_k),
            in_specs=[
                pl.BlockSpec((bm, bk), lambda m, n, k: (m, k)),
                pl.BlockSpec((bk, bn), lambda m, n, k: (k, n)),
                pl.BlockSpec((1, bn), lambda m, n, k: (0, n)),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda m, n, k: (m, n)),
            out_shape=out_shape,
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
            interpret=interpret,
        )(x, w_q, scale)
    elif dataflow == "ws":
        y = pl.pallas_call(
            functools.partial(_kernel_ws, n_k=n_k, bm=bm),
            grid=(n_n, n_k, n_m),
            in_specs=[
                pl.BlockSpec((bm, bk), lambda n, k, m: (m, k)),
                pl.BlockSpec((bk, bn), lambda n, k, m: (k, n)),
                pl.BlockSpec((1, bn), lambda n, k, m: (0, n)),
            ],
            out_specs=pl.BlockSpec((mp, bn), lambda n, k, m: (0, n)),
            out_shape=out_shape,
            interpret=interpret,
        )(x, w_q, scale)
    else:
        raise ValueError(f"unknown dataflow {dataflow!r}")
    return y[:M] if mp != M else y
