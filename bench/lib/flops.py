"""Operations and bytes the work needs, from shapes alone.

These are the yardstick's own counts: what the algorithm must do, not
what the program happens to do (padding, repeated heads and upcasts are
not counted).  They read the benchmark's config files, never the
program's configs; what differs by model family is in families/.
"""
from __future__ import annotations

from .spec import family


def int8_gemm_cost(m: int, n: int, k: int) -> tuple[float, float]:
    """(FLOPs, HBM bytes) of one `int8_gemm` call, y = x @ (q * scale):
    bf16 x (m, k), int8 q (k, n), f32 scale (n,), f32 y (m, n)."""
    flops = 2.0 * m * n * k
    bytes_ = 2.0 * m * k + 1.0 * k * n + 4.0 * n + 4.0 * m * n
    return flops, bytes_


def least_time(flops: float, bytes_: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of the compute
    bound at the bf16 peak (the kernel's activations are bf16) and the
    bandwidth bound."""
    return max(flops / peaks["bf16_flops_per_s"],
               bytes_ / peaks["hbm_bytes_per_s"])


def step_model_flops(model: dict, active: int, live_len: int) -> float:
    """Model FLOPs of one decode step of `active` lanes whose attention
    reads `live_len` positions in all (summed over the lanes).

    Projections and the head count 2 x weights per lane; each layer's
    mixer counts what its family's `mixer_flops` says (families/)."""
    fam = family(model["family"])
    flops = 2.0 * active * (
        model["n_layers"] * fam.proj_weights_per_layer(model)
        + model["d_model"] * model["vocab"])
    return flops + model["n_layers"] * fam.mixer_flops(model, active,
                                                       live_len)
