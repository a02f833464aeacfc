"""The yardstick's operation and byte counts, against hand counts."""
import json
import os

import pytest

from bench.lib import flops

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def conf(name):
    with open(os.path.join(REPO, "bench", "configs", f"{name}.json")) as f:
        return json.load(f)


def test_int8_gemm_cost():
    # x (8, 1536) bf16, q (1536, 3072) int8, scale (3072,) f32, y f32
    f, b = flops.int8_gemm_cost(8, 3072, 1536)
    assert f == 2 * 8 * 3072 * 1536
    assert b == 8 * 1536 * 2 + 1536 * 3072 + 3072 * 4 + 8 * 3072 * 4


def test_least_time_takes_the_binding_bound():
    # a decode GEMV is bound by bytes, a square prefill GEMM by FLOPs
    f, b = flops.int8_gemm_cost(8, 3072, 1536)
    assert flops.least_time(f, b, V5E) == pytest.approx(b / 819e9)
    f, b = flops.int8_gemm_cost(4096, 4096, 4096)
    assert flops.least_time(f, b, V5E) == pytest.approx(f / 197e12)


def test_mamba2_step_flops():
    m = conf("mamba2-780m")
    d, di, nh, n, p = 1536, 3072, 48, 128, 64
    per_layer = d * (2 * di + 2 * n + nh) + di * d      # z, x, B, C, dt, out
    assert per_layer == 14_622_720
    want = (2 * 10 * (48 * per_layer + d * 50280)
            + 5 * 10 * 48 * nh * n * p)
    assert flops.step_model_flops(m, active=10, live_len=999) == want


def test_nemo_step_flops():
    m = conf("mistral-nemo-12b-pp4")
    d, h, kv, dh, ff = 5120, 32, 8, 128, 14336
    per_layer = d * h * dh + 2 * d * kv * dh + h * dh * d + 3 * d * ff
    assert per_layer == 272_629_760          # the 272.6 M of the config
    lanes, live = 3, 100 + 200 + 300
    want = (2 * lanes * (10 * per_layer + d * 131072)
            + 4 * 10 * h * dh * live)
    assert flops.step_model_flops(m, active=lanes, live_len=live) == want
