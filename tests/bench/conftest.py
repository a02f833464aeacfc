"""Fixtures for the benchmark harness's CPU tests: a tiny benchmark (the
two families at `reduced`-like widths, short traffic, a CPU row in the
peaks table) laid out in a temporary directory exactly as the real one
is, with the real metric readers and references copied in."""
import json
import os
import shutil
import sys

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path[:0] = [REPO, os.path.join(REPO, "src")]

TINY_SSM = {"n_layers": 2, "d_model": 64, "vocab": 256,
            "ssm": {"d_state": 16, "d_conv": 4, "headdim": 16, "expand": 2,
                    "chunk": 32, "n_groups": 1}}
TINY_DENSE = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
              "d_head": 16, "d_ff": 128, "vocab": 256}
LENGTHS = {"prompt_len": {"median": 8, "sigma": 0.5, "min": 3, "max": 16},
           "output_len": {"median": 6, "sigma": 0.5, "min": 2, "max": 12},
           "block": 8, "chunk": 4}
# on the CPU the served tokens sit within 0.025 of the reference's best
# at these sizes (measured 0.0-0.021): any fault that changes a token
# shows above it
TINY_LIMIT = 0.05


def tiny_config(real: str, name: str, sizes: dict) -> dict:
    with open(os.path.join(REPO, "bench", "configs", f"{real}.json")) as f:
        conf = json.load(f)
    conf.update(sizes, name=name)
    conf["reduced"] = dict(conf["reduced"], **{k: "tiny" for k in sizes})
    return conf


def write_tiny_bench(root: str) -> str:
    """A benchmark under `root` (bench/ + BENCHMARK.json); returns the
    bench directory."""
    bench = os.path.join(root, "bench")
    for sub in ("metrics", "reference"):
        shutil.copytree(os.path.join(REPO, "bench", sub),
                        os.path.join(bench, sub))
    files = {
        "configs/tiny-ssm.json": tiny_config("mamba2-780m", "tiny-ssm",
                                             TINY_SSM),
        "configs/tiny-dense.json": tiny_config("mistral-nemo-12b-pp4",
                                               "tiny-dense", TINY_DENSE),
        "traffic/tiny_offline.json": dict(LENGTHS, prime="steady",
                                          arrival={"kind": "backlog"}),
        "traffic/tiny_chat.json": dict(LENGTHS, preroll_s=0.3,
                                       arrival={"kind": "gamma",
                                                "cv": 2.0}),
        "cells/ssm_offline.json": {"slots": 4, "max_len": 32,
                                   "block_size": 4, "rate_req_s": None,
                                   "limits": {"max_gap": TINY_LIMIT}},
        "cells/dense_chat.json": {"slots": 4, "max_len": 32,
                                  "block_size": 4, "rate_req_s": 40.0,
                                  "limits": {"max_gap": TINY_LIMIT}},
        "peaks.json": {"cpu": {"bf16_flops_per_s": 1e12,
                               "int8_ops_per_s": 2e12,
                               "hbm_bytes_per_s": 1e11,
                               "hbm_bytes": 1 << 34, "source": "test"}},
    }
    for rel, obj in files.items():
        path = os.path.join(bench, rel)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            json.dump(obj, f)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"] = [
        {"name": n, "source": "test", "file": f"bench/configs/{n}.json",
         "reduced": [], "why": "test"} for n in ("tiny-ssm", "tiny-dense")]
    spec["workloads"] = [
        {"name": "ssm_offline", "config": "tiny-ssm",
         "traffic": "tiny_offline", "chips": 1, "why": "test"},
        {"name": "dense_chat", "config": "tiny-dense",
         "traffic": "tiny_chat", "chips": 1, "why": "test"}]
    # both tiny cells report every metric: the readers run on a backlog
    # and on an open-loop window alike
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["ssm_offline", "dense_chat"]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return bench


@pytest.fixture
def tiny_bench(tmp_path):
    from bench.lib.spec import Bench
    bench_dir = write_tiny_bench(str(tmp_path))
    return Bench(root=bench_dir, spec_path=tmp_path / "BENCHMARK.json")
