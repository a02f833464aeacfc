"""The benchmark runs on a listed TPU or not at all."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench.lib.cell import NoChip, device_info
from bench.lib.spec import Bench, UnknownDevice

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))


def test_a_cpu_is_not_a_chip():
    with pytest.raises(NoChip, match="no TPU"):
        device_info(chips=1)


def test_more_chips_than_jax_sees():
    with pytest.raises(NoChip, match="needs 4 chips"):
        device_info(chips=4, require_tpu=False)


def test_an_unlisted_device_kind_is_an_error():
    bench = Bench()
    assert bench.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(UnknownDevice, match="TPU v6 lite"):
        bench.peaks("TPU v6 lite")


def run_bench(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "mamba2_offline",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def no_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if "metrics" in json.loads(line):
                return False
        except ValueError:
            pass
    return True


def test_run_without_a_chip_fails_without_a_result():
    r = run_bench(REPO)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert no_result(r.stdout)


def test_run_from_the_benchmark_files_alone_fails(tmp_path):
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    for p in json.load(open(os.path.join(REPO, "BENCHMARK.json")))["paths"]:
        shutil.copytree(os.path.join(REPO, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    r = run_bench(tmp_path)
    assert r.returncode != 0
    assert no_result(r.stdout)
