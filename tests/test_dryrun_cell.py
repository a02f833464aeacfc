"""Dry-run machinery test: one real cell lowered + compiled against the
production mesh in a subprocess (512 host-platform devices), plus unit
tests of the HLO collective parser and extrapolation math."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_collective_parser():
    from repro.launch.hlo_analysis import collective_stats
    hlo = """
  %ag = bf16[16,4096]{1,0} all-gather(%x), dimensions={0}
  %ar = f32[128]{0} all-reduce(%y), to_apply=%sum
  %cp = f32[2,2]{1,0} collective-permute(%z), source_target_pairs={{0,1}}
  %dot = f32[4,4]{1,0} dot(%a, %b)
"""
    st = collective_stats(hlo)
    assert st["by_type"]["all-gather"]["count"] == 1
    assert st["by_type"]["all-gather"]["bytes"] == 16 * 4096 * 2
    assert st["by_type"]["all-reduce"]["bytes"] == 128 * 4
    # all-reduce weighted 2x (ring traffic)
    want = 16 * 4096 * 2 + 2 * 128 * 4 + 16
    assert st["collective_bytes"] == want


def test_extrapolation_math():
    from repro.launch.dryrun import _extrapolate, _unroll_points
    # measured(k) = 100 + 7k  =>  true(L=28) = 100 + 196
    m = [(7, {"flops": 100 + 7 * 7}), (2, {"flops": 100 + 7 * 2})]
    out = _extrapolate(m, 28)
    assert out["flops"] == pytest.approx(100 + 7 * 28)
    assert _unroll_points(28) == [7, 2]
    assert _unroll_points(9) == [3, 1]
    assert _unroll_points(3) == [3]


def test_cost_analysis_is_one_dict():
    """_compile_costs reads "flops" and "bytes accessed" straight from
    Compiled.cost_analysis(): on the installed jax that is one flat dict
    for the whole program."""
    import jax
    import jax.numpy as jnp
    x = jnp.ones((64, 32), jnp.float32)
    cost = jax.jit(lambda a: a.T @ a).lower(x).compile().cost_analysis()
    assert isinstance(cost, dict)
    assert cost["flops"] == pytest.approx(2 * 32 * 32 * 64, rel=0.1)
    assert cost["bytes accessed"] > 0


def test_unroll_points_divide():
    from repro.launch.dryrun import _unroll_points
    for L in (9, 20, 24, 28, 32, 40, 48, 64):
        pts = _unroll_points(L)
        assert all(L % k == 0 for k in pts), (L, pts)


@pytest.mark.slow
@pytest.mark.parametrize("cell", [("mamba2-780m", "decode_32k", "single")])
def test_dryrun_cell_compiles_on_production_mesh(cell, tmp_path):
    """Lower + compile one real (arch x shape) against the 16x16 mesh with
    512 placeholder devices — the deliverable-e mechanism, end to end."""
    arch, shape, mesh = cell
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
         "--shape", shape, "--mesh", mesh, "--fast",
         "--out", str(tmp_path)],
        capture_output=True, text=True, timeout=560, env=env, cwd=REPO)
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.load(open(tmp_path / f"{arch}.{shape}.{mesh}.json"))
    assert out["status"] == "ok", out
    assert out["chips"] == 256
    assert out["roofline"]["bottleneck"] in ("compute", "memory",
                                             "collective")
    # decode cells carry what/when/where verdicts + sweep-cache telemetry
    p = out["planner"]
    assert p["summary"]["n_gemms"] > 0
    assert p["plan_hits"] + p["plan_misses"] > 0
    assert p["cache"]["size"] > 0
    # per-backend keyspace breakdown rides along in the embedded engine
    # cache_info (report.py renders it)
    assert p["cache"]["backends"]["vectorized"]["misses"] > 0
