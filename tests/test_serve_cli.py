"""The serve CLI's shared args -> report path, the compile-cache helper,
and chip_smoke.py's refusal to run without a TPU."""
import importlib.util
import json
import os

import pytest

from repro.launch import compile_cache
from repro.launch.serve import PARITY_ATOL, build_parser, serve
from repro.serving import CIM_ROUTE

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE_ARGS = ["--arch", "mamba2-780m", "--smoke", "--quantize",
              "--batch", "8", "--prompt-len", "4", "--new-tokens", "4"]


def test_serve_fixed_batch_gating_report():
    """mamba2 at batch 8 is the mixed-verdict cell: its gating block
    names the routed labels with their executed shapes, and carries
    gated/ungated parity, finiteness and the no-retrace count.  On the
    CPU Pallas runs in interpret mode, so no Mosaic call is lowered."""
    rep = serve(build_parser().parse_args(SMOKE_ARGS))
    g = rep["gating"]
    routed = {lab: r for lab, r in g["routes"].items()
              if r["route"] == CIM_ROUTE}
    assert routed and g["cim_routed"] == len(routed)
    for r in routed.values():
        assert r["shapes"] and all(m == 8 for m, _, _ in r["shapes"])
    assert g["decode_step_tpu_custom_calls"] == 0
    assert g["parity_max_abs_diff"] <= PARITY_ATOL
    assert g["logits_finite"] and rep["tokens_in_vocab"]
    assert g["decode_executables"] == 1
    assert rep["generated_shape"] == [8, 4]


def test_serve_traffic_phase_executables():
    """Traffic mode through the same entry: every request completes and
    each phase plan's batch step compiles exactly once."""
    rep = serve(build_parser().parse_args(
        SMOKE_ARGS + ["--requests", "3", "--slots", "2",
                      "--arrival-rate", "0"]))
    agg = rep["traffic"]["aggregate"]
    assert agg["completed"] == 3
    ex = agg["phase_gating"]["executables"]
    assert set(ex) == {"decode", "prefill"}
    assert all(n == 1 for n in ex.values())
    # one program per distinct phase plan (the phases share one when
    # their plans agree)
    assert 1 <= agg["decode_executables"] <= len(ex)


def test_compile_cache_defaults_to_checkout(monkeypatch):
    calls = []
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    monkeypatch.setattr(compile_cache.jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    path = compile_cache.configure_compile_cache()
    assert path == os.path.join(REPO, ".jax_cache")
    assert calls == [("jax_compilation_cache_dir", path)]


def test_compile_cache_leaves_placed_dir_to_jax(monkeypatch, tmp_path):
    calls = []
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    monkeypatch.setattr(compile_cache.jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    assert compile_cache.configure_compile_cache() == str(tmp_path)
    assert calls == []


def test_chip_smoke_fails_without_tpu(capsys, tmp_path):
    """On the CPU the smoke exits non-zero before any phase and never
    prints its result line."""
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    assert smoke.main(["--out", str(tmp_path)]) == 1
    out = capsys.readouterr().out
    assert "JAX found no TPU" in out
    for line in out.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)
    assert not list(tmp_path.iterdir())
