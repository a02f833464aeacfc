"""BENCHMARK.json and the files it names: every piece is found by name,
a new piece dropped in is found with no other file edited, and the
definition keeps to its own rules."""
import json
import os
import re

import pytest

from bench.lib.spec import Bench, family

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return Bench()


def test_every_named_piece_exists(bench):
    spec = bench.spec
    for c in spec["configs"]:
        conf = bench.config(c["name"])
        assert c["file"] == f"bench/configs/{c['name']}.json"
        assert sorted(c["reduced"]) == sorted(conf["reduced"])
        bench.reference(conf["family"])
        fam = family(conf["family"])
        assert fam.proj_weights_per_layer(conf) > 0
        assert conf["cache_dtypes"]
    for w in spec["workloads"]:
        assert {"slots", "max_len", "block_size", "rate_req_s",
                "limits"} <= set(bench.cell(w["name"]))
        assert "arrival" in bench.traffic(w["traffic"])
        assert w["chips"] == 1
        for trace in (False, True):
            names = {m["name"] for m in bench.metrics(w["name"], trace)}
            if not trace:
                assert "setup_s" in names and len(names) >= 2
            else:
                assert names
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert callable(bench.reader(m["name"]))


def test_definition_keeps_its_rules(bench):
    spec = bench.spec
    e2e = {m["name"] for m in spec["end_to_end"]}
    cells = {w["name"] for w in spec["workloads"]}
    names = [m["name"] for m in spec["end_to_end"] + spec["per_layer"]]
    assert len(names) == len(set(names))
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        assert set(m.get("workloads", cells)) <= cells
    for m in spec["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in spec["per_layer"]:
        assert m["moves"] in e2e
        moved = next(x for x in spec["end_to_end"] if x["name"] == m["moves"])
        assert set(m["workloads"]) <= set(moved.get("workloads", cells))
    assert 1 <= spec["run_seconds"] <= 51


def test_new_pieces_are_found_by_name(tiny_bench):
    root = tiny_bench.root
    (root / "configs" / "extra.json").write_text(json.dumps({"x": 1}))
    (root / "traffic" / "extra_mix.json").write_text(
        json.dumps({"arrival": {"kind": "poisson"}}))
    (root / "cells" / "extra_cell.json").write_text(json.dumps({"slots": 2}))
    (root / "metrics" / "extra.metric.py").write_text(
        "def read(run):\n    return 42.0\n")
    (root / "generators").mkdir()
    (root / "generators" / "sessions.py").write_text(
        "def schedule(mix, cell, seed, vocab):\n    return iter(())\n")
    (root / "families").mkdir()
    (root / "families" / "extra.py").write_text(
        "LEAVES = {}\n"
        "def proj_weights_per_layer(m):\n    return 1\n"
        "def mixer_flops(m, active, live_len):\n    return 0.0\n")
    assert tiny_bench.config("extra") == {"x": 1}
    assert tiny_bench.traffic("extra_mix")["arrival"]["kind"] == "poisson"
    gen = tiny_bench.generator({"generator": "sessions"})
    assert list(gen.schedule({}, {}, 1, 10)) == []
    assert tiny_bench.generator({}).__name__ == "bench.lib.traffic"
    fam = family("extra", root)
    assert fam.proj_weights_per_layer({}) == 1 and fam.LEAVES == {}
    assert tiny_bench.cell("extra_cell") == {"slots": 2}
    assert tiny_bench.reader("extra.metric")(None) == 42.0
    with pytest.raises(FileNotFoundError):
        tiny_bench.reader("missing")
