"""Find the benchmark's pieces by the names in `BENCHMARK.json`.

Each configuration, traffic mix, cell and per-layer metric lives in a file
of its own under the benchmark's root, so a later change adds a file and
an entry and edits nothing:

  configs/<config>.json     the model as it is run: sizes, source, cuts
  traffic/<mix>.json        parameters of one traffic mix (lengths, arrivals)
  generators/<name>.py      a traffic generator other than the default
                            (lib/traffic.py), named by a mix's "generator"
  cells/<workload>.json     one cell's engine size, offered rate and limits
  metrics/<metric>.py       the reader of one per-layer metric
  reference/<family>.py     the plain float32 forward of one model family
  families/<family>.py      one family's FLOP count, weight-drawing rules
                            and reference controls (bench/calibrate.py)
  peaks.json                chip peaks, keyed by JAX's device_kind
"""
from __future__ import annotations

import functools
import importlib.util
import json
from pathlib import Path

BENCH_ROOT = Path(__file__).resolve().parents[1]
REPO_ROOT = BENCH_ROOT.parent


class UnknownDevice(RuntimeError):
    """The chip's device_kind has no row in the peaks table."""


def _load_module(path: Path, name: str):
    if not path.is_file():
        raise FileNotFoundError(f"no {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@functools.cache
def family(name: str, root: Path = BENCH_ROOT):
    """One model family's module under families/: its FLOP count
    (`proj_weights_per_layer`, `mixer_flops`) and the rules that draw
    its own parameters (`LEAVES`)."""
    return _load_module(Path(root) / "families" / f"{name}.py",
                        f"bench_family_{name}")


class Bench:
    """The benchmark's definition and the files it names.

    `root` is the directory holding configs/, traffic/, cells/, metrics/,
    reference/ and peaks.json; `spec_path` the BENCHMARK.json to read."""

    def __init__(self, root: Path = BENCH_ROOT,
                 spec_path: Path = REPO_ROOT / "BENCHMARK.json"):
        self.root = Path(root)
        with open(spec_path) as f:
            self.spec = json.load(f)

    def _json(self, *parts: str) -> dict:
        path = self.root.joinpath(*parts)
        with open(path) as f:
            return json.load(f)

    def workload(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{[w['name'] for w in self.spec['workloads']]}")

    def config(self, name: str) -> dict:
        return self._json("configs", f"{name}.json")

    def traffic(self, name: str) -> dict:
        return self._json("traffic", f"{name}.json")

    def generator(self, mix: dict):
        """The module that turns `mix` into requests: the default one,
        or generators/<name>.py where the mix names one."""
        name = mix.get("generator")
        if name is None:
            from . import traffic
            return traffic
        return _load_module(self.root / "generators" / f"{name}.py",
                            f"bench_generator_{name}")

    def cell(self, name: str) -> dict:
        return self._json("cells", f"{name}.json")

    def peaks(self, device_kind: str) -> dict:
        table = self._json("peaks.json")
        if device_kind not in table:
            raise UnknownDevice(
                f"device_kind {device_kind!r} is not in the peaks table "
                f"({sorted(table)}); add its published peaks first")
        return table[device_kind]

    def metrics(self, workload: str, trace: bool) -> list[dict]:
        """The metrics a run of `workload` reports: the end-to-end ones
        with --trace 0, the per-layer ones with --trace 1.  A metric with
        a `workloads` list is reported only in those cells."""
        group = self.spec["per_layer" if trace else "end_to_end"]
        return [m for m in group
                if "workloads" not in m or workload in m["workloads"]]

    def reader(self, metric: str):
        """The `read(run)` function of one per-layer metric."""
        mod = _load_module(self.root / "metrics" / f"{metric}.py",
                           f"bench_metric_{metric.replace('.', '_')}")
        return mod.read

    def reference(self, family: str):
        """The plain float32 reference module of one model family."""
        return _load_module(self.root / "reference" / f"{family}.py",
                            f"bench_reference_{family}")
