"""Find everything a cell needs by name, from files alone.

``BENCHMARK.json`` at the root names the cells, configurations and metrics.
Each piece is a file of its own, found by its name:

- configuration ``<c>``: the file its entry names (``bench/configs/<c>.json``),
  whose ``reference`` key names the plain reference module beside it;
- traffic mix ``<t>``: ``bench/traffic/<t>.json``, read by ``traffic.py``;
- cell ``<c>.<t>``: ``bench/cells/<c>.<t>.json`` (engine settings, the
  offered rate, the correctness limits);
- per-layer metric ``<m>``: ``bench/metrics/<m>.py`` with ``read(ctx)``,
  which returns a number, or None where the run has nothing to read. A
  quantity split by the end-to-end metric it moves, ``<m>.<part>``, is read
  by ``<m>.py`` where it has no file of its own.

A new configuration, mix, cell or metric is therefore new files and a new
entry in ``BENCHMARK.json``, with no edit to code.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _module(path: Path, name: str):
    if not path.is_file():
        raise FileNotFoundError(f"no module {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Spec:
    """The benchmark as the files under ``root`` define it."""

    def __init__(self, root=ROOT):
        self.root = Path(root)
        self.bench = _json(self.root / "BENCHMARK.json")

    def workload(self, name: str) -> dict:
        for w in self.bench["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        for c in self.bench["configs"]:
            if c["name"] == name:
                return _json(self.root / c["file"])
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def reference(self, config: dict):
        return _module(self.root / "bench" / "configs"
                       / f"{config['reference']}.py",
                       f"bench_ref_{config['reference']}")

    def traffic(self, name: str) -> dict:
        return _json(self.root / "bench" / "traffic" / f"{name}.json")

    def cell(self, name: str) -> dict:
        return _json(self.root / "bench" / "cells" / f"{name}.json")

    def metrics(self, workload: str, traced: bool) -> list:
        """The metric entries a run of ``workload`` reports: end-to-end
        without tracing, per-layer with it."""
        kind = "per_layer" if traced else "end_to_end"
        return [m for m in self.bench[kind]
                if workload in m.get("workloads", [workload])]

    def reader(self, metric: str):
        d = self.root / "bench" / "metrics"
        path = d / f"{metric}.py"
        if not path.is_file() and "." in metric:
            path = d / f"{metric.split('.')[0]}.py"
        return _module(path, "bench_metric_" + metric.replace(".", "_"))

    def peaks(self, kind: str) -> dict:
        """The chip's peaks by ``device_kind``; an unknown kind is an
        error, never a default."""
        kinds = self.json_file("peaks.json")["kinds"]
        if kind not in kinds:
            raise KeyError(f"no peaks for device kind {kind!r} in "
                           f"bench/peaks.json")
        return kinds[kind]

    def json_file(self, name: str) -> dict:
        return _json(self.root / "bench" / name)
