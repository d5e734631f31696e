"""Finds every part of a cell by name, each in a file of its own.

* ``BENCHMARK.json`` at the repository root: cells and metric declarations;
* ``configs/<config>.json``: a deployment (graph counts, service settings,
  guarantees, what was assumed or reduced);
* ``traffic/<mix>/<config>.json``: the mix's parameters for that deployment;
* ``metrics/<metric>.py``: a per-layer metric's reader, ``read(run)``;
* ``peaks.json``: published peaks keyed by JAX's ``device_kind``.

A new configuration, mix or metric is a new file and a new entry; no file
here changes.
"""

from __future__ import annotations

import importlib.util
import json
import pathlib
from typing import Callable, List, Optional

HERE = pathlib.Path(__file__).resolve().parent


class Registry:
    """The benchmark's files under ``root`` (the ``tcqbench`` directory),
    with ``BENCHMARK.json`` in ``root``'s parent unless ``benchmark`` says
    otherwise."""

    def __init__(self, root=HERE, benchmark=None):
        self.root = pathlib.Path(root)
        self.benchmark_path = pathlib.Path(
            benchmark or self.root.parent / "BENCHMARK.json")

    def benchmark(self) -> dict:
        return json.loads(self.benchmark_path.read_text())

    def workload(self, name: str) -> dict:
        for w in self.benchmark()["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in {self.benchmark_path}")

    def config(self, name: str) -> dict:
        return json.loads((self.root / "configs" / f"{name}.json")
                          .read_text())

    def traffic(self, mix: str, config: str) -> dict:
        return json.loads((self.root / "traffic" / mix / f"{config}.json")
                          .read_text())

    def peaks(self, device_kind: str) -> dict:
        table = json.loads((self.root / "peaks.json").read_text())
        if device_kind not in table["devices"]:
            raise KeyError(f"device kind {device_kind!r} has no entry in "
                           "peaks.json")
        return table["devices"][device_kind]

    def metrics(self, workload: str, kind: str) -> List[dict]:
        """The ``end_to_end`` or ``per_layer`` metrics a cell reports."""
        return [m for m in self.benchmark()[kind]
                if workload in m.get("workloads", [workload])]

    def reader(self, metric: str) -> Callable[[dict], Optional[float]]:
        path = self.root / "metrics" / f"{metric}.py"
        spec = importlib.util.spec_from_file_location(
            f"tcqbench_metric_{metric.replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read
