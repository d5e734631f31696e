import json
import os
import pathlib
import shutil

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = pathlib.Path(__file__).resolve().parents[1]

TINY_GRAPH = {"num_vertices": 400, "num_edges": 4000, "num_pairs": 2000,
              "days": 100, "time_units": 512}


def make_registry(root: pathlib.Path, name: str = "tiny",
                  rate: float = 4.0, seed_draws: str = "order"):
    """A copy of the benchmark's readers and peaks under ``root`` with one
    small deployment, ``<name>.adhoc``, built from the MathOverflow files,
    its mix's seed drawing ``seed_draws``."""
    from tcqbench.registry import Registry

    bench = root / "tcqbench"
    shutil.copytree(HERE / "metrics", bench / "metrics",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(HERE / "peaks.json", bench / "peaks.json")
    b = json.loads((HERE.parent / "BENCHMARK.json").read_text())
    b["workloads"] = [{"name": f"{name}.adhoc", "config": name,
                       "traffic": "adhoc", "chips": 1, "why": "test"}]
    for m in b["end_to_end"] + b["per_layer"]:
        m.pop("workloads", None)
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    cfg = json.loads((HERE / "configs" / "mathoverflow.json").read_text())
    cfg["name"] = name
    cfg["graph"].update(TINY_GRAPH)
    (bench / "configs").mkdir()
    (bench / "configs" / f"{name}.json").write_text(json.dumps(cfg))
    mix = json.loads((HERE / "traffic" / "adhoc" / "mathoverflow.json")
                     .read_text())
    mix["arrivals"]["rate_per_s"] = rate
    mix["seed_draws"] = seed_draws
    mix["windows"]["width_timestamps"] = [8, 16]
    mix["warmup"]["widths"] = [8, 16]
    (bench / "traffic" / "adhoc").mkdir(parents=True)
    (bench / "traffic" / "adhoc" / f"{name}.json").write_text(json.dumps(mix))
    return Registry(bench)


@pytest.fixture
def tiny(tmp_path):
    return make_registry(tmp_path)
