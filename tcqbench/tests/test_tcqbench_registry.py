"""Every part of a cell is found by name in a file of its own, and
BENCHMARK.json keeps to its layout."""

import json
import re

import pytest

from tcqbench import harness
from tcqbench.registry import Registry
from tcqbench.tests.conftest import make_registry

REG = Registry()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_a_configuration_mix_and_metric_added_as_files_are_found(tmp_path):
    reg = make_registry(tmp_path, name="newgraph")
    (reg.root / "metrics" / "pools_per_request.py").write_text(
        "def read(run):\n"
        "    return len(run['pools']) / max(1, len(run['tickets']))\n")
    b = reg.benchmark()
    b["per_layer"].append({
        "name": "pools_per_request", "unit": "pools", "better": "lower",
        "source": "program_counter", "layer": "service",
        "moves": "latency_p50_ms", "workloads": ["newgraph.adhoc"]})
    reg.benchmark_path.write_text(json.dumps(b))
    cell = reg.workload("newgraph.adhoc")
    assert reg.config(cell["config"])["name"] == "newgraph"
    assert reg.traffic(cell["traffic"], "newgraph")["arrivals"]["rate_per_s"] == 4.0
    names = [m["name"] for m in reg.metrics("newgraph.adhoc", "per_layer")]
    assert "pools_per_request" in names and "step_device_ms" in names
    read = reg.reader("pools_per_request")
    assert read({"pools": [1, 2], "tickets": [1, 2, 3, 4]}) == 0.5


def test_a_device_kind_missing_from_the_peaks_is_an_error():
    with pytest.raises(KeyError):
        REG.peaks("TPU v99")
    assert REG.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_benchmark_layout():
    b = REG.benchmark()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["paths"] == ["tcqbench"]
    assert 1 <= b["run_seconds"] <= 51
    e2e = {m["name"] for m in b["end_to_end"]}
    assert "setup_s" in e2e
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    configs = {c["name"] for c in b["configs"]}
    for c in b["configs"]:
        cfg = json.loads((REG.root.parent / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
    for w in b["workloads"]:
        assert NAME.match(w["name"]) and w["config"] in configs
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        REG.traffic(w["traffic"], w["config"])
        reported = {m["name"] for m in REG.metrics(w["name"], "end_to_end")}
        assert "setup_s" in reported and len(reported) >= 2
        per_layer = REG.metrics(w["name"], "per_layer")
        assert per_layer
        assert all(m["moves"] in reported for m in per_layer)
    for m in b["per_layer"]:
        assert NAME.match(m["name"]) and m["moves"] in e2e
        assert (REG.root / "metrics" / f"{m['name']}.py").exists()
    names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(names) == len(set(names))


def test_timed_traffic_compiles_with_the_persistent_cache_off():
    import jax

    before = jax.config.jax_enable_compilation_cache
    try:
        harness.disable_persistent_cache(jax)
        assert jax.config.jax_enable_compilation_cache is False
    finally:
        jax.config.update("jax_enable_compilation_cache", before)
