"""A whole run on the CPU, past the look for a chip, at a small size: sound
it comes out correct; with the timed path broken underneath, or with the
cut-short control in the program's place, the comparison fails it."""

import json

import jax.numpy as jnp
import pytest

from tcqbench import control, graphgen, harness, reference
from tcqbench.tests.conftest import make_registry

SECONDS = 2.0


def run(reg, traced=False, seed=2 ** 31 + 7):
    return harness.run("tiny.adhoc", seed, SECONDS, traced, registry=reg,
                       require_chip=False, log=lambda msg: None)


def wrap_steps(monkeypatch, change):
    """Every window's peel step built from now on passes its result
    through ``change(alive_in, result)``."""
    import repro.core.wave as wave

    orig = wave.make_wave_step_fn

    def make(tel, num_vertices, **kw):
        step = orig(tel, num_vertices, **kw)

        def broken(alive, ts, te, k, h):
            before = jnp.array(alive, copy=True)
            return change(before, step(alive, ts, te, k, h), num_vertices)

        broken.backend, broken.interpret = step.backend, step.interpret
        return broken

    monkeypatch.setattr(wave, "make_wave_step_fn", make)


def state_unchanged(before, res, nv):
    from repro.core.wave import _pack_u32

    return res._replace(alive=before, packed=_pack_u32(before, nv))


def half_the_lanes(before, res, nv):
    keep = jnp.arange(res.n_edges.shape[0]) < res.n_edges.shape[0] // 2
    return res._replace(n_edges=jnp.where(keep, res.n_edges, 0))


def test_sound_run_is_correct(tmp_path):
    out = run(make_registry(tmp_path))
    assert out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] == int(4 * SECONDS)
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    assert set(out["metrics"]) == {m["name"] for m in bench["end_to_end"]}
    assert list(out)[-1] == "compared"


def test_sound_run_on_a_relabelled_graph_is_correct(tmp_path):
    out = run(make_registry(tmp_path, seed_draws="labels"))
    assert out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] == int(4 * SECONDS)


def test_broken_step_on_a_relabelled_graph_is_not_correct(tmp_path,
                                                          monkeypatch):
    wrap_steps(monkeypatch, state_unchanged)
    out = run(make_registry(tmp_path, seed_draws="labels"))
    assert out["correct"] is False
    assert out["compared"]["wrong_answers"]["value"] > 0


def test_traced_run_reads_the_counters(tmp_path):
    out = run(make_registry(tmp_path), traced=True)
    assert out["correct"] is True
    got = out["metrics"]
    for name in ("queue_wait_p95_ms", "cache_hit_share", "cells_per_request",
                 "compiles_in_window", "compile_s_in_window",
                 "bytes_synced_per_step", "lane_occupancy"):
        assert name in got, name
    # no TPU plane in a CPU trace: the device metrics are left out, not 0
    for name in ("step_device_ms", "peel_step_roofline",
                 "device_idle_share"):
        assert name not in got


@pytest.mark.parametrize("fault", [state_unchanged, half_the_lanes],
                         ids=["state_unchanged", "half_the_lanes"])
def test_broken_step_is_not_correct(tmp_path, monkeypatch, fault):
    wrap_steps(monkeypatch, fault)
    out = run(make_registry(tmp_path))
    assert out["correct"] is False
    assert out["compared"]["wrong_answers"]["value"] > 0


def test_altered_answer_is_not_correct(tmp_path, monkeypatch):
    from repro.core.scheduler import QueryState

    orig = QueryState.decode_results

    def altered(self, num_vertices):
        out = orig(self, num_vertices)
        for key, core in out.items():
            out[key] = core.__class__(k=core.k, tti=core.tti,
                                      vertices=core.vertices[1:],
                                      n_edges=core.n_edges)
            break
        return out

    monkeypatch.setattr(QueryState, "decode_results", altered)
    out = run(make_registry(tmp_path))
    assert out["correct"] is False
    assert out["compared"]["wrong_answers"]["value"] > 0


def test_control_in_the_programs_place_is_not_correct(tmp_path,
                                                     monkeypatch):
    """The run's own comparison fails the cut-short reference's answers
    where they stand in for the program's."""
    graph = {}
    load = graphgen.load_or_generate

    def keep(*args, **kw):
        graph["uvt"] = out = load(*args, **kw)
        return out

    def control_answer(tk):
        u, v, t, _ = graph["uvt"]
        return reference.digest(reference.tcq(u, v, t, tk.k, tk.h, tk.ts,
                                              tk.te, max_peel_rounds=2))

    monkeypatch.setattr(graphgen, "load_or_generate", keep)
    monkeypatch.setattr(harness, "_digest", control_answer)
    out = run(make_registry(tmp_path))
    assert out["correct"] is False
    assert out["compared"]["wrong_answers"]["value"] > 0


def test_control_answers_are_counted_wrong(tmp_path):
    reg = make_registry(tmp_path)
    got = control.control_readings("tiny.adhoc", 11, SECONDS, 2,
                                   registry=reg)
    assert got["requests"] == int(4 * SECONDS)
    assert got["correct"] is False
    wrong = got["compared"]["wrong_answers"]
    assert wrong["value"] > wrong["limit"]
