"""The trace reduction on hand-made traces with known answers, and on a
trace recorded on a v5e chip."""

import gzip
import json
import pathlib

import pytest

from tcqbench import trace

DATA = pathlib.Path(__file__).resolve().parent / "data"


def ev(kind, name, a, b, plane="/device:TPU:0"):
    return {"kind": kind, "plane": "/host:CPU" if kind == "host" else plane,
            "name": name, "start_ns": float(a), "end_ns": float(b)}


def hand_made():
    # window 0..1000 ns; ops busy 100-300 (two overlapping) and 600-650;
    # one op starts before the window; a pump span covers 0-500, idle 500-1000
    return [
        ev("host", "tcqbench.window", 0, 1000),
        ev("host", "tcqbench.pump", 0, 500),
        ev("host", "tcqbench.idle", 500, 1000),
        ev("op", "fusion.12", 100, 250),
        ev("op", "fusion.13", 200, 300),
        ev("op", "custom-call", 600, 650),
        ev("op", "copy.1", -50, 20),
        ev("module", "jit__step(7)", 100, 300),
        ev("module", "jit__wave_step_impl", 600, 700),
        ev("module", "jit__set_lane", -50, 20),
    ]


def test_busy_and_idle_share_of_a_hand_made_trace():
    events = hand_made()
    win = trace.window(events)
    assert win == (0.0, 1000.0)
    # busy: 0-20, 100-300, 600-650 = 270 ns
    assert trace.busy_seconds(events, win) == pytest.approx(270e-9)


def test_idle_gaps_are_named_by_the_host_span_they_fall_in():
    gaps = trace.idle_gaps(hand_made(), (0.0, 1000.0))
    assert gaps[0] == ("idle", pytest.approx(350e-9))     # 650-1000
    assert gaps[1] == ("pump", pytest.approx(300e-9))     # 300-600
    assert gaps[2] == ("pump", pytest.approx(80e-9))      # 20-100
    assert len(gaps) == 3


def test_step_device_time_counts_both_step_programs_only():
    secs = trace.module_seconds(hand_made(), (0.0, 1000.0),
                                trace.STEP_PROGRAMS)
    assert sorted(secs) == pytest.approx([100e-9, 200e-9])


def test_top_ops_group_numbered_ops():
    top = dict(trace.top_ops(hand_made(), (0.0, 1000.0)))
    assert top["fusion"] == pytest.approx(250e-9)
    assert top["copy"] == pytest.approx(20e-9)


def test_recorded_chip_trace():
    path = DATA / "trace_v5e.json.gz"
    with gzip.open(path, "rt") as f:
        events = json.load(f)
    win = trace.window(events)
    assert win is not None
    busy = trace.busy_seconds(events, win)
    span = (win[1] - win[0]) / 1e9
    assert 0.0 < busy < span
    steps = trace.module_seconds(events, win, trace.STEP_PROGRAMS)
    assert steps and all(s > 0 for s in steps)
    gaps = trace.idle_gaps(events, win)
    assert sum(g for _, g in gaps) == pytest.approx(span - busy, rel=1e-6)
