"""Program spans and counters: the ``tcq.*`` profiler spans of a served
pool, read back from a CPU trace, agree with the service's own counters;
and every reason a wanted fused kernel gives way to the composite is
named in ``pool_log``."""

import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import ResilienceConfig, TCQService, TemporalGraph

SPANS = ("tcq.service.pump", "tcq.service.finalize",
         "tcq.engine.window_tel", "tcq.engine.window_tables",
         "tcq.engine.step_compile",
         "tcq.pipeline.run_pool", "tcq.pipeline.assemble",
         "tcq.pipeline.dispatch", "tcq.pipeline.retire",
         "tcq.pipeline.sync")


def small_graph(seed=3, n_v=24, n_e=240, max_t=40):
    rng = np.random.default_rng(seed)
    u = rng.integers(0, n_v, n_e)
    v = (u + rng.integers(1, n_v, n_e)) % n_v
    t = rng.integers(0, max_t, n_e)
    return TemporalGraph.from_edges(u, v, t, n_v)


def spans_of(trace_dir):
    """Every ``tcq.*`` event of the trace: (name, start, end, args)."""
    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("tcq."):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                {k: int(x) for k, x in ev.stats}))
    return out


def inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


@pytest.fixture(scope="module")
def traced_service(tmp_path_factory):
    """Two disjoint windows of one size class (two pools: the second
    reuses the first's composite program), then a repeat of the first
    (a window-TEL hit: no build, no first call), served under the
    profiler."""
    svc = TCQService(small_graph(), use_kernel=False, cache=False)
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    with jax.profiler.trace(trace_dir):
        first = [svc.submit({"k": 2, "ts": 0, "te": 12}),
                 svc.submit({"k": 2, "h": 1, "ts": 24, "te": 36})]
        svc.run_until_idle()
        again = svc.submit({"k": 2, "ts": 0, "te": 12})
        svc.run_until_idle()
    return svc, first + [again], spans_of(trace_dir)


def test_every_span_appears_and_nests(traced_service):
    svc, tickets, spans = traced_service
    names = {s[0] for s in spans}
    assert names == set(SPANS)
    by = {n: [s for s in spans if s[0] == n] for n in SPANS}
    for child, parent in [("tcq.pipeline.sync", "tcq.pipeline.retire"),
                          ("tcq.pipeline.retire", "tcq.pipeline.run_pool"),
                          ("tcq.pipeline.assemble", "tcq.pipeline.run_pool"),
                          ("tcq.pipeline.dispatch", "tcq.pipeline.run_pool"),
                          ("tcq.engine.step_compile",
                           "tcq.pipeline.dispatch"),
                          ("tcq.pipeline.run_pool", "tcq.service.pump"),
                          ("tcq.engine.window_tel", "tcq.service.pump"),
                          ("tcq.engine.window_tables",
                           "tcq.engine.window_tel"),
                          ("tcq.service.finalize", "tcq.service.pump")]:
        for s in by[child]:
            assert any(inside(s, p) and p[3]["pool"] == s[3]["pool"]
                       for p in by[parent]), (child, parent, s)
    assert len(by["tcq.service.pump"]) == len(svc.pool_log) == 3
    assert len(by["tcq.service.finalize"]) == len(tickets)


def test_span_counts_match_the_counters(traced_service):
    svc, tickets, spans = traced_service
    count = {n: sum(s[0] == n for s in spans) for n in SPANS}
    pools = {tk.pool: tk.result.stats for tk in tickets}
    assert count["tcq.pipeline.sync"] == sum(s.host_syncs
                                             for s in pools.values())
    assert count["tcq.pipeline.retire"] == count["tcq.pipeline.sync"]
    misses = svc.stats["window_tel"]["misses"]
    assert misses == 2
    assert count["tcq.engine.window_tel"] == misses
    assert count["tcq.engine.window_tables"] == misses
    assert count["tcq.engine.step_compile"] == 1
    assert sum(p["step_compiles"] for p in svc.pool_log) == 1
    assert [p["window_tel_miss"] for p in svc.pool_log] == [1, 1, 0]
    assert [p["step_compiles"] for p in svc.pool_log] == [1, 0, 0]
    assert [p["step_program_reuses"] for p in svc.pool_log] == [0, 1, 0]
    edges = [s[3]["edges"] for s in spans
             if s[0] == "tcq.engine.window_tables"]
    assert edges == [p["window_edges"] for p in svc.pool_log[:2]]
    assert all(p["fallback"] is None for p in svc.pool_log)


def test_pool_args_match_pool_log_and_tickets(traced_service):
    svc, tickets, spans = traced_service
    assert [p["pool"] for p in svc.pool_log] == [0, 1, 2]
    assert [tk.pool for tk in tickets] == [0, 1, 2]
    assert [tk.result.stats.pool for tk in tickets] == [0, 1, 2]
    pumps = sorted((s for s in spans if s[0] == "tcq.service.pump"),
                   key=lambda s: s[1])
    assert [s[3] for s in pumps] == [{"pool": i, "members": 1}
                                     for i in range(3)]
    for name in SPANS:
        assert {s[3]["pool"] for s in spans if s[0] == name} <= {0, 1, 2}
    steps = {}
    for s in spans:
        if s[0] == "tcq.pipeline.dispatch":
            steps.setdefault(s[3]["pool"], []).append(s[3]["step"])
    for tk in tickets:
        assert len(steps[tk.pool]) >= tk.result.stats.device_steps


@pytest.mark.parametrize("reason", ["smem_tables", "vmem_budget",
                                    "build_error"])
def test_each_fallback_reason_is_named(monkeypatch, reason):
    import repro.kernels.wave_peel.ops as ops

    # as on a chip: the fused kernel is compiled, so its budgets apply
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    kw = {}
    if reason == "smem_tables":
        monkeypatch.setattr(ops, "SMEM_TABLE_BUDGET", 0)
    elif reason == "vmem_budget":
        monkeypatch.setattr(ops, "DEFAULT_VMEM_BUDGET", 1)
    else:
        def broken(*args):
            raise RuntimeError("class build failed")

        # the window tables are shared with the composite; the fused
        # step's own build starts at its size class
        monkeypatch.setattr(ops, "fused_step_class", broken)
        kw["resilience"] = True     # the ladder turns the error into a rung
    g = small_graph()
    svc = TCQService(g, use_kernel=True, cache=False, **kw)
    ref = TCQService(g, use_kernel=False, cache=False)
    tk = svc.submit({"k": 2, "ts": 0, "te": 12})
    want = ref.submit({"k": 2, "ts": 0, "te": 12})
    svc.run_until_idle()
    ref.run_until_idle()
    (rec,) = svc.pool_log
    assert rec["backend"] == "xla" and rec["fallback"] == reason
    assert tk.result.by_tti().keys() == want.result.by_tti().keys()
    assert ref.pool_log[0]["fallback"] is None


def test_second_window_of_a_class_reuses_the_fused_program(tmp_path):
    _second_window_reuses_its_class(tmp_path, None)


def test_second_window_of_a_class_reuses_the_ladder_program(tmp_path):
    _second_window_reuses_its_class(tmp_path, ResilienceConfig())


def _second_window_reuses_its_class(tmp_path, resilience):
    """Two pools on different windows of one fused-step size class: the
    second pool's fresh step (the fused step, or a degradation ladder on
    its fused rung) reuses the class's compiled program, so it counts a
    reuse, no compile, and opens no ``step_compile`` span; its answers
    equal the composite's."""
    svc = TCQService(small_graph(), use_kernel=True, cache=False,
                     resilience=resilience)
    reqs = [{"k": 2, "ts": ts, "te": ts + 12} for ts in (0, 24)]
    tickets = []
    with jax.profiler.trace(str(tmp_path)):
        for req in reqs:
            tickets.append(svc.submit(req))
            svc.run_until_idle()
    ref = TCQService(small_graph(), cache=False)
    for req, tk in zip(reqs, tickets):
        want = ref.submit(req)
        ref.run_until_idle()
        assert tk.result.by_tti().keys() == want.result.by_tti().keys()
    first, second = svc.pool_log
    if resilience is not None:
        assert svc.engine.resilience_events() == []   # stayed on its rung
    assert first["backend"] == second["backend"] == "pallas"
    assert first["window_tel_miss"] == second["window_tel_miss"] == 1
    assert first["step_compiles"] + first["step_program_reuses"] == 1
    assert second["step_compiles"] == 0
    assert second["step_program_reuses"] == 1
    compiled = [s for s in spans_of(str(tmp_path))
                if s[0] == "tcq.engine.step_compile"]
    assert [s[3]["pool"] for s in compiled] == \
        [first["pool"]] * first["step_compiles"]
