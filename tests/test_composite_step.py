"""The XLA composite step in the window's own vertex and pair space.

The composite peels over the window's local tables (``window_tables``,
shared with the fused kernel) padded to power-of-two rows, and must stay
bit-identical to the graph-space numpy oracle step on every
``StepResult`` field; every window of a size class shares one compiled
program; and the service serves the YouTube-growth deployment's shape
exactly."""

import jax
import jax.monitoring
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import TCQEngine, TCQService, TemporalGraph
from repro.core.wave import make_oracle_step_fn

FIELDS = ("alive", "packed", "tti_lo", "tti_hi", "n_edges", "iters")


def sparse_window_graph(seed, n_v=20_000, background=8_000, n_t=400):
    """Sparse background edges over every vertex and time, and a dense
    block of 200 vertices at times 100-110: a window around the block
    touches a few percent of the vertices and has non-trivial cores."""
    rng = np.random.default_rng(seed)
    u = rng.integers(0, n_v, background)
    v = (u + rng.integers(1, n_v, background)) % n_v
    t = rng.integers(0, n_t, background)
    block = rng.choice(n_v, 200, replace=False)
    bu = block[rng.integers(0, 200, 1_500)]
    bv = block[rng.integers(0, 200, 1_500)]
    keep = bu != bv
    u = np.concatenate([u, bu[keep]])
    v = np.concatenate([v, bv[keep]])
    t = np.concatenate([t, rng.integers(100, 111, int(keep.sum()))])
    return TemporalGraph.from_edges(u, v, t, n_v)


def lane_args(rng, n_v, ts_lo, te_hi, wave=7):
    """Per-lane windows inside [ts_lo, te_hi], k from -1 to 4, h 1-2, two
    padded lanes (the pipeline's empty window over a dead row), and
    warm-start rows that keep vertices outside the window alive."""
    ts = rng.integers(ts_lo, te_hi + 1, wave)
    te = np.minimum(ts + rng.integers(0, te_hi - ts_lo + 1, wave), te_hi)
    k = rng.integers(-1, 5, wave)
    k[:3] = [-1, 0, 2]
    h = rng.integers(1, 3, wave)
    alive = rng.random((wave, n_v)) < 0.9
    alive[1] = True
    ts[-2:], te[-2:], k[-2:], h[-2:] = 0, -1, 1, 1
    alive[-2:] = False
    return (jnp.asarray(alive), *(jnp.asarray(x, jnp.int32)
                                  for x in (ts, te, k, h)))


def assert_same(got, want, what):
    for field in FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(got, field)), np.asarray(getattr(want, field)),
            err_msg=f"composite vs oracle diverge on {field} ({what})")


@pytest.mark.parametrize("seed,whole", [(0, False), (1, False), (2, False),
                                        (3, True)])
def test_window_space_composite_equals_the_oracle_step(seed, whole):
    g = sparse_window_graph(seed)
    eng = TCQEngine(g, use_kernel=False)
    lo, hi = (int(g.t.min()), int(g.t.max())) if whole else (98, 112)
    wt = eng._window_tel(lo, hi)
    step = wt.step_fn
    assert step.backend == "xla"
    tables, verts, slot = step.window_args
    assert (tables[0].shape[0], tables[2].shape[0],
            verts.shape[0]) == tuple(step.rows)
    isolated = np.asarray(slot) >= step.rows[2]
    n_local = int(np.sum(~isolated))
    if not whole:
        # the window touches under 10% of the vertices, and its tables
        # are padded past their live rows
        assert n_local < 0.1 * g.num_vertices
        assert step.rows[2] > n_local
    oracle = make_oracle_step_fn(wt.tel, wt.num_vertices)
    rng = np.random.default_rng(100 + seed)
    for call in range(2):
        args = lane_args(rng, wt.num_vertices, lo, hi)
        want = oracle(*args)
        got = step(*args)           # the engine's step donates ``alive``
        assert_same(got, want, f"seed={seed} call={call}")
        # a restart from the fixpoint converges on its first iteration,
        # vertices without a live edge included
        want = oracle(got.alive, *args[1:])
        # ... and one whose vertices outside the window were made alive
        # again takes a second, only for those vertices (lanes with k > 0)
        revived = got.alive | isolated[None, :]
        again = step(got.alive, *args[1:])
        assert_same(again, want, f"seed={seed} call={call} restart")
        assert int(again.iters) == 1
        want = oracle(revived, *args[1:])
        again = step(revived, *args[1:])
        assert_same(again, want, f"seed={seed} call={call} revived")
        assert int(again.iters) == 2


def compile_counter():
    names = []

    def listen(event, start, end, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            names.append(str(kw.get("fun_name", "")))

    jax.monitoring.register_event_time_span_listener(listen)
    return names, listen


def test_windows_of_one_class_share_one_composite_program():
    """Two windows of one size class: the second pool's fresh composite
    step reuses the class's program (no backend compile of it, a
    ``step_program_reuses`` count); a window of a larger class compiles.
    The graph's vertex width is its own, so no other test warmed it."""
    from repro.core.graph import window_tables
    from repro.core.wave import composite_rows

    g = sparse_window_graph(7, n_v=20_011)
    svc = TCQService(g, use_kernel=False, cache=False)
    windows = [(300, 301), (350, 351), (98, 112)]
    classes = []
    for w in windows:
        tab = window_tables(svc.engine._window_tel(*w).tel)
        classes.append(composite_rows(tab))
    assert classes[0] == classes[1] != classes[2]
    names, listen = compile_counter()
    compiled = []
    try:
        for ts, te in windows:
            before = len(names)
            svc.submit({"k": 2, "ts": ts, "te": te})
            svc.run_until_idle()
            compiled.append(sum("_wave_step_impl" in n
                                for n in names[before:]))
    finally:
        jax.monitoring.unregister_event_time_span_listener(listen)
    log = svc.pool_log
    assert [p["step_compiles"] for p in log] == [1, 0, 1]
    assert [p["step_program_reuses"] for p in log] == [0, 1, 0]
    assert compiled == [1, 0, 1]
    assert [tuple(p["step_rows"]) for p in log] == classes


def test_pool_log_carries_step_rows_and_peel_iters():
    g = sparse_window_graph(8)
    svc = TCQService(g, use_kernel=False, cache=False)
    tk = svc.submit({"k": 3, "ts": 99, "te": 108})
    svc.run_until_idle()
    (rec,) = svc.pool_log
    wt = svc.engine._window_tel(99, 108)
    assert rec["step_rows"] == list(wt.step_fn.rows)
    assert rec["peel_iters"] == tk.result.stats.peel_iters
    assert rec["peel_iters"] >= rec["device_steps"] > 0


def youtube_scaled():
    from tcqbench import graphgen
    from tcqbench.registry import Registry

    g = dict(Registry().config("youtube")["graph"])
    for key in ("num_vertices", "num_edges", "num_pairs"):
        g[key] = max(64, int(g[key] * 0.002))
    u, v, t = graphgen.generate(g, 5)
    return g, u, v, t


def test_service_answers_the_youtube_shape_as_the_references_do():
    """The YouTube-growth deployment's graph at 0.002 of its size (each
    friendship once, days as timestamps), 2-8-day windows at k 2-3: the
    service on the window-space composite equals the benchmark's plain
    reference and the repository's brute-force oracle."""
    from repro.core.oracle import brute_force_query
    from tcqbench import reference

    cfg, u, v, t = youtube_scaled()
    g = TemporalGraph.from_edges(u, v, t, cfg["num_vertices"])
    assert g.num_pairs == g.num_edges
    days = np.unique(t)
    svc = TCQService(g, use_kernel=False, cache=False)
    reqs = [(2, 30, 2), (3, 180, 8), (2, 150, 8), (3, 20, 5)]
    tickets = []
    for k, start, width in reqs:
        ts, te = int(days[start]), int(days[start + width - 1])
        tickets.append((svc.submit({"k": k, "ts": ts, "te": te}), k, ts, te))
    svc.run_until_idle()
    assert all(p["backend"] == "xla" for p in svc.pool_log)
    for tk, k, ts, te in tickets:
        got = {c.tti: (c.vertices, c.n_edges) for c in tk.result.cores}
        assert got
        assert reference.digest(got) == reference.digest(
            reference.tcq(u, v, t, k, 1, ts, te))
        want = brute_force_query(g, k, ts, te)
        assert got.keys() == want.keys()
        for tti, (verts, n_edges) in got.items():
            assert set(verts.tolist()) == set(want[tti]["vertices"])
            assert n_edges == want[tti]["n_edges"]
