"""Streaming runtime equivalence gates.

The three load-bearing properties of the service refactor:

1. **Incremental append == rebuild** — ``TemporalGraph.add_edges``'s
   sorted-run merge must produce *bit-identical* canonical arrays to a
   from-scratch ``from_edges`` rebuild (same pair factorization, same
   canonical order, same dtypes), across arbitrary batch sequences:
   late timestamps, new vertices, new pairs, duplicate edges.

2. **Mid-flight admission == isolation** — a query admitted into a live
   pool while other queries are peeling returns exactly the result of
   running it alone on its pinned snapshot.

3. **Epoch pinning** — no query ever observes edges pushed after its
   admission, and post-push queries observe exactly the new snapshot.

Plus: an ``EmptyStaircase`` fuzz against the naive empty-marks scan, the
depth-aware ``autotune_wave`` budget, capacity-class shape stability
under appends, and window clustering.
"""

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import (TCQEngine, TCQService, TemporalGraph,
                        cluster_windows)
from repro.core.scheduler import EmptyStaircase, autotune_wave

CANON_FIELDS = ("src", "dst", "t", "pair_id", "pair_u", "pair_v",
                "unique_ts")


def assert_graphs_identical(got, want):
    for f in CANON_FIELDS:
        a, b = getattr(got, f), getattr(want, f)
        assert a.dtype == b.dtype, f
        assert np.array_equal(a, b), f
    assert got.num_vertices == want.num_vertices


def assert_same(got, want, ctx=""):
    assert got.by_tti().keys() == want.by_tti().keys(), ctx
    for key, cw in want.by_tti().items():
        cg = got.by_tti()[key]
        assert np.array_equal(cg.vertices, cw.vertices), (ctx, key)
        assert cg.n_edges == cw.n_edges, (ctx, key)


def random_graph(seed, n_v=20, n_e=140, max_t=16):
    rng = np.random.default_rng(seed)
    return TemporalGraph.from_edges(rng.integers(0, n_v, n_e),
                                    rng.integers(0, n_v, n_e),
                                    rng.integers(1, max_t + 1, n_e), n_v)


# ------------------------------------------------- append == rebuild (exact)
@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_merge_append_bit_identical_to_rebuild(seed):
    rng = np.random.default_rng(seed)
    n_v = int(rng.integers(3, 30))
    batches = []
    for bi in range(int(rng.integers(2, 6))):
        b = int(rng.integers(0, 50))
        # batches 1+ may introduce new vertices (n_v grows) and late
        # (out-of-order, negative) timestamps
        hi_v = n_v + (bi * 7 if bi else 0)
        batches.append((rng.integers(0, hi_v, b), rng.integers(0, hi_v, b),
                        rng.integers(-25, 25, b)))
    g = TemporalGraph.from_edges(*batches[0])
    flat = [np.asarray(c) for c in batches[0]]
    for bi, (u, v, t) in enumerate(batches[1:], start=1):
        g = g.add_edges(u, v, t)
        assert g.epoch == bi
        flat = [np.concatenate([a, np.asarray(c)])
                for a, c in zip(flat, (u, v, t))]
    ref = TemporalGraph.from_edges(*flat, num_vertices=g.num_vertices)
    assert_graphs_identical(g, ref)


@settings(max_examples=40, deadline=None)
@given(st.lists(st.lists(st.tuples(st.integers(0, 14), st.integers(0, 14),
                                   st.integers(-9, 9)),
                         min_size=0, max_size=20),
                min_size=1, max_size=5))
def test_merge_append_fuzz(batches):
    """Hypothesis fuzz: any batch sequence (duplicates, self loops, empty
    batches, late data) merges to the exact rebuilt canonical arrays."""
    def cols(b):
        if not b:
            return (np.zeros(0, np.int64),) * 3
        a = np.asarray(b, np.int64)
        return a[:, 0], a[:, 1], a[:, 2]

    g = TemporalGraph.from_edges(*cols(batches[0]), num_vertices=15)
    flat = list(batches[0])
    for b in batches[1:]:
        g = g.add_edges(*cols(b))
        flat += list(b)
    ref = TemporalGraph.from_edge_list(flat, num_vertices=g.num_vertices)
    assert_graphs_identical(g, ref)


def test_append_empty_and_self_loop_batches_are_noops():
    g = random_graph(5)
    assert g.add_edges([], [], []) is g
    assert g.add_edges([3, 7], [3, 7], [1, 2]) is g
    assert g.epoch == 0


# ------------------------------------------------------- engine epoch swaps
def test_update_graph_equals_fresh_engine():
    g0 = random_graph(7, n_v=18, n_e=120, max_t=14)
    eng = TCQEngine(g0)
    Ts, Te = g0.span
    base = eng.query(2, Ts, Te)
    rng = np.random.default_rng(8)
    g1 = g0.add_edges(rng.integers(0, 22, 40), rng.integers(0, 22, 40),
                      rng.integers(1, 20, 40))
    assert eng.update_graph(g1) == eng.epoch == 1
    for mode in ("serial", "wave"):
        got = eng.query(2, *g1.span, mode=mode)
        want = TCQEngine(g1).query(2, *g1.span)
        assert_same(got, want, ctx=mode)
    # the pre-update result is reproducible from the old snapshot
    assert_same(base, TCQEngine(g0).query(2, Ts, Te))


def test_update_graph_capacity_classes_keep_shapes():
    """Appends inside a capacity class must not change device TEL shapes
    (that is what lets streaming reuse compiled programs)."""
    g = random_graph(9, n_v=30, n_e=100, max_t=20)
    eng = TCQEngine(g)
    # first growth jumps the edge buffers to a power-of-two capacity
    g = g.add_edges([1, 2, 3], [4, 5, 6], [3, 4, 5])
    eng.update_graph(g)
    shape0 = {f: getattr(eng.tel, f).shape for f in eng.tel._fields}
    cap0 = (eng._edge_cap, eng._pair_cap, eng._v_cap)
    assert eng._edge_cap == 128      # pow2 bucket above 103
    rng = np.random.default_rng(10)
    while g.num_edges < cap0[0] and g.num_pairs < cap0[1]:
        g = g.add_edges(rng.integers(0, 30, 4), rng.integers(0, 30, 4),
                        rng.integers(1, 24, 4))
        eng.update_graph(g)
        if (eng._edge_cap, eng._pair_cap, eng._v_cap) != cap0:
            break               # a class legitimately grew: shapes may too
        assert {f: getattr(eng.tel, f).shape
                for f in eng.tel._fields} == shape0
    # growth beyond the class doubles it (power-of-two)
    add = cap0[0]
    g = g.add_edges(rng.integers(0, 30, add), rng.integers(0, 30, add),
                    rng.integers(1, 24, add))
    eng.update_graph(g)
    assert eng._edge_cap >= 2 * cap0[0]
    assert eng._edge_cap & (eng._edge_cap - 1) == 0


def test_window_cache_is_epoch_keyed():
    g0 = random_graph(11, n_v=16, n_e=110, max_t=18)
    eng = TCQEngine(g0)
    Ts, Te = g0.span
    lo, hi = Ts + 2, Te - 2
    r0 = eng.query(2, lo, hi)
    assert (0, lo, hi) in eng._win_cache
    # push edges INSIDE the window: a stale truncation would be wrong
    g1 = g0.add_edges([0, 1, 2, 3], [5, 6, 7, 8],
                      [lo + 1, lo + 1, lo + 2, lo + 2])
    eng.update_graph(g1)
    r1 = eng.query(2, lo, hi)
    assert (1, lo, hi) in eng._win_cache      # new epoch, new entry
    want = TCQEngine(g1).query(2, lo, hi)
    assert_same(r1, want)
    # and the old snapshot's result is still derivable from its epoch
    assert_same(r0, TCQEngine(g0).query(2, lo, hi))


# ------------------------------------------------------ service: mid-flight
@pytest.mark.parametrize("seed", [0, 4])
def test_midflight_admission_equals_isolated(seed):
    g = random_graph(seed, n_v=22, n_e=200, max_t=20)
    Ts, Te = g.span
    mid = (Ts + Te) // 2
    svc = TCQService(g, wave=4)
    first = svc.submit({"k": 2, "ts": Ts, "te": Te})
    late_reqs = [{"k": 3, "ts": Ts, "te": mid},
                 {"k": 2, "ts": mid, "te": Te, "h": 2},
                 {"k": 4, "ts": Ts + 1, "te": Te - 1}]
    injected = []

    def poll(s):
        if late_reqs:
            injected.append(s.submit(late_reqs.pop()))

    served = svc.run_until_idle(poll)
    assert first.done and all(tk.done for tk in injected)
    assert len(served) == 4
    # at least some of the injected queries joined the live pool
    assert sum(p["admitted_midflight"] for p in svc.pool_log) >= 1
    eng = TCQEngine(g)
    for tk in [first] + injected:
        want = eng.query(tk.k, tk.ts, tk.te, h=tk.h)
        assert_same(tk.result, want, ctx=f"ticket {tk.id}")


def test_epoch_pinning_no_future_edges():
    """A query admitted at epoch e must not see edges pushed after its
    admission — even when the push lands mid-flight inside its window."""
    g0 = random_graph(13, n_v=20, n_e=160, max_t=18)
    Ts, Te = g0.span
    svc = TCQService(g0, wave=4)
    pinned = svc.submit({"k": 2, "ts": Ts, "te": Te})
    fired = {}

    def poll(s):
        if "late" not in fired:
            # a dense clique inside the pinned window: would change the
            # result set if the pinned query could see it
            u = [0, 0, 0, 1, 1, 2]
            v = [1, 2, 3, 2, 3, 3]
            t = [Ts + 1] * 6
            s.push_edges(u, v, t)
            fired["late"] = s.submit({"k": 2, "ts": Ts, "te": Te})

    svc.run_until_idle(poll)
    late = fired["late"]
    assert pinned.epoch == 0 and late.epoch == 1
    assert_same(pinned.result, TCQEngine(g0).query(2, Ts, Te), "pinned")
    g1 = svc.graph
    assert_same(late.result, TCQEngine(g1).query(2, Ts, Te), "late")
    # the snapshots genuinely diverge (the test would be vacuous otherwise)
    assert len(late.result) != len(pinned.result) or \
        late.result.by_tti().keys() != pinned.result.by_tti().keys()


def test_service_batch_equals_query_batch():
    """Same fixed request set: the clustered service and the single-pool
    query_batch must agree result-for-result."""
    g = random_graph(17, n_v=24, n_e=220, max_t=24)
    Ts, Te = g.span
    third = (Te - Ts) // 3
    reqs = [{"k": 2, "ts": Ts, "te": Ts + third},
            {"k": 3, "ts": Ts, "te": Ts + third // 2},
            {"k": 2, "ts": Te - third, "te": Te},       # disjoint cluster
            {"k": 2, "ts": Te - third // 2, "te": Te, "h": 2}]
    eng = TCQEngine(g)
    batch = eng.query_batch(reqs)
    svc = TCQService(graph=None, engine=eng)
    tickets = [svc.submit(r) for r in reqs]
    svc.run_until_idle()
    assert len(svc.pool_log) == 2       # two window clusters, two pools
    for tk, want in zip(tickets, batch):
        assert_same(tk.result, want, ctx=f"ticket {tk.id}")


def test_empty_window_and_snapshot_retention():
    """Resolved-at-submit tickets must still come back from pump /
    run_until_idle, and completion drops the heavy per-ticket state
    (QueryState always; the pinned snapshot when retain_snapshots=False)."""
    g = random_graph(19)
    Ts, Te = g.span
    svc = TCQService(g)
    empty = svc.submit({"k": 2, "ts": Te + 10, "te": Te + 20})
    real = svc.submit({"k": 2, "ts": Ts, "te": Te})
    served = svc.run_until_idle()
    assert empty in served and real in served
    assert empty.done and len(empty.result) == 0
    assert real.state is None           # packed rows freed on completion
    assert real.graph is g              # snapshots retained by default
    svc2 = TCQService(g, retain_snapshots=False)
    tk = svc2.submit({"k": 2, "ts": Ts, "te": Te})
    out = svc2.run_until_idle()
    assert out == [tk] and tk.done and tk.graph is None


# ------------------------------------------------------------- clustering
def test_cluster_windows():
    assert cluster_windows([]) == []
    assert cluster_windows([(3, 9)]) == [[0]]
    assert cluster_windows([(0, 5), (4, 9), (20, 30), (8, 10)]) == \
        [[0, 1, 3], [2]]
    assert cluster_windows([(10, 12), (0, 2), (3, 5)]) == [[1], [2], [0]]
    assert cluster_windows([(10, 12), (0, 2), (3, 5)], gap=1) == \
        [[1, 2], [0]]
    # chains merge transitively
    assert cluster_windows([(0, 4), (3, 8), (7, 11)]) == [[0, 1, 2]]


# ------------------------------------------------- EmptyStaircase fuzz
@settings(max_examples=60, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 24), st.integers(0, 24)),
                min_size=1, max_size=40),
       st.lists(st.integers(-1, 25), min_size=1, max_size=8))
def test_empty_staircase_fuzz_vs_naive(marks, probes):
    stair = EmptyStaircase()
    for i, j in marks:
        stair.add(i, j)
    for r in probes:
        naive = max((je for ie, je in marks if ie <= r), default=-1)
        assert stair.bound(r) == naive, (marks, r)


# ------------------------------------------------------- autotune depth
def test_autotune_wave_accounts_for_ring_depth():
    v, e = 2_000, 60_000
    base = autotune_wave(v, e, num_queries=64, depth=2)
    # the element budget covers D*W lanes in flight: deeper rings shrink W
    assert autotune_wave(v, e, num_queries=64, depth=8) <= base // 2
    # depth=2 matches the historical (pre-depth-aware) tuning
    assert base == autotune_wave(v, e, num_queries=64)
    for depth in (1, 2, 3, 4, 8):
        w = autotune_wave(v, e, num_queries=64, depth=depth)
        assert 4 <= w <= 64 and w & (w - 1) == 0
    # demand-bound regimes (small V*E) are depth-insensitive
    assert autotune_wave(30, 200, num_queries=1, depth=8) == \
        autotune_wave(30, 200, num_queries=1, depth=1)


# ------------------------------------------------ ticket lifecycle edges
def test_cancel_before_first_slot():
    """A ticket cancelled while still queued never touches the pool: it
    resolves immediately with an empty partial result, and its pool-mates
    are served exactly as if it had never been submitted."""
    g = random_graph(21, n_v=22, n_e=200, max_t=20)
    Ts, Te = g.span
    svc = TCQService(g, wave=4)
    keep = svc.submit({"k": 2, "ts": Ts, "te": Te})
    gone = svc.submit({"k": 3, "ts": Ts, "te": Te})
    assert svc.cancel(gone)
    assert gone.status == "cancelled" and gone.done
    assert gone.result is not None and len(gone.result) == 0
    served = svc.run_until_idle()
    assert keep.status == "done"
    # the cancelled ticket was handed back by pump(), not re-run
    assert {tk.id for tk in served} == {keep.id, gone.id}
    assert_same(keep.result, TCQEngine(g).query(2, Ts, Te), "survivor")


def test_deadline_expires_mid_pool():
    """A running ticket whose deadline passes mid-pool has its lanes
    reclaimed at the next wave and resolves as ``timeout`` with whatever
    cells had completed; pool-mates are unaffected."""
    g = random_graph(22, n_v=22, n_e=200, max_t=20)
    Ts, Te = g.span
    svc = TCQService(g, wave=4)
    keep = svc.submit({"k": 2, "ts": Ts, "te": Te})
    # far-future deadline: admitted normally, expired deterministically
    # by the poll below (wall-clock-free determinism)
    doomed = svc.submit({"k": 3, "ts": Ts, "te": Te,
                         "deadline_s": 3600.0})
    state = {"polls": 0}

    def poll(s):
        state["polls"] += 1
        if state["polls"] == 2:         # inside the live pool's admit hook
            doomed.deadline = 1.0       # long past (perf_counter scale)

    svc.run_until_idle(poll)
    assert doomed.status == "timeout" and doomed.done
    assert doomed.result is not None
    assert keep.status == "done"
    assert_same(keep.result, TCQEngine(g).query(2, Ts, Te), "survivor")
    assert any(p["timeouts"] for p in svc.pool_log)


def test_empty_result_query_races_ingest():
    """A query whose window holds no snapshot timestamps resolves empty
    at submit — and stays empty even when an ingest lands edges inside
    that window before the next pump (epoch pinning for the degenerate
    cell-free schedule)."""
    g = random_graph(23, n_v=18, n_e=120, max_t=10)
    Ts, Te = g.span
    svc = TCQService(g, wave=4)
    empty = svc.submit({"k": 2, "ts": Te + 5, "te": Te + 9})
    assert empty.done and empty.status == "done" and len(empty.result) == 0
    # the race: edges land inside [Te+5, Te+9] right after submission
    svc.push_edges([0, 0, 1], [1, 2, 2], [Te + 6, Te + 7, Te + 8])
    fresh = svc.submit({"k": 2, "ts": Te + 5, "te": Te + 9})
    served = svc.run_until_idle()
    assert {tk.id for tk in served} == {empty.id, fresh.id}
    assert len(empty.result) == 0           # still pinned to epoch 0
    assert_same(fresh.result, TCQEngine(svc.graph).query(2, Te + 5, Te + 9),
                "post-ingest")


def test_window_cache_retires_dead_epochs():
    """Window TELs and pair tables of epochs no ticket pins anymore are
    evicted after each pool instead of lingering until LRU capacity."""
    g = random_graph(24, n_v=18, n_e=120, max_t=10)
    Ts, Te = g.span
    svc = TCQService(g, wave=4)
    svc.submit({"k": 2, "ts": Ts, "te": Te})
    svc.push_edges([0, 1], [2, 3], [Ts + 1, Ts + 2])
    svc.submit({"k": 2, "ts": Ts, "te": Te})
    svc.push_edges([2, 3], [4, 5], [Ts + 1, Ts + 2])
    svc.submit({"k": 2, "ts": Ts, "te": Te})
    svc.run_until_idle()
    live = {svc.engine.epoch}
    assert set(svc.engine._epoch_aux) <= live
    assert {key[0] for key in svc.engine._win_cache} <= live
