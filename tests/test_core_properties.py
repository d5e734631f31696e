"""Property-based tests (hypothesis) for the paper's invariants."""

import numpy as np

from hypothesis import given, settings, strategies as st

from repro.core import TCQEngine, TemporalGraph, brute_force_query
from repro.core.oracle import peel_window


@st.composite
def temporal_graphs(draw, max_v=12, max_e=50, max_t=10):
    n_v = draw(st.integers(3, max_v))
    n_e = draw(st.integers(1, max_e))
    edges = draw(st.lists(
        st.tuples(st.integers(0, n_v - 1), st.integers(0, n_v - 1),
                  st.integers(1, max_t)),
        min_size=n_e, max_size=n_e))
    return TemporalGraph.from_edge_list(edges, num_vertices=n_v)


@settings(max_examples=60, deadline=None)
@given(temporal_graphs(), st.integers(1, 4), st.integers(1, 2))
def test_otcd_equals_oracle(g, k, h):
    if g.num_edges == 0:
        return
    Ts, Te = g.span
    oracle = brute_force_query(g, k, Ts, Te, h)
    res = TCQEngine(g).query(k, Ts, Te, h=h)
    assert set(c.tti for c in res.cores) == set(oracle.keys())
    for c in res.cores:
        assert set(c.vertices.tolist()) == set(oracle[c.tti]["vertices"])
        assert c.n_edges == oracle[c.tti]["n_edges"]


@settings(max_examples=30, deadline=None)
@given(temporal_graphs(), st.integers(1, 4))
def test_wave_equals_serial(g, k):
    if g.num_edges == 0:
        return
    Ts, Te = g.span
    eng = TCQEngine(g)
    a = eng.query(k, Ts, Te)
    b = eng.query(k, Ts, Te, mode="wave", wave=5)
    assert a.by_tti().keys() == b.by_tti().keys()


@settings(max_examples=30, deadline=None)
@given(temporal_graphs(), st.integers(1, 3))
def test_tti_inclusion_property(g, k):
    """Paper Property 3: [ts,te] ⊆ [ts',te'] => TTI ⊆ TTI'."""
    if g.num_edges == 0:
        return
    Ts, Te = g.span
    mid = (Ts + Te) // 2
    em_small = peel_window(g, Ts, mid, k)
    em_big = peel_window(g, Ts, Te, k)
    if em_small.any() and em_big.any():
        lo_s, hi_s = g.t[em_small].min(), g.t[em_small].max()
        lo_b, hi_b = g.t[em_big].min(), g.t[em_big].max()
        assert lo_b <= lo_s and hi_s <= hi_b


@settings(max_examples=30, deadline=None)
@given(temporal_graphs(), st.integers(1, 3))
def test_tti_fixpoint_property(g, k):
    """Theorem 2 + Property 1: re-peeling a core over its own TTI returns the
    identical core (TTI is tight and unique)."""
    if g.num_edges == 0:
        return
    Ts, Te = g.span
    em = peel_window(g, Ts, Te, k)
    if not em.any():
        return
    lo, hi = int(g.t[em].min()), int(g.t[em].max())
    em2 = peel_window(g, lo, hi, k)
    assert np.array_equal(em, em2)


@settings(max_examples=25, deadline=None)
@given(temporal_graphs(), st.integers(1, 3))
def test_monotone_in_k(g, k):
    """(k+1)-cores are subgraphs of k-cores (classic nesting), and the number
    of distinct cores is non-increasing in k (paper Fig. 10 rationale)."""
    if g.num_edges == 0:
        return
    Ts, Te = g.span
    em_k = peel_window(g, Ts, Te, k)
    em_k1 = peel_window(g, Ts, Te, k + 1)
    assert not np.any(em_k1 & ~em_k)
    eng = TCQEngine(g)
    assert len(eng.query(k + 1, Ts, Te)) <= len(eng.query(k, Ts, Te))


@settings(max_examples=25, deadline=None)
@given(temporal_graphs(), st.integers(1, 3))
def test_monotone_in_h(g, k):
    """Link-strength: raising h only shrinks cores (paper §6.2)."""
    if g.num_edges == 0:
        return
    Ts, Te = g.span
    em1 = peel_window(g, Ts, Te, k, h=1)
    em2 = peel_window(g, Ts, Te, k, h=2)
    assert not np.any(em2 & ~em1)


@settings(max_examples=25, deadline=None)
@given(temporal_graphs(), st.integers(1, 3))
def test_warm_start_invariance(g, k):
    """Theorem 1: peeling warm-started from any superset core equals the
    cold-start result — checked through the device engine."""
    import jax.numpy as jnp

    from repro.core.tcd import tcd

    if g.num_edges == 0:
        return
    Ts, Te = g.span
    tel = g.device_tel()
    ones = jnp.ones((g.num_vertices,), dtype=bool)
    big = tcd(tel, ones, Ts, Te, k, 1, num_vertices=g.num_vertices)
    mid = (Ts + Te) // 2
    cold = tcd(tel, ones, Ts, mid, k, 1, num_vertices=g.num_vertices)
    warm = tcd(tel, big.alive, Ts, mid, k, 1, num_vertices=g.num_vertices)
    assert np.array_equal(np.asarray(cold.alive), np.asarray(warm.alive))


def test_pruning_accounting_is_exact():
    """evaluated + pruned + trivially-empty cells cover the whole schedule."""
    from repro.graphs import planted_cores

    g = planted_cores(seed=3)
    s = TCQEngine(g).query(3, 1, 40).stats
    covered = (s.cells_evaluated + s.pruned_total + s.pruned_empty
               + s.cells_trivial)
    assert covered == s.cells_total
    assert 0 <= s.pruned_pct() <= 100.0


def test_span_constraint_filter():
    from repro.graphs import planted_cores

    g = planted_cores(seed=3)
    res = TCQEngine(g).query(3, 1, 40, max_span=3)
    assert all(c.span <= 3 for c in res.cores)
    full = TCQEngine(g).query(3, 1, 40)
    expect = [c for c in full.cores if c.span <= 3]
    assert len(res) == len(expect)
    top = full.top_n_shortest_span(3)
    assert len(top) == 3
    assert top[0].span <= top[-1].span
