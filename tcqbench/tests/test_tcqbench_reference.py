"""The plain reference agrees with the repository's brute-force oracle, and
its cut-short control does not."""

import json

import numpy as np
import pytest

from tcqbench import graphgen, reference
from tcqbench.tests.conftest import HERE, TINY_GRAPH


def tiny_graph(seed):
    g = json.loads((HERE / "configs" / "mathoverflow.json").read_text())
    g = dict(g["graph"], **TINY_GRAPH)
    return graphgen.generate(g, seed)


@pytest.mark.parametrize("k,h,width", [(2, 1, 14), (3, 1, 30), (2, 2, 40)])
def test_reference_equals_the_oracle(k, h, width):
    from repro.core.graph import TemporalGraph
    from repro.core.oracle import brute_force_query

    u, v, t = tiny_graph(5)
    g = TemporalGraph.from_edges(u, v, t, TINY_GRAPH["num_vertices"])
    times = np.unique(t)
    ts, te = int(times[100]), int(times[100 + width - 1])
    want = brute_force_query(g, k, ts, te, h)
    got = reference.tcq(u, v, t, k, h, ts, te)
    assert got.keys() == want.keys() and got
    for tti, (verts, n_edges) in got.items():
        assert set(verts) == set(want[tti]["vertices"])
        assert n_edges == want[tti]["n_edges"]


def test_cut_peel_control_differs():
    u, v, t = tiny_graph(6)
    times = np.unique(t)
    ts, te = int(times[200]), int(times[215])
    exact = reference.tcq(u, v, t, 2, 1, ts, te)
    cut = reference.tcq(u, v, t, 2, 1, ts, te, max_peel_rounds=2)
    assert reference.digest(exact) != reference.digest(cut)
