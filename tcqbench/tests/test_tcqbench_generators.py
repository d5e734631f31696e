"""The graph generator and the traffic generator: deterministic per seed,
calibrated to each configuration's published counts, the same work for
every seed."""

import numpy as np
import pytest

from tcqbench import graphgen, traffic
from tcqbench.registry import Registry

REG = Registry()
CONFIGS = sorted(p.stem for p in (REG.root / "configs").glob("*.json"))


def scaled(g, s):
    g = dict(g)
    for key in ("num_vertices", "num_edges", "num_pairs"):
        g[key] = max(64, int(g[key] * s))
    return g


@pytest.mark.parametrize("name", CONFIGS)
def test_config_graph_holds_the_published_counts(name):
    cfg = REG.config(name)
    pub, g = cfg["published"], cfg["graph"]
    assert g["num_vertices"] == pub["vertices"]
    assert g["num_edges"] == pub["temporal_edges"]
    assert g["num_pairs"] == pub["static_edges"]
    assert g["days"] == pub["days"]
    assert set(cfg["reduced"]) <= set(g)


def test_mathoverflow_matches_its_published_counts_within_tolerance():
    cfg = REG.config("mathoverflow")
    g = cfg["graph"]
    u, v, t = graphgen.generate(g, 2 ** 33 + 5)
    got = graphgen.counts(u, v, t, g["time_units"], g["days"])
    for key, want in cfg["published"].items():
        assert abs(got[key] - want) <= cfg["tolerance"][key] * want, key
    assert not np.any(u == v)


@pytest.mark.parametrize("name", CONFIGS)
def test_generator_is_exact_and_deterministic_at_a_reduced_scale(name):
    cfg = REG.config(name)
    g = scaled(cfg["graph"], 0.01 if name != "youtube" else 0.002)
    a = graphgen.generate(g, 123)
    b = graphgen.generate(g, 123)
    c = graphgen.generate(g, 124)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    assert any(not np.array_equal(x, y) for x, y in zip(a, c))
    got = graphgen.counts(*a, g["time_units"], g["days"])
    assert got["temporal_edges"] == g["num_edges"]
    assert got["static_edges"] == g["num_pairs"]
    assert got["vertices"] == g["num_vertices"]
    assert a[2].min() >= 1 and a[2].max() <= g["time_units"]


def test_graph_cache_round_trip(tmp_path):
    g = scaled(REG.config("mathoverflow")["graph"], 0.01)
    u, v, t, cached = graphgen.load_or_generate("x", g, 9, tmp_path)
    assert not cached
    u2, v2, t2, cached = graphgen.load_or_generate("x", g, 9, tmp_path)
    assert cached
    for x, y in ((u, u2), (v, v2), (t, t2)):
        np.testing.assert_array_equal(x, y)
    assert len(list(tmp_path.glob("*.npz"))) == 1


@pytest.mark.parametrize("name", CONFIGS)
def test_traffic_is_the_same_work_in_another_order(name):
    mix = REG.traffic("adhoc", name)
    times = np.arange(1, 2000, 3)
    seconds = 50
    runs = []
    for seed in (2 ** 32 + 1, 77):
        warm = traffic.warmup(mix, times, seed)
        reqs = traffic.schedule(mix, times, seconds, seed,
                                exclude=traffic.warmup_windows(warm))
        assert reqs == traffic.schedule(mix, times, seconds, seed,
                                        exclude=traffic.warmup_windows(warm))
        wins = [(r["ts"], r["te"]) for r in reqs]
        assert len(set(wins)) == len(wins)
        assert not set(wins) & set(traffic.warmup_windows(warm))
        arrive = [r["arrive_s"] for r in reqs]
        assert arrive == sorted(arrive) and 0 < arrive[0]
        assert arrive[-1] < seconds
        lo, hi = mix["windows"]["width_timestamps"]
        idx = {int(x): i for i, x in enumerate(times)}
        widths = [idx[r["te"]] - idx[r["ts"]] + 1 for r in reqs]
        assert lo <= min(widths) and max(widths) <= hi
        assert {r["k"] for r in reqs} <= {int(k) for k in mix["k"]}
        assert mix["warmup"]["k"] not in {r["k"] for r in reqs}
        runs.append((arrive,
                     sorted(zip(widths, (r["k"] for r in reqs),
                                (r["h"] for r in reqs))),
                     [(r["k"], r["h"]) for r in reqs], wins))
    a, b = runs
    assert a[0] == b[0]                     # the same arrivals
    assert a[1] == b[1]                     # the same requests
    assert sorted(a[3]) == sorted(b[3])     # at the same places
    assert a[2] != b[2] and a[3] != b[3]    # in another order


def test_arrivals_are_poisson_gaps_inside_the_window():
    mix = {"arrivals": {"process": "poisson", "rate_per_s": 0.42}}
    at = traffic.arrivals(mix, 21, 50.0)
    gaps = np.diff(np.concatenate([[0.0], at]))
    assert 0 < at[0] and at[-1] < 50.0 and np.all(gaps > 0)
    # exponential gaps: about as many below the mean gap's log 2 as above
    assert abs(np.sum(gaps < np.log(2) * 50.0 / 21) - 10.5) <= 1
    with pytest.raises(ValueError):
        traffic.arrivals({"arrivals": {"process": "uniform"}}, 21, 50.0)


def test_relabel_keeps_every_core_and_changes_the_arrays():
    from tcqbench import reference

    g = dict(REG.config("mathoverflow")["graph"], num_vertices=300,
             num_edges=3000, num_pairs=1500, time_units=256)
    u, v, t = graphgen.generate(g, 5)
    ts, te = int(np.quantile(t, 0.3)), int(np.quantile(t, 0.5))

    def shapes(uu, vv, tt):
        cores = reference.tcq(uu, vv, tt, 2, 1, ts, te)
        return sorted((tti, n, len(vs)) for tti, (vs, n) in cores.items())

    a = graphgen.relabel(u, v, t, 300, 2 ** 32 + 3)
    b = graphgen.relabel(u, v, t, 300, 8)
    assert not np.array_equal(a[0], b[0])
    np.testing.assert_array_equal(np.sort(a[2]), np.sort(t))
    want = shapes(u, v, t)
    assert want and shapes(*a) == want and shapes(*b) == want


def test_a_labels_mix_offers_every_seed_the_same_requests(tmp_path):
    from tcqbench import harness
    from tcqbench.tests.conftest import make_registry

    reg = make_registry(tmp_path, seed_draws="labels")
    cell = reg.workload("tiny.adhoc")
    cfg, mix = reg.config("tiny"), reg.traffic("adhoc", "tiny")
    runs = [harness.inputs(reg, cfg, mix, seed, 5.0)
            for seed in (2 ** 32 + 1, 77, 77)]
    assert cell["config"] == "tiny"
    (a, _, ra), (b, _, rb), (c, _, rc) = runs
    assert ra == rb == rc
    assert not np.array_equal(a[0], b[0])
    for x, y in zip(b[:3], c[:3]):
        np.testing.assert_array_equal(x, y)
    with pytest.raises(ValueError):
        harness.base_seed(dict(mix, seed_draws="places"), 1)
