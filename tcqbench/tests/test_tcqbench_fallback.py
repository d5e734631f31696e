"""``kernel_fallback_share`` on hand-made pool records, on a program that
records no ``fallback``, and on the records of a service whose fused
kernel the SMEM budget refuses."""

import pytest

from tcqbench.registry import Registry

read = Registry().reader("kernel_fallback_share")


def pool(backend, fallback):
    return {"backend": backend, "fallback": fallback}


def test_share_of_pools_that_wanted_the_kernel():
    pools = [pool("xla", "smem_tables"), pool("pallas", None),
             pool("xla", "vmem_budget"), pool("pallas", None),
             pool("xla", None)]          # never wanted the kernel
    assert read({"pools": pools}) == pytest.approx(50.0)
    assert read({"pools": pools[:1]}) == pytest.approx(100.0)


def test_nothing_to_read_without_the_counter_or_a_wanted_kernel():
    assert read({"pools": [{"backend": "pallas"}]}) is None
    assert read({"pools": [pool("xla", None)]}) is None
    assert read({"pools": []}) is None


def test_reads_the_services_pool_log(monkeypatch):
    import numpy as np

    import repro.kernels.wave_peel.ops as ops
    from repro.core import TCQService, TemporalGraph

    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    monkeypatch.setattr(ops, "SMEM_TABLE_BUDGET", 0)
    rng = np.random.default_rng(5)
    u = rng.integers(0, 20, 200)
    g = TemporalGraph.from_edges(u, (u + rng.integers(1, 20, 200)) % 20,
                                 rng.integers(0, 30, 200), 20)
    svc = TCQService(g, use_kernel=True, cache=False)
    for ts in (0, 15):
        svc.submit({"k": 2, "ts": ts, "te": ts + 10})
    svc.run_until_idle()
    assert read({"pools": [dict(p) for p in svc.pool_log]}) == 100.0
