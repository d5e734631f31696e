"""The step-layer readers of ``step_rows`` and ``peel_iters`` read the
program's ``pool_log`` counters, and read nothing where the program
records none."""

import pytest

from tcqbench.registry import Registry

REG = Registry()


def pool(steps, rows=None, iters=None):
    p = {"device_steps": steps, "backend": "xla", "fallback": None}
    if rows is not None:
        p["step_rows"] = rows
    if iters is not None:
        p["peel_iters"] = iters
    return p


def test_step_vertex_rows_is_the_step_weighted_vertex_rows():
    read = REG.reader("step_vertex_rows")
    pools = [pool(10, [1024, 512, 2048], 50), pool(30, [256, 256, 512], 60),
             pool(5, None, 9)]          # a step without a class: no rows
    assert read({"pools": pools}) == pytest.approx((2048 * 10 + 512 * 30)
                                                   / 40)


def test_peel_iters_per_step_is_iterations_over_steps():
    read = REG.reader("peel_iters_per_step")
    pools = [pool(10, [1024, 512, 2048], 50), pool(30, [256, 256, 512], 60)]
    assert read({"pools": pools}) == pytest.approx(110 / 40)


@pytest.mark.parametrize("name", ["step_vertex_rows", "peel_iters_per_step"])
def test_step_counter_readers_read_nothing_without_the_counters(name):
    """As on a program that records neither counter, or ran no step."""
    read = REG.reader(name)
    assert read({"pools": [pool(10), pool(3)]}) is None
    assert read({"pools": []}) is None
    assert read({"pools": [pool(0, [128, 128, 128], 0)]}) is None


def test_youtube_cell_reports_its_readers():
    names = {m["name"] for m in REG.metrics("youtube.adhoc", "per_layer")}
    assert names == {"queue_wait_p95_ms.youtube", "compiles_in_window.youtube",
                     "step_device_ms.youtube", "peel_step_roofline.youtube",
                     "device_idle_share.youtube", "step_vertex_rows",
                     "peel_iters_per_step", "compile_s_in_window.youtube",
                     "bytes_synced_per_step.youtube", "lane_occupancy.youtube",
                     "cells_per_request.youtube", "kernel_fallback_share"}
    for name in names:
        assert callable(REG.reader(name))
    e2e = {m["name"] for m in REG.metrics("youtube.adhoc", "end_to_end")}
    assert e2e == {"latency_mean_ms", "setup_s"}


@pytest.mark.parametrize("base", ["compiles_in_window", "compile_s_in_window",
                                  "bytes_synced_per_step", "lane_occupancy",
                                  "cells_per_request"])
def test_youtube_readers_read_what_their_base_readers_read(base):
    stats = {"device_steps": 7, "bytes_synced": 7 * 4096,
             "cells_evaluated": 3}
    run = {"compiles": [(1.0, 5.5, "jit__wave_step_impl")],
           "pools": [dict(pool(7, [262144] * 3, 60), occupancy=3, wave=4)],
           "tickets": [{"stats": stats}, {"stats": None}]}
    value = REG.reader(base)(run)
    assert value is not None
    assert REG.reader(f"{base}.youtube")(run) == value
