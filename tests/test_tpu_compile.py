"""Compile the main path for a described TPU v5e topology (no chip needed).

The TPU compiler is installed even where no chip is attached: it compiles
for a topology that is described, not present, and refuses what the chip
would refuse (Mosaic lowering errors, SMEM/VMEM overflow, unpartitionable
programs).  Interpret-mode tests cannot see any of that.  These tests
compile the fused wave-peel step at the chip smoke's widths, the banded
segsum kernel, the window-space XLA composite step (at the smoke's and
the YouTube-growth cell's size class), and the sharded steps on a 2x2 and
a 4x1 mesh of the described devices.  Nothing runs, so nothing here says
anything about results or times.

The topology is described inside a module-scoped fixture (never at
import): only one process at a time may load the TPU library.
"""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as PS
from jax.sharding import SingleDeviceSharding

V_SMOKE = 24_576          # MathOverflow-shaped graph: vertex width


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A described-topology compile can be written to the persistent
    cache but never read back without a chip; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _window_tel():
    """A window TEL of ~16K live edges over the smoke's vertex width."""
    from repro.graphs import powerlaw_temporal

    g = powerlaw_temporal(V_SMOKE, 20_000, 2_048, seed=0)
    return g, g.device_tel()


def test_fused_wave_peel_step_compiles(one_chip):
    from repro.kernels.wave_peel.ops import make_fused_wave_step

    _, tel = _window_tel()
    assert 8_000 <= tel.num_edges <= 32_000
    step = make_fused_wave_step(tel, V_SMOKE, interpret=False)
    assert step is not None and step.interpret is False
    assert "tpu_custom_call" in _compile_fused(step, one_chip)


def test_fused_wave_peel_class_step_compiles(one_chip):
    """A MathOverflow-sized window takes its power-of-two size class:
    the program that every window of the class shares compiles."""
    from repro.graphs import powerlaw_temporal
    from repro.kernels.wave_peel.ops import make_fused_wave_step

    tel = powerlaw_temporal(V_SMOKE, 800, 64, seed=1).device_tel()
    step = make_fused_wave_step(tel, V_SMOKE, interpret=False)
    assert step is not None and step.operand_shapes[0] == (1024,)
    assert "tpu_custom_call" in _compile_fused(step, one_chip)


def _compile_fused(step, sharding, w=64):
    """HLO text of the fused step's shared program, compiled for the
    described chip with this window's arguments."""
    window = jax.tree.map(lambda a: _sds(a.shape, a.dtype, sharding),
                          step.window_args)
    lane = _sds((w,), jnp.int32, sharding)
    return step.jitted.lower(
        *window, _sds((w, V_SMOKE), jnp.bool_, sharding),
        lane, lane, lane, lane, interpret=False).compile().as_text()


def test_banded_segsum_kernel_compiles(one_chip):
    from repro.kernels.segdeg.kernel import (banded_segsum_pallas,
                                             required_k_max)

    rng = np.random.default_rng(0)
    n, s, q = 32_768, V_SMOKE, 128
    segs = np.sort(rng.integers(0, s, n)).astype(np.int32)
    k_max = required_k_max(segs, s)
    compiled = banded_segsum_pallas.lower(
        _sds((n, q), jnp.float32, one_chip), _sds((n,), jnp.int32, one_chip),
        num_segments=s, k_max=k_max, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


V_YOUTUBE = 3_223_589     # YouTube-growth graph: vertex width


@pytest.mark.parametrize("case", ["smoke", "youtube"])
def test_composite_step_compiles(one_chip, case):
    """The window-space XLA composite compiles for the described chip at
    a window's size class: the smoke window's own, and the YouTube-growth
    cell's widest (524,288 edge, pair and vertex rows, 4 lanes over its
    3,223,589 vertices)."""
    from repro.core.graph import window_tables
    from repro.core.wave import _COMPOSITE, _make_xla_step

    if case == "smoke":
        _, tel = _window_tel()
        step = _make_xla_step(window_tables(tel), V_SMOKE)
        (e_rows, p_rows, v_rows), v, w = step.rows, V_SMOKE, 64
        assert step.jitted is _COMPOSITE[False].jitted
    else:
        e_rows = p_rows = v_rows = 524_288
        v, w = V_YOUTUBE, 4
    i32 = functools.partial(_sds, dtype=jnp.int32, sharding=one_chip)
    tables = tuple(i32((n,)) for n in (e_rows, e_rows, p_rows, p_rows,
                                       p_rows, v_rows, 2 * p_rows,
                                       2 * p_rows))
    lane = i32((w,))
    compiled = _COMPOSITE[True].jitted.lower(
        tables, i32((v_rows,)), i32((v,)),
        _sds((w, v), jnp.bool_, one_chip), lane, lane, lane, lane).compile()
    mem = compiled.memory_analysis()
    assert mem is not None
    assert mem.temp_size_in_bytes < (4 << 30)


@pytest.mark.parametrize("combine", ["psum", "rs_ag"])
def test_sharded_step_compiles_on_2x2(topo, combine):
    from repro.core.distributed import ShardPlan, _sharded_step_jit
    from repro.launch.mesh import make_mesh

    g, _ = _window_tel()
    mesh = make_mesh((2, 2), ("data", "model"), devices=topo.devices)
    plan = ShardPlan.build(g, 2, vertex_capacity=V_SMOKE)
    edges = NamedSharding(mesh, PS("model", None))
    lane = _sds((64,), jnp.int32, NamedSharding(mesh, PS("data")))
    arrays = [_sds(a.shape, a.dtype, edges)
              for a in (plan.src, plan.dst, plan.t, plan.pair_local,
                        plan.hp_src, plan.hp_pair)]
    jitted = _sharded_step_jit(mesh, plan.num_vertices, plan.p_cap,
                               combine, False)
    compiled = jitted.lower(
        *arrays,
        _sds((64, plan.num_vertices), jnp.bool_,
             NamedSharding(mesh, PS("data", None))),
        lane, lane, lane, lane).compile()
    hlo = compiled.as_text()
    want = "all-reduce" if combine == "psum" else "reduce-scatter"
    assert want in hlo


def test_sharded_kernel_step_compiles_on_4x1(topo):
    from repro.core.distributed import make_sharded_kernel_step
    from repro.launch.mesh import make_mesh

    _, tel = _window_tel()
    mesh = make_mesh((4, 1), ("data", "model"), devices=topo.devices)
    step = make_sharded_kernel_step(mesh, tel, V_SMOKE, interpret=False)
    assert step is not None and step.backend == "pallas"
    lane = _sds((64,), jnp.int32, NamedSharding(mesh, PS("data")))
    compiled = step.jitted.lower(
        _sds((64, V_SMOKE), jnp.bool_,
             NamedSharding(mesh, PS("data", None))),
        lane, lane, lane, lane).compile()
    assert "tpu_custom_call" in compiled.as_text()
