"""Substrate tests: optimizers, compression, checkpointing, fault-tolerant
trainer (failure injection -> restart-exact resume), straggler watchdog,
elastic resharding, data-pipeline determinism."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.data import SyntheticLMData
from repro.optim import (Adafactor, AdamW, compressed_psum_exact,
                         dequantize_int8, quantize_int8)


# --------------------------------------------------------------- optimizers
@pytest.mark.parametrize("opt", [AdamW(lr=0.1), Adafactor(lr=0.5)])
def test_optimizer_descends_quadratic(opt):
    params = {"w": jnp.asarray([3.0, -2.0, 5.0]),
              "m": jnp.ones((4, 6)) * 2.0}
    state = opt.init(params)

    def loss(p):
        return jnp.sum(p["w"] ** 2) + jnp.sum(p["m"] ** 2)

    l0 = loss(params)
    for _ in range(60):
        g = jax.grad(loss)(params)
        upd, state = opt.update(g, state, params)
        params = jax.tree.map(lambda p, u: p + u, params, upd)
    assert float(loss(params)) < 0.05 * float(l0)


def test_opt_state_pspecs_match_structure():
    from jax.sharding import PartitionSpec as PS

    pspecs = {"w": PS("data", "model"), "b": PS(None)}
    adam = AdamW()
    st = adam.state_pspecs(pspecs)
    assert st["m"]["w"] == PS("data", "model")
    fac = Adafactor()
    st2 = fac.state_pspecs(pspecs)
    assert st2["f"]["w"]["vr"] == PS("data")
    assert st2["f"]["w"]["vc"] == PS("model")


# -------------------------------------------------------------- compression
def test_quantize_roundtrip_error_bound():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(0, 3, (256, 64)), jnp.float32)
    q, s = quantize_int8(x)
    err = np.abs(np.asarray(dequantize_int8(q, s)) - np.asarray(x)).max()
    assert err <= float(s) * 0.5 + 1e-6


def test_compressed_psum_with_error_feedback():
    """On a 1-device axis the compressed psum must equal the input up to
    quantization error, and error feedback must carry the residual."""
    from jax.sharding import PartitionSpec as PS

    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1,), ("d",))
    x = jnp.asarray(np.random.default_rng(1).normal(0, 1, (64,)),
                    jnp.float32)
    err = jnp.zeros_like(x)
    fn = jax.shard_map(lambda a, e: compressed_psum_exact(a, "d", e),
                       mesh=mesh, in_specs=(PS(), PS()),
                       out_specs=(PS(), PS()), check_vma=False)
    out, new_err = fn(x, err)
    np.testing.assert_allclose(np.asarray(out + new_err), np.asarray(x),
                               rtol=1e-6, atol=1e-6)
    # accumulated mean over steps is unbiased thanks to error feedback
    total = jnp.zeros_like(x)
    e = jnp.zeros_like(x)
    for _ in range(50):
        o, e = fn(x, e)
        total = total + o
    np.testing.assert_allclose(np.asarray(total / 50), np.asarray(x),
                               rtol=0.02, atol=0.02)


# ------------------------------------------------------------- checkpointing
def test_checkpoint_roundtrip_and_prune(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "b": {"c": jnp.asarray(7, jnp.int32)}}
    for step in (1, 2, 3):
        mgr.save(step, tree)
    assert mgr.steps() == [2, 3]  # pruned to keep=2
    out = mgr.restore(tree)
    np.testing.assert_array_equal(np.asarray(out["a"]),
                                  np.asarray(tree["a"]))
    assert out["b"]["c"].dtype == jnp.int32


def test_checkpoint_detects_corruption(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    tree = {"w": jnp.ones((4,))}
    path = mgr.save(1, tree)
    target = os.path.join(path, "w.npy")
    with open(target, "r+b") as f:
        f.seek(-1, 2)
        f.write(b"\x42")
    with pytest.raises(IOError):
        mgr.restore(tree, verify=True)


def test_checkpoint_async(tmp_path):
    mgr = CheckpointManager(str(tmp_path), async_save=True)
    tree = {"w": jnp.ones((128, 128))}
    mgr.save(5, tree)
    mgr.wait()
    assert mgr.latest_step() == 5


def test_checkpoint_tmp_dir_is_not_a_checkpoint(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    os.makedirs(os.path.join(str(tmp_path), "step_000009.tmp"))
    assert mgr.latest_step() is None  # crash-atomic: tmp dirs invisible


# ------------------------------------------------------------ data pipeline
def test_data_restart_exact():
    a = SyntheticLMData(vocab=100, batch=4, seq=8, seed=3)
    b = SyntheticLMData(vocab=100, batch=4, seq=8, seed=3)
    for step in (0, 7, 123):
        x, y = a.batch_at(step), b.batch_at(step)
        np.testing.assert_array_equal(x["tokens"], y["tokens"])
    assert not np.array_equal(a.batch_at(1)["tokens"],
                              a.batch_at(2)["tokens"])


def test_data_host_sharding():
    full = SyntheticLMData(vocab=50, batch=8, seq=4, seed=1)
    h0 = SyntheticLMData(vocab=50, batch=8, seq=4, seed=1, host_id=0,
                         n_hosts=2)
    assert h0.batch_at(0)["tokens"].shape[0] == 4
    assert full.batch_at(0)["tokens"].shape[0] == 8


# ------------------------------------------------- fault-tolerant training
def _tiny_trainer(tmp_path, injector=None, steps=8):
    from repro.configs import get_smoke_config
    from repro.launch.mesh import make_host_mesh
    from repro.runtime import Trainer, TrainerConfig

    cfg = get_smoke_config("qwen2-7b").scaled(n_layers=2)
    mesh = make_host_mesh()
    data = SyntheticLMData(vocab=cfg.vocab, batch=4, seq=16, seed=0)
    tcfg = TrainerConfig(steps=steps, ckpt_every=3,
                         ckpt_dir=str(tmp_path), lr=1e-3)
    return Trainer(cfg, mesh, data, tcfg, injector=injector)


def test_trainer_runs_and_checkpoints(tmp_path):
    tr = _tiny_trainer(tmp_path)
    out = tr.run()
    assert out["steps_run"] == 8
    assert np.isfinite(out["final_loss"])
    assert tr.ckpt.latest_step() == 8


def test_trainer_survives_injected_failure(tmp_path):
    from repro.runtime import FaultInjector

    tr = _tiny_trainer(tmp_path, FaultInjector(fail_at={5: "node loss"}))
    out = tr.run()
    assert out["restarts"] == 1
    # restart-exact: steps 3..4 replayed after restoring the step-3 ckpt
    steps_seen = [m["step"] for m in tr.metrics]
    assert steps_seen.count(4) == 2 and steps_seen[-1] == 7


def test_trainer_restart_budget(tmp_path):
    from repro.runtime import FaultInjector, InjectedFault

    inj = FaultInjector(fail_at={2: "a"})
    inj._fired = set()  # re-fire forever

    class Always(FaultInjector):
        def check(self, step):
            if step == 2:
                raise InjectedFault("flaky node")

    tr = _tiny_trainer(tmp_path, Always(), steps=4)
    with pytest.raises(RuntimeError, match="restart budget"):
        tr.run()


def test_straggler_watchdog(tmp_path):
    from repro.runtime import FaultInjector

    tr = _tiny_trainer(tmp_path, FaultInjector(delay_at={6: 1.5}))
    tr.run()
    assert tr.straggler_flags >= 1


def test_elastic_restore_across_meshes(tmp_path):
    """Save under one mesh, restore under another (reshard-on-restore)."""
    from jax.sharding import NamedSharding, PartitionSpec as PS

    from repro.launch.mesh import make_mesh

    mesh1 = make_mesh((1, 1), ("data", "model"))
    tree = {"w": jax.device_put(
        jnp.arange(64, dtype=jnp.float32).reshape(8, 8),
        NamedSharding(mesh1, PS("data", "model")))}
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, tree)
    mesh2 = make_mesh((1,), ("data",))
    sh = {"w": NamedSharding(mesh2, PS("data", None))}
    out = mgr.restore(tree, shardings=sh)
    np.testing.assert_array_equal(np.asarray(out["w"]),
                                  np.asarray(tree["w"]))
    assert out["w"].sharding == sh["w"]
