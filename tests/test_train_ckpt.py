"""Trainer fault tolerance, checkpoint ECC, determinism, grad compression."""

import os
import tempfile

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import AxisType

from repro.checkpoint import manager as ckpt
from repro.data.pipeline import DataConfig, TokenPipeline
from repro.optim.adamw import AdamWConfig
from repro.train.train_step import TrainConfig
from repro.train.trainer import FaultInjected, Trainer
from tests.conftest import tiny_cfg

CFG = tiny_cfg(vocab=64)
DC = DataConfig(vocab=64, global_batch=8, seq_len=32)
TC = TrainConfig(optimizer=AdamWConfig(lr=1e-3, warmup_steps=5, total_steps=100), remat=None)


def test_loss_decreases_and_resume_is_deterministic():
    with tempfile.TemporaryDirectory() as d:
        tr = Trainer(CFG, TC, TokenPipeline(DC), d, ckpt_every=5)
        h = tr.run(12)
        losses = [r["loss"] for r in h if "loss" in r]
        assert losses[-1] < losses[0]
        tr2 = Trainer(CFG, TC, TokenPipeline(DC), d, ckpt_every=5)
        assert tr2.restore() and tr2.step == 10
        h2 = tr2.run(2)
        l2 = [r["loss"] for r in h2 if "loss" in r]
        np.testing.assert_allclose(losses[-2:], l2, rtol=1e-5)


def test_fault_recovery_restores_and_continues():
    with tempfile.TemporaryDirectory() as d:
        armed = {"on": True}

        def chaos(step):
            if step == 7 and armed["on"]:
                armed["on"] = False
                raise FaultInjected("boom")

        tr = Trainer(CFG, TC, TokenPipeline(DC), d, ckpt_every=5, fault_hook=chaos)
        tr.run(10)
        assert tr.recoveries == 1
        assert tr.step == 10
        events = [r for r in tr.history if r.get("event") == "recovery"]
        assert len(events) == 1 and events[0]["step"] == 5  # restored to ckpt 5


def test_straggler_monitor():
    from repro.train.trainer import StragglerMonitor

    mon = StragglerMonitor(factor=3.0, warmup=3)
    for i in range(6):
        mon.observe(i, 0.1)
    assert not mon.events
    assert mon.observe(6, 1.0)  # 10x median
    assert mon.events[0].step == 6


def test_checkpoint_ecc_corrects_single_bit_corruption():
    with tempfile.TemporaryDirectory() as d:
        tree = {"w": np.arange(4096, dtype=np.float32).reshape(64, 64)}
        ckpt.save(d, 1, tree, ecc_protect=True)
        # flip one bit in the stored leaf
        path = os.path.join(d, "step_000001", "leaf_00000.npy")
        raw = bytearray(open(path, "rb").read())
        raw[-100] ^= 0x04
        open(path, "wb").write(bytes(raw))
        out = ckpt.load(d, 1, tree)
        np.testing.assert_array_equal(np.asarray(out["w"]), tree["w"])  # corrected


def test_checkpoint_ecc_detects_multi_bit_and_falls_back():
    with tempfile.TemporaryDirectory() as d:
        tree = {"w": np.arange(1024, dtype=np.float32)}
        ckpt.save(d, 1, tree, ecc_protect=True)
        tree2 = {"w": np.arange(1024, dtype=np.float32) * 2}
        ckpt.save(d, 2, tree2, ecc_protect=True)
        # corrupt 2 bits in one 64-bit word of step 2
        path = os.path.join(d, "step_000002", "leaf_00000.npy")
        raw = bytearray(open(path, "rb").read())
        raw[-8] ^= 0x03
        open(path, "wb").write(bytes(raw))
        with pytest.raises(ckpt.CheckpointCorruption):
            ckpt.load(d, 2, tree)
        # trainer restore() falls back to step 1
        tr = Trainer(CFG, TC, TokenPipeline(DC), d, ckpt_every=5)
        # build matching checkpoints for trainer state
        ckpt.save(d, 3, tr._state(), ecc_protect=True)
        assert tr.restore()


def test_checkpoint_reshard_on_load():
    with tempfile.TemporaryDirectory() as d:
        tree = {"w": np.arange(64, dtype=np.float32).reshape(8, 8)}
        ckpt.save(d, 1, tree)
        mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
        from jax.sharding import NamedSharding, PartitionSpec as P

        shard = {"w": NamedSharding(mesh, P("data"))}
        out = ckpt.load(d, 1, tree, shardings=shard)
        np.testing.assert_array_equal(np.asarray(out["w"]), tree["w"])
        assert out["w"].sharding == shard["w"]


def test_elastic_rescale_keeps_state():
    with tempfile.TemporaryDirectory() as d:
        tr = Trainer(CFG, TC, TokenPipeline(DC), d, ckpt_every=100)
        tr.run(3)
        l3 = tr.history[-1]["loss"]
        mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
        tr.rescale(mesh)  # re-place on a "new" mesh
        h = tr.run(1)
        assert np.isfinite(h[-1]["loss"]) and h[-1]["loss"] < l3 + 1.0


def test_compressed_dp_step_matches_uncompressed():
    from repro.distributed.collectives import (
        init_error_feedback,
        make_dp_compressed_train_step,
    )
    from repro.models import lm as lm_mod

    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    params = lm_mod.init_params(CFG, jax.random.PRNGKey(0))
    from repro.optim import adamw

    opt = adamw.init(params, TC.optimizer)
    ef = init_error_feedback(params)
    batch = {k: jnp.asarray(v) for k, v in TokenPipeline(DC).batch_at(0).items()}

    step_c = make_dp_compressed_train_step(CFG, TC, mesh, compress=True)
    step_u = make_dp_compressed_train_step(CFG, TC, mesh, compress=False)
    p1, _, ef1, loss_c = step_c(params, opt, ef, batch)
    p2, _, _, loss_u = step_u(params, opt, ef, batch)
    assert float(loss_c) == pytest.approx(float(loss_u), rel=1e-5)
    # int8 compression: params close but not identical; error feedback non-zero
    d = max(
        float(jnp.max(jnp.abs(a - b)))
        for a, b in zip(jax.tree_util.tree_leaves(p1), jax.tree_util.tree_leaves(p2))
    )
    assert d < 5e-3
    assert any(float(jnp.max(jnp.abs(l))) > 0 for l in jax.tree_util.tree_leaves(ef1))
