"""Fault-tolerance drills: checkpoint/restart, NaN rollback, transient
retry, straggler detection, elastic re-mesh (restore onto a different
sharding)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.models import Model
from repro.train import optimizer as opt
from repro.train.checkpoint import CheckpointManager
from repro.train.supervisor import (
    SupervisorConfig,
    TrainSupervisor,
    _InjectedFault,
)


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("granite-3-8b").reduced()
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    ocfg = opt.OptimizerConfig(warmup_steps=2, total_steps=50)
    opt_state = opt.init(ocfg, params)

    def step_fn(p, s, batch):
        def loss_fn(pp):
            return model.loss(pp, batch["tokens"], batch["labels"], remat=False)

        loss, grads = jax.value_and_grad(loss_fn)(p)
        new_p, new_s, m = opt.update(ocfg, grads, s, p)
        return new_p, new_s, dict(m, loss=loss)

    def batch(i):
        rng = np.random.default_rng(i)
        t = rng.integers(0, cfg.vocab_size, (2, 16)).astype(np.int32)
        return {"tokens": jnp.asarray(t), "labels": jnp.asarray(t)}

    return cfg, model, params, opt_state, step_fn, batch


class TestCheckpoint:
    def test_roundtrip(self, setup, tmp_path):
        _, _, params, opt_state, _, _ = setup
        mgr = CheckpointManager(tmp_path, async_save=False)
        mgr.save(7, {"params": params, "opt_state": opt_state})
        assert mgr.latest_step() == 7
        step, restored = mgr.restore({"params": params, "opt_state": opt_state})
        assert step == 7
        for a, b in zip(jax.tree.leaves(restored["params"]), jax.tree.leaves(params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_atomic_publish_and_gc(self, setup, tmp_path):
        _, _, params, _, _, _ = setup
        mgr = CheckpointManager(tmp_path, keep=2, async_save=False)
        for s in (1, 2, 3, 4):
            mgr.save(s, {"p": params["final_norm"]})
        steps = sorted(p.name for p in tmp_path.glob("step_*"))
        assert len(steps) == 2 and steps[-1].endswith("4".zfill(10))
        assert mgr.latest_step() == 4

    def test_corruption_detected(self, setup, tmp_path):
        _, _, params, _, _, _ = setup
        mgr = CheckpointManager(tmp_path, async_save=False)
        mgr.save(1, {"p": params["final_norm"]})
        # leaves are .bin.zst with zstandard installed, plain .bin without
        victim = next((tmp_path / "step_0000000001").glob("leaf_*.bin*"))
        blob = bytearray(victim.read_bytes())
        # corrupt the compressed payload so decompress-or-crc fails
        blob[-1] ^= 0xFF
        victim.write_bytes(bytes(blob))
        with pytest.raises(Exception):
            mgr.restore({"p": params["final_norm"]})

    def test_async_save(self, setup, tmp_path):
        _, _, params, _, _, _ = setup
        mgr = CheckpointManager(tmp_path, async_save=True)
        mgr.save(5, {"p": params["final_norm"]})
        mgr.wait()
        assert mgr.latest_step() == 5

    def test_elastic_restore_resharding(self, setup, tmp_path):
        """512-chip checkpoint restores onto a different mesh (here: the
        host mesh) by passing new shardings -- the node-failure path."""
        _, _, params, _, _, _ = setup
        from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

        mesh = jax.make_mesh((1, 1), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
        mgr = CheckpointManager(tmp_path, async_save=False)
        mgr.save(3, {"p": params["final_norm"]})
        shardings = {"p": jax.tree.map(
            lambda _: NamedSharding(mesh, P()), params["final_norm"]
        )}
        step, restored = mgr.restore({"p": params["final_norm"]}, shardings=shardings)
        assert step == 3
        leaf = jax.tree.leaves(restored["p"])[0]
        assert leaf.sharding.mesh.shape == {"data": 1, "model": 1}


class TestSupervisor:
    def test_nan_rollback(self, setup, tmp_path):
        cfg, model, params, opt_state, step_fn, batch = setup
        mgr = CheckpointManager(tmp_path, async_save=False)
        mgr.save(0, {"params": params, "opt_state": opt_state})

        calls = {"n": 0}

        def poisoned_step(p, s, b):
            calls["n"] += 1
            if calls["n"] == 2:
                new_p, new_s, m = step_fn(p, s, b)
                return new_p, new_s, dict(m, loss=jnp.float32(float("nan")))
            return step_fn(p, s, b)

        sup = TrainSupervisor(
            poisoned_step, mgr, SupervisorConfig(checkpoint_every=0)
        )
        p, s, hist = sup.run(
            params, opt_state, iter([batch(i) for i in range(4)]), num_steps=4
        )
        assert any(r.rolled_back for r in hist)
        assert sum(1 for r in hist if not r.rolled_back) == 3

    def test_transient_fault_retry(self, setup, tmp_path):
        cfg, model, params, opt_state, step_fn, batch = setup
        mgr = CheckpointManager(tmp_path, async_save=False)
        fail_at = {2: 1}  # step 2 fails once then succeeds

        def injector(step):
            if fail_at.get(step, 0) > 0:
                fail_at[step] -= 1
                raise _InjectedFault("boom")

        sup = TrainSupervisor(
            step_fn, mgr, SupervisorConfig(checkpoint_every=0), fault_injector=injector
        )
        p, s, hist = sup.run(
            params, opt_state, iter([batch(i) for i in range(4)]), num_steps=4
        )
        assert [r.retried for r in hist] == [0, 0, 1, 0]

    def test_straggler_flagged(self, setup, tmp_path):
        """Deterministic: a fake clock makes step 3 run 10x the EMA."""
        cfg, model, params, opt_state, step_fn, batch = setup
        mgr = CheckpointManager(tmp_path, async_save=False)

        # fake clock: each _one_step calls clock() twice (start, end);
        # step durations: 1s, 1s, 1s, 10s, 1s
        durations = [1.0, 1.0, 1.0, 10.0, 1.0]
        ticks = []
        t = 0.0
        for d in durations:
            ticks.extend([t, t + d])
            t += d
        it = iter(ticks)

        flagged = []
        sup = TrainSupervisor(
            step_fn,
            mgr,
            SupervisorConfig(checkpoint_every=0, straggler_factor=4.0),
            on_straggler=flagged.append,
            clock=lambda: next(it),
        )
        sup.run(params, opt_state, iter([batch(i) for i in range(5)]), num_steps=5)
        assert flagged == [3], flagged

    def test_resume_from_checkpoint(self, setup, tmp_path):
        cfg, model, params, opt_state, step_fn, batch = setup
        mgr = CheckpointManager(tmp_path, async_save=False)
        sup = TrainSupervisor(step_fn, mgr, SupervisorConfig(checkpoint_every=2))
        p, s, _ = sup.run(
            params, opt_state, iter([batch(i) for i in range(4)]), num_steps=4
        )
        # new supervisor (fresh process) resumes from the saved step
        sup2 = TrainSupervisor(step_fn, mgr, SupervisorConfig())
        start, p2, s2 = sup2.resume_or_init(params, opt_state)
        assert start == 4
        assert int(s2.step) == int(s.step)


class TestTrainingProgress:
    def test_loss_decreases(self, setup, tmp_path):
        """End-to-end: a few hundred params steps reduce loss on a fixed batch."""
        cfg, model, params, opt_state, step_fn, batch = setup
        b = batch(0)
        losses = []
        p, s = params, opt_state
        for _ in range(30):
            p, s, m = step_fn(p, s, b)
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0] * 0.9, losses[:3] + losses[-3:]
