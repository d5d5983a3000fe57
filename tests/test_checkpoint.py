"""Checkpoint save/restore, atomicity/GC, resharding, fault tolerance."""


import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.train import checkpoint, fault


@pytest.fixture
def state():
    return {"params": {"w": jnp.arange(12.0).reshape(3, 4),
                       "layers": {"wq": jnp.ones((2, 4, 4))}},
            "opt": {"count": jnp.asarray(7, jnp.int32)}}


def test_save_restore_roundtrip(tmp_path, state):
    d = str(tmp_path)
    checkpoint.save(d, 5, state, extra={"note": "x"})
    restored, step, extra = checkpoint.restore(d, state)
    assert step == 5 and extra == {"note": "x"}
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_gc_keeps_last_n(tmp_path, state):
    d = str(tmp_path)
    for s in range(6):
        checkpoint.save(d, s, state, keep=3)
    assert checkpoint.latest_steps(d) == [3, 4, 5]


def test_restore_latest_by_default(tmp_path, state):
    d = str(tmp_path)
    for s in (1, 9, 4):
        checkpoint.save(d, s, state)
    _, step, _ = checkpoint.restore(d, state)
    assert step == 9


def test_restore_missing_array_fails(tmp_path, state):
    d = str(tmp_path)
    checkpoint.save(d, 0, {"params": state["params"]})
    with pytest.raises(ValueError):
        checkpoint.restore(d, state)


def test_restore_with_shardings_replaces_devices(tmp_path, state):
    """Elastic restore: same checkpoint re-placed under a (new) mesh."""
    d = str(tmp_path)
    checkpoint.save(d, 2, state)
    mesh = jax.make_mesh((1,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    sh = jax.tree.map(
        lambda _: jax.NamedSharding(mesh, jax.sharding.PartitionSpec()),
        state)
    restored, step, _ = checkpoint.restore(d, state, shardings=sh)
    assert step == 2
    for leaf in jax.tree.leaves(restored):
        assert isinstance(leaf, jax.Array)


class TestFault:
    def test_preemption_flag(self):
        h = fault.PreemptionHandler()
        assert not h.should_checkpoint_and_exit
        h.request()
        assert h.should_checkpoint_and_exit

    def test_watchdog_flags_stragglers(self, monkeypatch):
        w = fault.StragglerWatchdog(alpha=0.5, threshold=2.0)
        times = iter([0.0, 1.0, 1.0, 2.0, 2.0, 3.0, 3.0, 13.0])
        monkeypatch.setattr(fault.time, "monotonic", lambda: next(times))
        for step in range(4):
            w.start()
            w.stop(step)
        assert len(w.flagged) == 1
        assert w.flagged[0][0] == 3
        assert "re-dispatch" in w.mitigation_plan()

    def test_failure_injection_env(self, monkeypatch):
        monkeypatch.setenv("REPRO_FAIL_AT_STEP", "7")
        assert fault.should_inject_failure(7)
        assert not fault.should_inject_failure(6)


def test_train_restart_resumes_identically(tmp_path):
    """Train 6 steps with a checkpoint at 3; crash; resume; the final state
    equals an uninterrupted 6-step run (deterministic data by step)."""
    from repro.configs import get_config
    from repro.data import pipeline
    from repro.train import trainer

    cfg = get_config("qwen2-1.5b").reduced()
    dcfg = pipeline.DataConfig(vocab=cfg.vocab, seq_len=32, global_batch=2)

    def data():
        return ({"tokens": t, "labels": l} for _, (t, l)
                in pipeline.batches(dcfg))

    def data_iter():
        return ((s, {"tokens": t, "labels": l})
                for s, (t, l) in pipeline.batches(dcfg))

    tc = trainer.TrainConfig(steps=6, ckpt_every=3, log_every=100,
                             ckpt_dir=str(tmp_path / "a"), remat="none")
    state_a, hist_a = trainer.run(cfg, tc, data_iter(),
                                  key=jax.random.PRNGKey(1))

    # interrupted run: 3 steps, then a fresh process resumes from ckpt
    tc_b1 = trainer.TrainConfig(steps=3, ckpt_every=3, log_every=100,
                                ckpt_dir=str(tmp_path / "b"), remat="none")
    trainer.run(cfg, tc_b1, data_iter(), key=jax.random.PRNGKey(1))
    tc_b2 = trainer.TrainConfig(steps=6, ckpt_every=3, log_every=100,
                                ckpt_dir=str(tmp_path / "b"), remat="none")
    state_b, hist_b = trainer.run(cfg, tc_b2, data_iter(),
                                  key=jax.random.PRNGKey(1))
    la = [h["loss"] for h in hist_a if h["step"] >= 3]
    lb = [h["loss"] for h in hist_b]
    np.testing.assert_allclose(la, lb, rtol=1e-5)
    for a, b in zip(jax.tree.leaves(state_a["params"]),
                    jax.tree.leaves(state_b["params"])):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), rtol=1e-5,
                                   atol=1e-6)
