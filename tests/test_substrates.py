"""Substrate tests: checkpointing, fault-tolerant trainer, data pipeline,
straggler detection, serving engine under USF."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt import latest_step, restore_checkpoint, save_checkpoint
from repro.configs.base import get_smoke
from repro.core.policies import SchedCoop
from repro.core.threads import UsfRuntime
from repro.core.topology import Topology
from repro.data.pipeline import SyntheticLMDataset
from repro.train.trainer import StragglerDetector, Trainer, TrainerConfig


def test_checkpoint_roundtrip(tmp_path):
    state = {
        "step": jnp.asarray(7, jnp.int32),
        "params": {"w": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
                   "b": jnp.ones((4,), jnp.bfloat16)},
        "opt": {"m": [jnp.zeros((2,)), jnp.full((3,), 2.5)]},
    }
    save_checkpoint(state, str(tmp_path), 7)
    assert latest_step(str(tmp_path)) == 7
    target = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state
    )
    back = restore_checkpoint(str(tmp_path), 7, target)
    for a, b in zip(jax.tree_util.tree_leaves(state),
                    jax.tree_util.tree_leaves(back)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_checkpoint_keep_last_k(tmp_path):
    state = {"x": jnp.zeros(3)}
    for s in (1, 2, 3, 4, 5):
        save_checkpoint(state, str(tmp_path), s, keep=2)
    steps = sorted(p.name for p in tmp_path.iterdir())
    assert steps == ["step_00000004", "step_00000005"]


def test_data_pipeline_deterministic_and_learnable():
    cfg = get_smoke("smollm_360m")
    ds1 = SyntheticLMDataset(cfg, global_batch=4, seq_len=32, seed=1)
    ds2 = SyntheticLMDataset(cfg, global_batch=4, seq_len=32, seed=1)
    b1, b2 = ds1.batch_at(5), ds2.batch_at(5)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    # different steps differ
    b3 = ds1.batch_at(6)
    assert not np.array_equal(b1["tokens"], b3["tokens"])


def test_straggler_detector():
    det = StragglerDetector(factor=2.0)
    flags = [det.observe(i, 0.1) for i in range(5)]
    assert not any(flags)
    assert det.observe(5, 0.5)  # 5x the EWMA
    assert det.flagged == [5]
    assert not det.observe(6, 0.1)  # recovered


def test_trainer_loss_decreases(tmp_path):
    cfg = get_smoke("smollm_360m")
    t = Trainer(cfg, TrainerConfig(steps=50, global_batch=4, seq_len=64,
                                   ckpt_dir=None, peak_lr=1e-2, warmup=5,
                                   log_every=100))
    t.run(resume=False)
    losses = [m["loss"] for m in t.metrics_log]
    assert all(np.isfinite(losses))
    # structured bigram stream: CE must fall well below the ~6.0 start
    assert np.mean(losses[-5:]) < 4.0


def test_trainer_crash_restart_is_deterministic(tmp_path):
    """Fault tolerance: crash after 10 steps, resume from checkpoint,
    final state equals the uninterrupted run (deterministic data + step)."""
    cfg = get_smoke("smollm_360m")

    def mk(ckpt_dir, steps):
        return Trainer(cfg, TrainerConfig(
            steps=steps, global_batch=2, seq_len=32, ckpt_every=5,
            ckpt_dir=ckpt_dir, peak_lr=1e-3, warmup=2, seed=3,
        ))

    # uninterrupted reference
    ref_state = mk(None, 14).run(resume=False)

    # interrupted run: "crash" after step 10 (ckpt at 10), resume to 14
    d = str(tmp_path / "ckpt")
    mk(d, 14).run(resume=False, stop_at=10)
    assert latest_step(d) == 10
    resumed = mk(d, 14).run(resume=True)

    for a, b in zip(jax.tree_util.tree_leaves(ref_state["params"]),
                    jax.tree_util.tree_leaves(resumed["params"])):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-6, atol=1e-7)


def test_serving_engine_under_usf():
    """Two oversubscribed model servers + gateway on a 2-slot runtime:
    all requests complete; USF gates concurrency; blocking points swap."""
    from repro.serve.engine import Gateway, InferenceServer, Request
    from repro.core.task import Job

    usf = UsfRuntime(Topology(2, 1), SchedCoop(quantum=0.05))
    try:
        s1 = InferenceServer("srv-a", get_smoke("smollm_360m"), usf,
                             max_batch=2, max_len=32, nice=10)
        s2 = InferenceServer("srv-b", get_smoke("qwen1_5_110b"), usf,
                             max_batch=2, max_len=32, nice=10)
        s1.start()
        s2.start()
        gw = Gateway(usf, [s1, s2])
        results = []

        def client():
            results.append(gw.handle([5, 6, 7], max_new=3))

        tasks = [usf.create(client, job=gw.job, name=f"client{i}")
                 for i in range(3)]
        for t in tasks:
            assert usf.join(t, timeout=120.0), "client timed out"
        assert len(results) == 3
        assert s1.served == 3 and s2.served == 3
        for r in results:
            assert r["latency"] > 0

        # live policy change without drain (the rescale-driven swap):
        # s1 swaps to a fresh dedicated policy, s2 demotes into the
        # default group — both keep serving without restarting
        lease1 = s1.set_policy(SchedCoop(quantum=0.02), share=2.0)
        assert lease1.group.dedicated and s1.job.lease is lease1
        lease2 = s2.set_policy(None)
        assert not lease2.group.dedicated
        t = usf.create(client, job=gw.job, name="client-post-swap")
        assert usf.join(t, timeout=120.0), "post-swap client timed out"
        assert s1.served == 4 and s2.served == 4

        s1.stop()
        s2.stop()
    finally:
        usf.shutdown(timeout=5.0)


def test_served_tokens_match_full_forward_reference():
    """Greedy tokens served through the KV cache agree with ``LM.forward``
    teacher-forced over prompt + answer, and a wrong token is caught."""
    from repro.serve.engine import Gateway, InferenceServer

    usf = UsfRuntime(Topology(2, 1), SchedCoop(quantum=0.05))
    try:
        srv = InferenceServer("srv", get_smoke("smollm_360m"), usf,
                              max_batch=2, max_len=32)
        srv.start()
        gw = Gateway(usf, [srv])
        prompt = [5, 6, 7, 8, 9]
        out = []
        t = usf.create(lambda: out.append(gw.handle(prompt, max_new=6)),
                       job=gw.job)
        assert usf.join(t, timeout=120.0)
        served = out[0]["outputs"]["srv"]
        assert len(served) == 6
        chk = srv.reference_check(prompt, served, margin=0.0)
        assert chk == {"positions": 6, "checked": 6, "mismatches": 0,
                       "ok": True}
        wrong = list(served)
        wrong[2] = (wrong[2] + 1) % srv.cfg.vocab
        bad = srv.reference_check(prompt, wrong, margin=0.0)
        assert not bad["ok"] and bad["mismatches"] >= 1
        with pytest.raises(ValueError):
            srv.reference_check(prompt, served * 6, margin=0.0)  # > max_len
        srv.stop()
    finally:
        usf.shutdown(timeout=5.0)
