"""Fault-tolerance module + deterministic data pipeline."""
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from hypothesis import given, settings, strategies as st

from repro.data.pipeline import DataConfig, TokenPipeline
from repro.ft.checkpoint import CheckpointManager, ovh_checkpoint_period


def test_checkpoint_roundtrip(tmp_path):
    state = {"a": jnp.arange(6.0).reshape(2, 3),
             "b": {"c": jnp.ones((4,), jnp.int32)},
             "step": jnp.asarray(7)}
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(7, state, extra={"note": "x"})
    step, restored, extra = mgr.restore(state)
    assert step == 7 and extra["note"] == "x"
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_gc_keeps_latest(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    state = {"x": jnp.zeros(3)}
    for s in (1, 2, 3, 4):
        mgr.save(s, state)
    assert mgr.latest_step() == 4
    files = [f for f in os.listdir(tmp_path) if f.endswith(".npz")]
    assert len(files) == 2


def test_torn_write_never_restored(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    state = {"x": jnp.zeros(3)}
    mgr.save(1, state)
    # a crash mid-write leaves a temp file; manifest still points to step 1
    with open(os.path.join(tmp_path, "ckpt_00000002.tmp.npz"), "wb") as f:
        f.write(b"garbage")
    assert mgr.latest_step() == 1
    step, _, _ = mgr.restore(state)
    assert step == 1


@given(step_time=st.floats(0.01, 10.0), ovh=st.floats(0.01, 0.5))
@settings(max_examples=50, deadline=None)
def test_ovh_period_bounds_overhead(step_time, ovh):
    """Checkpoint cadence honours the paper's ovh budget."""
    ckpt = 0.5
    period = ovh_checkpoint_period(step_time, ckpt, ovh)
    assert period >= 1
    # overhead fraction with this period stays within ~budget
    assert ckpt / (period * step_time) <= ovh * 1.5 + 1e-9


def test_pipeline_deterministic_and_restartable():
    cfg = DataConfig(vocab=512, batch=4, seq_len=32, seed=5)
    p1 = TokenPipeline(cfg)
    p2 = TokenPipeline(cfg)
    b1 = p1.batch(17)
    # restart: a fresh pipeline produces the identical step-17 batch
    b2 = p2.batch(17)
    for k in b1:
        np.testing.assert_array_equal(b1[k], b2[k])
    assert not np.array_equal(p1.batch(18)["tokens"], b1["tokens"])


def test_pipeline_embeds_mode():
    cfg = DataConfig(vocab=512, batch=2, seq_len=8, seed=0, embed_dim=16)
    b = TokenPipeline(cfg).batch(0)
    assert "embeds" in b and b["embeds"].shape == (2, 8, 16)
    assert b["labels"].shape == (2, 8)


def test_trace_executor_end_to_end(tmp_path):
    """Scheduler trace -> real training with checkpoint/restore parity."""
    from repro.cluster.runtime import TraceExecutor, TrainTaskPayload
    from repro.configs import get_config
    from repro.models.model import init_params
    from repro.train.optimizer import AdamWConfig, adamw_init
    from repro.train.steps import make_train_step

    cfg = get_config("stablelm-1.6b", tiny=True)
    pipe = TokenPipeline(DataConfig(vocab=cfg.vocab, batch=2, seq_len=16))
    # warmup sized to the 24-step run: the default 100-step ramp keeps lr
    # so small that inter-batch noise swamps the descent this test asserts
    step_fn = jax.jit(make_train_step(cfg, AdamWConfig(warmup_steps=5)))

    def make_state():
        params = init_params(cfg, jax.random.PRNGKey(0))
        return {"params": params, "opt": adamw_init(params)}

    payload = TrainTaskPayload(
        name="t0", total_steps=24, make_state=make_state,
        train_step=step_fn, batch_fn=pipe.batch,
        ckpt_dir=str(tmp_path / "t0"))
    # synthetic trace: dispatch, preempt at 50% (checkpointed), re-dispatch
    records = [
        {"t": 0.0, "ev": "dispatch", "tid": 0, "vm": "a", "mode": "full",
         "from_base": 0.0},
        {"t": 50.0, "ev": "preempt", "tid": 0, "vm": "a", "to_base": 50.0},
        {"t": 60.0, "ev": "dispatch", "tid": 0, "vm": "b", "mode": "full",
         "from_base": 50.0},
        {"t": 120.0, "ev": "complete", "tid": 0, "vm": "b"},
    ]
    ex = TraceExecutor(records, {0: payload}, {0: 100.0})
    out = ex.run()
    assert out[0]["steps"] == 24
    assert out[0]["final_loss"] < out[0]["first_loss"]
    assert payload.manager.latest_step() == 24
