"""Trainer substrate: loss goes down, checkpoint/resume is exact, crash
injection recovers, grad compression error feedback behaves."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_smoke_config
from repro.data.pipeline import CorpusConfig, TokenPipeline
from repro.train.checkpoint import CheckpointManager
from repro.train.loop import LoopConfig, run_training
from repro.train.optimizer import AdamWConfig, adamw_update, init_opt_state
from repro.train.trainer import TrainState, init_train_state, make_train_step


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("olmo-1b")
    opt = AdamWConfig(lr_peak=3e-3, warmup_steps=5, total_steps=60,
                      moment_dtype=jnp.float32)
    key = jax.random.PRNGKey(0)
    state = init_train_state(cfg, key, opt)
    step = jax.jit(make_train_step(cfg, opt, n_micro=2))
    tp = TokenPipeline(CorpusConfig(n_docs=64, mean_len=64, vocab=cfg.vocab,
                                    seed=1), seq_len=32)
    return cfg, opt, state, step, tp


def _batches(tp, bs):
    """Step-indexed batch function (pure in step — resumable)."""
    def fn(step):
        b = tp.batch_at(step, bs)
        return {"tokens": jnp.asarray(b["tokens"]), "labels": jnp.asarray(b["labels"])}
    return fn


def test_loss_decreases(setup):
    cfg, opt, state, step, tp = setup
    fn = _batches(tp, 4)
    losses = []
    for i in range(30):
        state, m = step(state, fn(i))
        losses.append(float(m["loss"]))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.1, losses


def test_checkpoint_roundtrip(tmp_path, setup):
    cfg, opt, state, step, tp = setup
    ckpt = CheckpointManager(str(tmp_path / "ck"), keep=2)
    ckpt.save(7, state, blocking=True)
    assert ckpt.latest_step() == 7
    restored = ckpt.restore(7, like=state)
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_retention_and_gc(tmp_path, setup):
    cfg, opt, state, step, tp = setup
    ckpt = CheckpointManager(str(tmp_path / "ck2"), keep=2)
    for s in (1, 2, 3, 4):
        ckpt.save(s, state, blocking=True)
    assert ckpt.all_steps() == [3, 4]
    # stale tmp dirs are collected on next manager construction
    os.makedirs(str(tmp_path / "ck2" / "step_000000099.tmp"))
    CheckpointManager(str(tmp_path / "ck2"), keep=2)
    assert not os.path.exists(str(tmp_path / "ck2" / "step_000000099.tmp"))


def test_crash_and_resume_exact(tmp_path, setup):
    cfg, opt, state0, step, tp = setup
    loop_dir = str(tmp_path / "loop")

    # uninterrupted reference run
    ck_a = CheckpointManager(loop_dir + "_a", keep=5)
    out_a = run_training(step, state0, _batches(tp, 4), ck_a,
                         LoopConfig(total_steps=12, ckpt_every=4),
                         log=lambda s: None)

    # crash at step 9, then resume from the step-8 checkpoint
    ck_b = CheckpointManager(loop_dir + "_b", keep=5)
    with pytest.raises(RuntimeError, match="injected failure"):
        run_training(step, state0, _batches(tp, 4), ck_b,
                     LoopConfig(total_steps=12, ckpt_every=4, fail_at_step=9),
                     log=lambda s: None)
    out_b = run_training(step, state0, _batches(tp, 4), ck_b,
                         LoopConfig(total_steps=12, ckpt_every=4),
                         log=lambda s: None)
    assert out_b["resumed_from"] == 8
    for a, b in zip(jax.tree.leaves(out_a["final_state"].params),
                    jax.tree.leaves(out_b["final_state"].params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_elastic_restore_new_sharding(tmp_path, setup):
    """A checkpoint restores onto a different mesh/sharding (elastic)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.mesh import make_mesh
    cfg, opt, state, step, tp = setup
    ckpt = CheckpointManager(str(tmp_path / "ck3"), keep=1)
    ckpt.save(1, state.params, blocking=True)
    mesh = make_mesh((1,), ("data",))
    shardings = jax.tree.map(lambda _: NamedSharding(mesh, P()), state.params)
    restored = ckpt.restore(1, like=state.params, shardings=shardings)
    leaf = jax.tree.leaves(restored)[0]
    assert isinstance(leaf.sharding, NamedSharding)


def test_grad_compression_error_feedback():
    params = {"w": jnp.zeros((4, 4), jnp.float32)}
    opt = AdamWConfig(grad_compress_bits=8, clip_norm=1e9, weight_decay=0.0,
                      lr_peak=1.0, warmup_steps=0, total_steps=1,
                      moment_dtype=jnp.float32)
    state = init_opt_state(params, opt)
    assert state.err is not None
    g = {"w": jnp.full((4, 4), 0.333e-3, jnp.float32)
         + jnp.arange(16, dtype=jnp.float32).reshape(4, 4) * 1e-6}
    _, state2, _ = adamw_update(params, g, state, opt)
    # residual is bounded by one quantization bucket
    scale = float(jnp.max(jnp.abs(g["w"]))) / 127.0
    assert float(jnp.abs(state2.err["w"]).max()) <= scale * 0.5 + 1e-12
    # and is carried (nonzero somewhere, because values straddle buckets)
    assert float(jnp.abs(state2.err["w"]).max()) > 0


def test_straggler_detection(tmp_path, setup):
    import time
    cfg, opt, state, step, tp = setup
    calls = {"n": 0}

    def slow_step(s, b):
        calls["n"] += 1
        if calls["n"] == 9:
            time.sleep(0.25)
        return step(s, b)

    ck = CheckpointManager(str(tmp_path / "ck4"), keep=1)
    out = run_training(slow_step, state, _batches(tp, 4), ck,
                       LoopConfig(total_steps=10, ckpt_every=100,
                                  straggler_factor=2.5),
                       log=lambda s: None)
    assert out["stragglers"] >= 1
