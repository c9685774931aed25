"""Substrate tests: optimizer, schedules, checkpoint, data determinism."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.checkpoint import (CheckpointManager, latest_step,
                              load_checkpoint, save_checkpoint)
from repro.data.synthetic import frames_batch, lm_batch, vision_batch
from repro.optim import (OptimizerConfig, adamw_init, adamw_update,
                         cosine_schedule, linear_warmup_cosine,
                         make_optimizer)


# ------------------------------------------------------------- optimizer
def _quad_problem():
    target = jnp.asarray([1.5, -2.0, 0.5])
    params = {"w": jnp.zeros(3)}

    def loss(p):
        return jnp.sum(jnp.square(p["w"] - target))

    return params, loss, target


def test_adamw_converges():
    params, loss, target = _quad_problem()
    state = adamw_init(params)
    for step in range(1, 300):
        g = jax.grad(loss)(params)
        params, state = adamw_update(params, state, g, jnp.int32(step),
                                     lr=5e-2, weight_decay=0.0)
    np.testing.assert_allclose(np.asarray(params["w"]), np.asarray(target),
                               atol=1e-2)


@pytest.mark.parametrize("moment_dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("master", [False, True])
def test_full_optimizer_converges(moment_dtype, master):
    params, loss, target = _quad_problem()
    if master:
        params = jax.tree.map(lambda p: p.astype(jnp.bfloat16), params)
    cfg = OptimizerConfig(lr=5e-2, weight_decay=0.0,
                          moment_dtype=moment_dtype, master_weights=master,
                          clip_norm=10.0)
    init_fn, update_fn = make_optimizer(cfg)
    state = init_fn(params)
    for step in range(1, 400):
        g = jax.grad(lambda p: loss(jax.tree.map(
            lambda x: x.astype(jnp.float32), p)))(params)
        params, state, metrics = update_fn(params, state, g,
                                           jnp.int32(step))
    got = np.asarray(params["w"], np.float32)
    np.testing.assert_allclose(got, np.asarray(target), atol=5e-2)
    assert np.isfinite(float(metrics["grad_norm"]))


def test_clipping_bounds_update():
    cfg = OptimizerConfig(lr=1.0, clip_norm=1e-3, weight_decay=0.0)
    init_fn, update_fn = make_optimizer(cfg)
    params = {"w": jnp.zeros(4)}
    state = init_fn(params)
    g = {"w": jnp.full(4, 1e6)}
    p2, _, m = update_fn(params, state, g, jnp.int32(1))
    assert float(m["grad_norm"]) > 1e5
    assert float(jnp.max(jnp.abs(p2["w"]))) < 2.0  # adam step bounded


def test_schedules():
    s = linear_warmup_cosine(1.0, 10, 100)
    assert float(s(jnp.float32(0))) == 0.0
    assert abs(float(s(jnp.float32(10))) - 1.0) < 1e-6
    assert float(s(jnp.float32(100))) < 0.2
    c = cosine_schedule(1.0, 100)
    assert float(c(jnp.float32(0))) == 1.0


# ------------------------------------------------------------ checkpoint
def _tree(seed=0):
    k = jax.random.PRNGKey(seed)
    return {"a": jax.random.normal(k, (4, 8)),
            "b": {"c": jnp.arange(7, dtype=jnp.int32),
                  "d": jax.random.normal(k, (3,)).astype(jnp.bfloat16)}}


def test_checkpoint_roundtrip(tmp_path):
    tree = _tree()
    save_checkpoint(str(tmp_path), 7, tree, extra={"note": "x"})
    out, step, extra = load_checkpoint(str(tmp_path), tree)
    assert step == 7 and extra["note"] == "x"
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_checkpoint_detects_corruption(tmp_path):
    tree = _tree()
    path = save_checkpoint(str(tmp_path), 1, tree)
    victim = os.path.join(path, "leaf_00000.npy")
    arr = np.load(victim)
    arr.ravel()[0] += 1.0
    np.save(victim, arr)
    with pytest.raises(IOError, match="checksum"):
        load_checkpoint(str(tmp_path), tree)


def test_manager_keep_k_and_resume(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2, every=1)
    tree = _tree()
    for s in range(1, 6):
        mgr.maybe_save(s, tree)
    steps = sorted(int(n.split("_")[1]) for n in os.listdir(tmp_path)
                   if n.startswith("step_"))
    assert steps == [4, 5]
    out = mgr.restore_latest(tree)
    assert out is not None and out[1] == 5


def test_async_save(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2, every=1, async_save=True)
    mgr.maybe_save(3, _tree())
    mgr.wait()
    assert latest_step(str(tmp_path)) == 3


def test_async_save_failure_surfaces(tmp_path):
    """Regression: a failing async save used to die silently on its
    daemon thread — the train loop believed the checkpoint existed.  The
    worker's exception must re-raise on ``wait()`` (or the next
    ``maybe_save``), once, and the manager must stay usable after."""
    blocker = tmp_path / "ckpt"
    blocker.write_text("a file where the checkpoint dir should go")
    mgr = CheckpointManager(str(blocker), keep=2, every=1, async_save=True)
    mgr.maybe_save(1, _tree())
    with pytest.raises(OSError):
        mgr.wait()
    mgr.wait()  # the error was consumed, not raised forever
    # the NEXT maybe_save also surfaces a pending failure (a loop that
    # never calls wait() between saves still finds out)
    mgr.maybe_save(2, _tree())
    with pytest.raises(OSError):
        mgr.maybe_save(3, _tree())
    blocker.unlink()
    mgr.maybe_save(4, _tree())
    mgr.wait()
    assert latest_step(str(blocker)) == 4


def test_atomicity_no_partial_dirs(tmp_path):
    save_checkpoint(str(tmp_path), 2, _tree())
    assert not any(n.startswith("tmp.") for n in os.listdir(tmp_path))


def test_back_to_back_maybe_save_joins_inflight(tmp_path, monkeypatch):
    """Two ``maybe_save`` calls with the first still on the wire: the
    second must JOIN the in-flight save (one at a time — no overlapping
    writers racing on the same step dir), and both checkpoints land."""
    import threading
    import time

    import repro.checkpoint.manager as M

    release = threading.Event()
    started = threading.Event()
    real, calls = M.save_checkpoint, []

    def slow_save(ckpt_dir, step, tree, extra=None):
        calls.append(step)
        started.set()
        assert release.wait(30), "test deadlock: save never released"
        return real(ckpt_dir, step, tree, extra)

    monkeypatch.setattr(M, "save_checkpoint", slow_save)
    mgr = CheckpointManager(str(tmp_path), keep=3, every=1,
                            async_save=True)
    assert mgr.maybe_save(1, _tree())
    assert started.wait(30)
    t = threading.Thread(
        target=lambda: mgr.maybe_save(2, _tree()), daemon=True)
    t.start()
    time.sleep(0.2)
    assert t.is_alive(), "second maybe_save should block on the join"
    assert calls == [1], "saves must never overlap"
    release.set()
    t.join(30)
    assert not t.is_alive()
    mgr.wait()
    assert calls == [1, 2]
    assert latest_step(str(tmp_path)) == 2


# ------------------------------------------------------------------ data
def test_lm_data_deterministic_and_learnable():
    a = lm_batch(997, 4, 64, seed=1, step=5)
    b = lm_batch(997, 4, 64, seed=1, step=5)
    np.testing.assert_array_equal(a, b)
    c = lm_batch(997, 4, 64, seed=1, step=6)
    assert not np.array_equal(a, c)
    # learnable: next token is a deterministic-ish function of current
    nxt = (5 * a[:, :-1] + 17) % 997
    close = np.abs(a[:, 1:] - nxt) <= 4
    assert close.mean() > 0.95


def test_vision_and_frames_shapes():
    v = vision_batch(16, 3, 32, 8, seed=0, step=0)
    assert v["inputs"].shape == (3, 16, 8 * 8 * 3)
    assert v["labels"].shape == (3,)
    f = frames_batch(24, 31, 2, 16, seed=0, step=0)
    assert f["inputs"].shape == (2, 16, 24)
    assert f["tokens"].shape == (2, 16)


@settings(max_examples=10, deadline=None)
@given(shard=st.integers(0, 7), step=st.integers(0, 100))
def test_data_shard_independence(shard, step):
    """Different shards at the same step never collide (fault-tolerant
    recomputation contract)."""
    a = lm_batch(503, 2, 32, seed=0, step=step, shard=shard)
    b = lm_batch(503, 2, 32, seed=0, step=step, shard=shard + 8)
    assert not np.array_equal(a, b)
    np.testing.assert_array_equal(
        a, lm_batch(503, 2, 32, seed=0, step=step, shard=shard))
