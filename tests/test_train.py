"""Training substrate: optimizer, gradient compression (property-based),
checkpoint/restart (atomicity + elastic restore), the ElasticRunner's
failure/straggler machinery, and loss-goes-down on a tiny model."""
import os
import shutil
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import get_config, smoke_variant
from repro.data.tokens import TokenDatasetConfig, batch_at_step
from repro.models import lm
from repro.train.checkpoint import CheckpointManager
from repro.train.elastic import ElasticConfig, ElasticRunner
from repro.train.grad_compress import (
    dequantize_int8, make_compressed_allreduce, quantize_int8,
)
from repro.train.optimizer import (
    AdamWConfig, adamw_update, init_opt_state, lr_at,
)
from repro.train.train_loop import TrainConfig, make_train_step


# --------------------------------------------------------------------------
# optimizer
# --------------------------------------------------------------------------

def test_lr_schedule_warmup_then_cosine():
    cfg = AdamWConfig(peak_lr=1e-3, warmup_steps=10, total_steps=100)
    lrs = [float(lr_at(cfg, jnp.asarray(s))) for s in range(100)]
    assert lrs[0] < lrs[5] < lrs[9]                  # warming up
    assert abs(lrs[10] - 1e-3) / 1e-3 < 0.05          # peak reached
    assert lrs[99] <= 0.11 * 1e-3                     # decayed to the floor
    assert lrs[99] >= 0.09 * 1e-3                     # min_lr_ratio respected


def test_adamw_moves_towards_minimum():
    params = {"w": jnp.asarray([4.0, -3.0])}
    opt = init_opt_state(params)
    cfg = AdamWConfig(peak_lr=0.1, warmup_steps=1, total_steps=200,
                      weight_decay=0.0)
    for _ in range(150):
        grads = {"w": 2 * params["w"]}               # d/dw (w^2)
        params, opt, _ = adamw_update(cfg, grads, opt, params)
    assert float(jnp.max(jnp.abs(params["w"]))) < 0.5


# --------------------------------------------------------------------------
# gradient compression (int8 + error feedback)
# --------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(st.lists(st.floats(-100, 100, allow_nan=False), min_size=1, max_size=64))
def test_int8_roundtrip_error_bounded(vals):
    x = jnp.asarray(np.asarray(vals, np.float32))
    q, scale = quantize_int8(x)
    err = np.abs(np.asarray(dequantize_int8(q, scale) - x))
    # quantization error bounded by half a step
    assert float(err.max()) <= float(scale) * 0.5 + 1e-5


def test_error_feedback_preserves_signal():
    """With EF, the *accumulated* applied gradient tracks the true sum even
    when single-step quantization is coarse."""
    rng = np.random.default_rng(0)
    g_true = jnp.asarray(rng.normal(size=256).astype(np.float32))
    resid = jnp.zeros_like(g_true)
    applied = jnp.zeros_like(g_true)
    for _ in range(50):
        gc = g_true + resid
        q, s = quantize_int8(gc)
        dec = dequantize_int8(q, s)
        resid = gc - dec
        applied = applied + dec
    # mean applied per step ~ true gradient
    np.testing.assert_allclose(np.asarray(applied / 50), np.asarray(g_true),
                               atol=float(s) * 0.6)


def test_compressed_allreduce_single_device_mesh():
    mesh = jax.make_mesh((jax.device_count(),), ("data",))
    allreduce = make_compressed_allreduce(mesh, "data")

    from jax.sharding import PartitionSpec as P

    def run(g, r):
        return allreduce({"g": g}, {"g": r})

    f = jax.shard_map(run, mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()),
                  check_vma=False)
    g = jnp.asarray(np.linspace(-1, 1, 32, dtype=np.float32))
    mean, resid = f(g, jnp.zeros_like(g))
    np.testing.assert_allclose(np.asarray(mean["g"]), np.asarray(g), atol=0.02)
    # residual = what quantization lost
    np.testing.assert_allclose(
        np.asarray(mean["g"] + resid["g"]), np.asarray(g), atol=1e-6)


# --------------------------------------------------------------------------
# checkpointing
# --------------------------------------------------------------------------

@pytest.fixture
def ckpt_dir():
    d = tempfile.mkdtemp(prefix="repro_ckpt_test_")
    yield d
    shutil.rmtree(d, ignore_errors=True)


def _tree(seed=0):
    rng = np.random.default_rng(seed)
    return {
        "a": jnp.asarray(rng.normal(size=(4, 8)).astype(np.float32)),
        "nested": {"b": jnp.asarray(rng.integers(0, 10, size=(3,)))},
    }


def test_checkpoint_roundtrip(ckpt_dir):
    mgr = CheckpointManager(ckpt_dir, keep=2)
    t = _tree(0)
    mgr.save(7, t, mesh_shape={"data": 1}, blocking=True)
    restored, manifest = mgr.restore(jax.tree.map(jnp.zeros_like, t))
    assert manifest["step"] == 7
    jax.tree.map(lambda x, y: np.testing.assert_array_equal(
        np.asarray(x), np.asarray(y)), t, restored)


def test_checkpoint_retention_and_latest(ckpt_dir):
    mgr = CheckpointManager(ckpt_dir, keep=2)
    for s in (1, 2, 3, 4):
        mgr.save(s, _tree(s), blocking=True)
    assert mgr.steps() == [3, 4]
    assert mgr.latest_step() == 4


def test_checkpoint_atomic_no_partial_dir(ckpt_dir):
    """A stale .tmp dir (simulated crash) is never listed as a checkpoint."""
    mgr = CheckpointManager(ckpt_dir, keep=3)
    mgr.save(1, _tree(1), blocking=True)
    os.makedirs(os.path.join(ckpt_dir, "step_00000002.tmp"))
    assert mgr.steps() == [1]
    restored, manifest = mgr.restore(jax.tree.map(jnp.zeros_like, _tree(1)))
    assert manifest["step"] == 1


def test_checkpoint_elastic_restore_shardings(ckpt_dir):
    """Restore with explicit shardings (the elastic path) places leaves on
    the current mesh regardless of the writing mesh."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    mgr = CheckpointManager(ckpt_dir, keep=1)
    t = _tree(3)
    mgr.save(5, t, mesh_shape={"data": 512}, blocking=True)   # "pod" run
    mesh = jax.make_mesh((jax.device_count(),), ("data",))
    sh = jax.tree.map(lambda _: NamedSharding(mesh, P()), t)
    restored, manifest = mgr.restore(jax.tree.map(jnp.zeros_like, t),
                                     shardings=sh)
    assert manifest["mesh_shape"] == {"data": 512}
    jax.tree.map(lambda x, y: np.testing.assert_array_equal(
        np.asarray(x), np.asarray(y)), t, restored)


# --------------------------------------------------------------------------
# elastic runner
# --------------------------------------------------------------------------

def test_elastic_runner_recovers_and_resizes(ckpt_dir):
    cfg = ElasticConfig(max_restarts=2, checkpoint_every=100)
    runner = ElasticRunner(cfg, None, [{"data": 16}, {"data": 8}])
    saves = {}

    def step_fn(state, step):
        return state + 1, {"loss": 1.0 / (step + 1)}

    def save_fn(state, step):
        saves["latest"] = (state, step)

    def restore_fn():
        return saves.get("latest", (0, 0))

    failures = {3: RuntimeError("node lost"), 5: RuntimeError("node lost"),
                6: RuntimeError("node lost")}
    state, history = runner.run(0, step_fn, 0, 10, save_fn, restore_fn,
                                failure_schedule=failures)
    # every step index eventually completed (restarts replay from the ckpt,
    # so some steps ran more than once)
    assert {r.step for r in history} == set(range(10))
    assert history[-1].step == 9
    # second failure hit max_restarts=2 -> resized down the preference list
    assert runner.current_mesh_shape() == {"data": 8}


def test_elastic_runner_flags_straggler():
    import time as _time
    cfg = ElasticConfig(straggler_factor=2.5, checkpoint_every=100)
    runner = ElasticRunner(cfg, None, [{"data": 1}])

    def step_fn(state, step):
        _time.sleep(0.08 if step == 5 else 0.005)
        return state, {"loss": 0.5}

    _, history = runner.run(0, step_fn, 0, 8, lambda *_: None, lambda: (0, 0))
    stragglers = [r.step for r in history if r.straggler]
    assert stragglers == [5]


# --------------------------------------------------------------------------
# end-to-end: loss decreases on a tiny model
# --------------------------------------------------------------------------

def test_train_loss_decreases_tiny_model():
    cfg = smoke_variant(get_config("olmo-1b"))
    tcfg = TrainConfig(opt=AdamWConfig(peak_lr=3e-3, warmup_steps=2,
                                       total_steps=30))
    step = jax.jit(make_train_step(cfg, tcfg))
    params, _ = lm.init_model(jax.random.PRNGKey(0), cfg)
    opt = init_opt_state(params)
    dcfg = TokenDatasetConfig(vocab_size=cfg.vocab_size, seq_len=32,
                              global_batch=4)
    losses = []
    for s in range(12):
        batch = {k: jnp.asarray(v) for k, v in batch_at_step(dcfg, 0).items()}
        params, opt, m = step(params, opt, batch)   # same batch: must overfit
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] * 0.9, losses


def test_microbatch_accumulation_matches_full_batch():
    """grad accumulation over k microbatches == one full-batch step."""
    cfg = smoke_variant(get_config("qwen2-1.5b"))
    opt_cfg = AdamWConfig(peak_lr=1e-3, warmup_steps=1, total_steps=10)
    params, _ = lm.init_model(jax.random.PRNGKey(0), cfg)
    dcfg = TokenDatasetConfig(vocab_size=cfg.vocab_size, seq_len=16,
                              global_batch=8)
    batch = {k: jnp.asarray(v) for k, v in batch_at_step(dcfg, 0).items()}

    step1 = jax.jit(make_train_step(cfg, TrainConfig(opt=opt_cfg, microbatches=1)))
    step4 = jax.jit(make_train_step(cfg, TrainConfig(opt=opt_cfg, microbatches=4)))
    p1, _, m1 = step1(params, init_opt_state(params), batch)
    p4, _, m4 = step4(params, init_opt_state(params), batch)
    diffs = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                           - b.astype(jnp.float32)))), p1, p4)
    assert max(jax.tree.leaves(diffs)) < 5e-2   # bf16 accumulation tolerance
