"""Substrate tests: optimizer, loss scaling, data, checkpointing,
fault-tolerant resume, gradient compression, serving."""
import os

from repro.compat import make_mesh
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.core.scaling import loss_scale_init, check_and_update_scale
from repro.data.pipeline import DataConfig, SyntheticTokens
from repro.models import build_model
from repro.optim.adamw import AdamWConfig, adamw_init, adamw_update
from repro.train.train_step import make_train_state, make_train_step
from repro.train.trainer import Trainer
from repro.checkpoint.ckpt import CheckpointManager


# ----------------------------------------------------------- optimizer ----

def test_adamw_converges_on_quadratic():
    cfg = AdamWConfig(lr=0.1, weight_decay=0.0, warmup_steps=1,
                      schedule="constant")
    params = {"w": jnp.array([5.0, -3.0])}
    opt = adamw_init(params, cfg)
    for _ in range(200):
        g = {"w": 2 * params["w"]}  # d/dw (w^2)
        params, opt, _ = adamw_update(g, opt, params, cfg)
    assert float(jnp.abs(params["w"]).max()) < 1e-2


def test_adamw_skip_freezes_state():
    cfg = AdamWConfig()
    params = {"w": jnp.ones(4)}
    opt = adamw_init(params, cfg)
    g = {"w": jnp.ones(4)}
    p2, opt2, _ = adamw_update(g, opt, params, cfg,
                               skip=jnp.array(True))
    np.testing.assert_array_equal(np.asarray(p2["w"]), np.ones(4))
    assert int(opt2["step"]) == 0


def test_adamw_low_precision_state():
    cfg = AdamWConfig(master_dtype=jnp.float16, moment_dtype=jnp.bfloat16)
    params = {"w": jnp.ones(8, jnp.bfloat16)}
    opt = adamw_init(params, cfg)
    assert opt["master"]["w"].dtype == jnp.float16
    assert opt["m"]["w"].dtype == jnp.bfloat16
    g = {"w": jnp.full(8, 0.5, jnp.float32)}
    p2, opt2, m = adamw_update(g, opt, params, cfg)
    assert p2["w"].dtype == jnp.bfloat16
    assert np.isfinite(float(m["grad_norm"]))


# --------------------------------------------------------- loss scaling ---

def test_loss_scale_shrinks_on_overflow_and_grows_back():
    st = loss_scale_init(2.0 ** 10)
    bad = {"g": jnp.array([jnp.inf])}
    _, st2, skip = check_and_update_scale(st, bad)
    assert bool(skip) and float(st2["scale"]) == 2.0 ** 9
    good = {"g": jnp.array([1.0])}
    st3 = st2
    for _ in range(3):
        _, st3, skip = check_and_update_scale(st3, good, growth_interval=2)
    assert float(st3["scale"]) > 2.0 ** 9


# ----------------------------------------------------------------- data ---

def test_data_deterministic_and_host_sharded():
    d = SyntheticTokens(DataConfig(vocab_size=1000, seq_len=16,
                                   global_batch=8))
    b1 = d.global_batch_at_step(3)
    b2 = d.global_batch_at_step(3)
    np.testing.assert_array_equal(b1, b2)
    assert (b1 != d.global_batch_at_step(4)).any()
    h0 = d.host_batch_at_step(3, 0, 2)
    h1 = d.host_batch_at_step(3, 1, 2)
    np.testing.assert_array_equal(np.concatenate([h0, h1]), b1)
    assert b1.min() >= 0 and b1.max() < 1000


# ----------------------------------------------------------- checkpoint ---

def test_checkpoint_roundtrip_and_latest(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    tree = {"a": jnp.arange(6).reshape(2, 3).astype(jnp.bfloat16),
            "b": {"c": jnp.float32(3.5)}}
    for s in (5, 10, 15):
        mgr.save(s, tree)
    assert mgr.latest_step() == 15
    like = jax.tree.map(jnp.zeros_like, tree)
    back = mgr.restore(15, like)
    np.testing.assert_array_equal(np.asarray(back["a"], np.float32),
                                  np.asarray(tree["a"], np.float32))
    # keep=2 garbage-collects the oldest
    dirs = [d for d in os.listdir(tmp_path) if d.startswith("step_")]
    assert len(dirs) == 2


# ------------------------------------------------- end-to-end training ----

def _tiny_setup(tmp_path, fail_at=None):
    cfg = ARCHS["qwen2.5-3b"].reduced()
    model = build_model(cfg)
    opt_cfg = AdamWConfig(lr=1e-3, warmup_steps=2, schedule="constant")
    state = make_train_state(model, jax.random.key(0), opt_cfg)
    step = make_train_step(model, opt_cfg, impl="xla")
    data = SyntheticTokens(DataConfig(cfg.vocab_size, seq_len=16,
                                      global_batch=4))
    tr = Trainer(model, step, state, data, ckpt_dir=str(tmp_path),
                 save_every=2, fail_at_step=fail_at)
    return tr


def test_training_runs_and_loss_finite(tmp_path):
    tr = _tiny_setup(tmp_path / "a")
    log = tr.run(4)
    assert len(log) == 4
    assert all(np.isfinite(m["loss"]) for m in log)
    assert log[-1]["skipped"] == 0


def test_failure_resume_is_bit_exact(tmp_path):
    # uninterrupted reference run: 6 steps
    ref = _tiny_setup(tmp_path / "ref")
    ref.run(6)
    ref_leaves = jax.tree.leaves(ref.state["params"])

    # interrupted run: dies at step 4 (checkpoints published at 2 and 4)
    tr = _tiny_setup(tmp_path / "crash", fail_at=4)
    with pytest.raises(RuntimeError):
        tr.run(6)
    # "new process": fresh trainer auto-resumes from the last *published*
    # checkpoint (the crash-time flush makes that step 4)
    tr2 = _tiny_setup(tmp_path / "crash")
    assert tr2.start_step in (2, 4)
    tr2.run(6 - tr2.start_step)  # finish the remaining steps
    for a, b in zip(ref_leaves, jax.tree.leaves(tr2.state["params"])):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_straggler_detection(tmp_path):
    tr = _tiny_setup(tmp_path / "s")
    seen = []
    tr.on_straggler = lambda step, dt: seen.append(step)
    import time as _t
    orig = tr.train_step

    def slow_step(state, batch):
        out = orig(state, batch)
        if len(tr.step_times) == 5:
            _t.sleep(1.0)
        return out

    tr.train_step = slow_step
    tr.run(7)
    assert tr.straggler_count >= 1


# ----------------------------------------------------- grad compression ---

def test_compressed_psum_matches_mean_with_error_feedback():
    # needs >1 device: simulate with a 1-device mesh reduction identity,
    # plus the pure quantization error-feedback property single-device.
    from repro.optim.grad_compress import (compressed_psum_mean,
                                           error_feedback_init)
    mesh = make_mesh((1,), ("data",))
    g = {"w": jnp.asarray(np.random.default_rng(0).normal(0, 1, (64,)),
                          jnp.float32)}
    ef = error_feedback_init(g)
    acc_true = np.zeros(64)
    acc_comp = np.zeros(64)
    for _ in range(50):
        red, ef = compressed_psum_mean(g, ef, mesh, "data")
        acc_true += np.asarray(g["w"])
        acc_comp += np.asarray(red["w"])
    # error feedback keeps the *accumulated* estimate tight even though a
    # single fp8 reduction is coarse
    rel = np.abs(acc_comp - acc_true).max() / np.abs(acc_true).max()
    assert rel < 0.01


def test_proj_under_mesh_runs_gspmd_gemms_as_xla():
    """A projection GSPMD partitions (no explicit TP wire) never reaches
    a compiled Mosaic kernel, which cannot be partitioned automatically:
    under a mesh, impl='pallas' runs it as the XLA GEMM (compiled Pallas
    would not even run on this CPU backend)."""
    from repro.core.linear import linear
    from repro.models.layers import proj
    from repro.parallel.sharding import make_rules
    rules = make_rules(make_mesh((1, 1), ("data", "model")))
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(0, 1, (2, 8, 64)), jnp.bfloat16)
    w = jnp.asarray(rng.normal(0, 1, (64, 128)), jnp.bfloat16)
    got = proj(x, w, None, "hfp8", rules, "pallas", kind="col")
    want = linear(x, w, policy="hfp8", impl="xla")
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


# -------------------------------------------------------------- serving ---

def test_generate_greedy():
    from repro.serve.decode import generate
    cfg = ARCHS["deepseek-7b"].reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    prompt = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, 4)))
    toks = generate(model, params, prompt, max_new_tokens=3, max_len=16)
    assert toks.shape == (2, 3)
    assert int(toks.max()) < cfg.vocab_size
