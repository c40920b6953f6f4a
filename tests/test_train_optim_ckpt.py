"""Trainer, optimizer, checkpointing, data pipeline."""

import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.checkpoint import (latest_step, restore_checkpoint,
                              save_checkpoint)
from repro.configs import get_config
from repro.data.pipeline import DataConfig, synthetic_batch
from repro.models import Model
from repro.optim import (AdamWConfig, adamw_init, adamw_update,
                         clip_by_global_norm, cosine_schedule)
from repro.train import Trainer, TrainerConfig


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------

def test_adamw_matches_reference_math():
    cfg = AdamWConfig(lr_peak=1e-2, lr_min=1e-2, warmup_steps=0,
                      decay_steps=1, weight_decay=0.0, clip_norm=1e9)
    p = {"w": jnp.asarray([[1.0, -2.0]], jnp.float32)}
    g = {"w": jnp.asarray([[0.5, 0.5]], jnp.float32)}
    p_before = np.asarray(p["w"]).copy()     # p is donated by adamw_update
    st_ = adamw_init(p, cfg)
    p1, st1, _ = adamw_update(p, g, st_, cfg)
    m = 0.1 * 0.5
    v = 0.05 * 0.25
    upd = (m / 0.1) / (np.sqrt(v / 0.05) + cfg.eps)
    np.testing.assert_allclose(np.asarray(p1["w"]), p_before - 1e-2 * upd,
                               rtol=1e-5)


def test_clip_by_global_norm():
    tree = {"a": jnp.ones((10,)) * 3.0}
    clipped, norm = clip_by_global_norm(tree, 1.0)
    assert float(norm) == pytest.approx(np.sqrt(90.0))
    n2 = float(jnp.linalg.norm(clipped["a"]))
    assert n2 == pytest.approx(1.0, rel=1e-5)


def test_cosine_schedule_shape():
    cfg = AdamWConfig(lr_peak=1e-3, lr_min=1e-4, warmup_steps=10,
                      decay_steps=100)
    lrs = [float(cosine_schedule(cfg, jnp.asarray(s))) for s in
           (0, 5, 10, 50, 100, 200)]
    assert lrs[0] == 0.0
    assert lrs[2] == pytest.approx(1e-3, rel=1e-3)
    assert lrs[-1] == pytest.approx(1e-4, rel=1e-2)
    assert lrs[3] < lrs[2]


def test_bf16_moments_still_learn():
    cfg = AdamWConfig(moment_dtype="bfloat16", warmup_steps=0,
                      decay_steps=10, lr_peak=1e-2, lr_min=1e-2)
    p = {"w": jnp.ones((4, 4), jnp.float32)}
    st_ = adamw_init(p, cfg)
    assert st_["m"]["w"].dtype == jnp.bfloat16
    g = {"w": jnp.ones((4, 4), jnp.float32)}
    p1, st1, _ = adamw_update(p, g, st_, cfg)
    assert float(p1["w"][0, 0]) < 1.0


def test_int8_moment_quantization_roundtrip():
    from repro.optim.adamw import _dequantize, _quantize
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(1000,)).astype(np.float32))
    q, s = _quantize(x, 256)
    back = np.asarray(_dequantize(q, s, (1000,)))
    assert np.abs(back - np.asarray(x)).max() < np.abs(np.asarray(x)).max() / 100


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------

def test_data_deterministic():
    dc = DataConfig(vocab_size=128, seq_len=16, global_batch=4)
    a = synthetic_batch(dc, 7)
    b = synthetic_batch(dc, 7)
    c = synthetic_batch(dc, 8)
    assert (a["tokens"] == b["tokens"]).all()
    assert not (a["tokens"] == c["tokens"]).all()


def test_data_labels_are_shifted_stream():
    dc = DataConfig(vocab_size=997, seq_len=32, global_batch=2)
    b = synthetic_batch(dc, 0)
    assert b["tokens"].shape == (2, 32) and b["labels"].shape == (2, 32)
    assert int(b["tokens"].max()) < 997


@settings(max_examples=10, deadline=None)
@given(st.integers(2, 64), st.integers(1, 8), st.integers(0, 100))
def test_property_data_shapes(seq, batch, step):
    dc = DataConfig(vocab_size=64, seq_len=seq, global_batch=batch)
    b = synthetic_batch(dc, step)
    assert b["tokens"].shape == (batch, seq)
    assert int(b["tokens"].min()) >= 0 and int(b["tokens"].max()) < 64


# ---------------------------------------------------------------------------
# checkpointing
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(6).reshape(2, 3), "b": {"c": jnp.ones(4)}}
    save_checkpoint(str(tmp_path), 5, tree)
    assert latest_step(str(tmp_path)) == 5
    got = restore_checkpoint(str(tmp_path), 5, tree)
    np.testing.assert_array_equal(np.asarray(got["a"]), np.asarray(tree["a"]))
    np.testing.assert_array_equal(np.asarray(got["b"]["c"]),
                                  np.asarray(tree["b"]["c"]))


def test_checkpoint_gc_keep_last(tmp_path):
    tree = {"a": jnp.zeros(2)}
    for s in (1, 2, 3, 4):
        save_checkpoint(str(tmp_path), s, tree, keep_last=2)
    dirs = sorted(d for d in os.listdir(tmp_path) if d.startswith("step_"))
    assert dirs == ["step_3", "step_4"]


# ---------------------------------------------------------------------------
# trainer end-to-end
# ---------------------------------------------------------------------------

def test_trainer_learns_and_resumes(tmp_path):
    cfg = get_config("qwen2-7b-smoke")
    m = Model(cfg)
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=32, global_batch=8)
    tc = TrainerConfig(num_steps=10, microbatches=2, ckpt_every=5,
                       ckpt_dir=str(tmp_path), log_every=100)
    tr = Trainer(m, AdamWConfig(warmup_steps=3, decay_steps=50), dc, tc)
    params, opt, hist = tr.run(jax.random.PRNGKey(0))
    # single-step losses on random tokens are noisy; compare a trailing
    # average against the leading one so the assertion tests the trend
    losses = [h["loss"] for h in hist]
    assert sum(losses[-3:]) / 3 < sum(losses[:3]) / 3
    # resume: picks up at step 10
    tr2 = Trainer(m, AdamWConfig(warmup_steps=3, decay_steps=50), dc, tc)
    _, _, h2 = tr2.run(jax.random.PRNGKey(0), num_steps=12)
    assert [h["step"] for h in h2] == [10, 11]


def test_microbatch_equivalence():
    """1 vs 4 microbatches produce (nearly) the same update."""
    from repro.train import make_train_step
    cfg = get_config("stablelm-1.6b-smoke")
    m = Model(cfg)
    ocfg = AdamWConfig(warmup_steps=0, decay_steps=10)
    params = m.init(jax.random.PRNGKey(0))
    opt = adamw_init(params, ocfg)
    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=16, global_batch=8)
    batch = synthetic_batch(dcfg, 0)
    p1, _, m1 = jax.jit(make_train_step(m, ocfg, 1))(params, opt, batch)
    opt2 = adamw_init(params, ocfg)
    p4, _, m4 = jax.jit(make_train_step(m, ocfg, 4))(params, opt2, batch)
    d = max(float(jnp.abs(a - b).max())
            for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p4)))
    assert d < 5e-3
    assert abs(float(m1["nll"]) - float(m4["nll"])) < 5e-2
