"""Training loop, checkpoint/restart, TMR store, elastic resharding,
gradient compression, straggler detection, data pipeline."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import TrainConfig
from repro.configs.registry import get_config
from repro.ckpt import checkpoint as ckpt
from repro.ckpt import tmr_store
from repro.data.pipeline import DataConfig, SyntheticLM, pack_documents
from repro.ft.elastic import plan_remesh
from repro.ft.failures import FailurePlan
from repro.ft.straggler import StragglerDetector
from repro.optim import compression as comp
from repro.train.step import init_train_state, make_train_step
from repro.train.trainer import Trainer, TrainerConfig


def _small():
    cfg = get_config("xlstm-125m", smoke=True)
    tc = TrainConfig(lr=3e-3, total_steps=30, warmup_steps=3)
    loader = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size, seq_len=32,
                                    global_batch=4))
    return cfg, tc, loader


def test_loss_decreases():
    cfg, tc, loader = _small()
    t = Trainer(cfg, tc, loader, TrainerConfig(log_every=1000),
                log_fn=lambda *_: None)
    hist = t.run(25)
    first = np.mean([h["loss"] for h in hist[:5]])
    last = np.mean([h["loss"] for h in hist[-5:]])
    assert last < first, (first, last)


def test_microbatching_matches_full_batch():
    cfg = get_config("chatglm3-6b", smoke=True)
    loader = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size, seq_len=16,
                                    global_batch=4))
    batch = loader.batch(0)
    s1, _ = init_train_state(jax.random.PRNGKey(0), cfg)
    s2, _ = init_train_state(jax.random.PRNGKey(0), cfg)
    st1 = jax.jit(make_train_step(cfg, TrainConfig(microbatches=1)))
    st2 = jax.jit(make_train_step(cfg, TrainConfig(microbatches=2)))
    s1, m1 = st1(s1, batch)
    s2, m2 = st2(s2, batch)
    assert float(m1["loss"]) == pytest.approx(float(m2["loss"]), rel=1e-3)
    for a, b in zip(jax.tree.leaves(s1.params), jax.tree.leaves(s2.params)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=5e-3)


def test_checkpoint_roundtrip(tmp_path):
    cfg, tc, loader = _small()
    state, _ = init_train_state(jax.random.PRNGKey(0), cfg)
    ckpt.save(state, str(tmp_path), 7)
    assert ckpt.latest_step(str(tmp_path)) == 7
    restored, step = ckpt.restore(state, str(tmp_path))
    assert step == 7
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        assert (np.asarray(a, np.float32) == np.asarray(b, np.float32)).all()


def test_checkpoint_detects_corruption(tmp_path):
    cfg, tc, loader = _small()
    state, _ = init_train_state(jax.random.PRNGKey(0), cfg)
    path = ckpt.save(state, str(tmp_path), 1)
    shard = os.path.join(path, "shard_p0.npz")
    blob = bytearray(open(shard, "rb").read())
    blob[len(blob) // 2] ^= 0xFF
    open(shard, "wb").write(bytes(blob))
    with pytest.raises(Exception):
        ckpt.restore(state, str(tmp_path))


def test_tmr_store_heals_corrupted_replica(tmp_path):
    cfg, tc, loader = _small()
    state, _ = init_train_state(jax.random.PRNGKey(0), cfg)
    tmr_store.save(state, str(tmp_path), 3, replicas=3)
    # corrupt one replica's payload
    shard = os.path.join(str(tmp_path), "replica_1", "step_00000003",
                         "shard_p0.npz")
    blob = bytearray(open(shard, "rb").read())
    blob[len(blob) // 3] ^= 0xFF
    open(shard, "wb").write(bytes(blob))
    restored, step, healed = tmr_store.restore(state, str(tmp_path))
    assert step == 3
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        assert (np.asarray(a, np.float32) == np.asarray(b, np.float32)).all()


def test_tmr_store_kernel_vote_heals_corrupted_replica(tmp_path):
    """The MAJX-kernel restore path picks its own kernel mode."""
    cfg, tc, loader = _small()
    state, _ = init_train_state(jax.random.PRNGKey(0), cfg)
    tmr_store.save(state, str(tmp_path), 3, replicas=3)
    shard = os.path.join(str(tmp_path), "replica_2", "step_00000003",
                         "shard_p0.npz")
    blob = bytearray(open(shard, "rb").read())
    blob[len(blob) // 2] ^= 0xFF
    open(shard, "wb").write(bytes(blob))
    restored, step, _ = tmr_store.restore(state, str(tmp_path),
                                          use_kernel=True)
    assert step == 3
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        assert (np.asarray(a, np.float32) == np.asarray(b, np.float32)).all()


def test_trainer_restarts_after_failure(tmp_path):
    cfg, tc, loader = _small()
    t = Trainer(cfg, tc, loader,
                TrainerConfig(ckpt_dir=str(tmp_path), ckpt_every=5,
                              log_every=1000),
                failure_plan=FailurePlan(at_steps=(12,)),
                log_fn=lambda *_: None)
    hist = t.run(20)
    steps = [h["step"] for h in hist]
    assert 12 in steps and 19 in steps
    # step 10..11 replayed after restart from the step-10 checkpoint
    assert steps.count(11) >= 2


def test_compression_error_feedback():
    key = jax.random.PRNGKey(0)
    grads = {"w": jax.random.normal(key, (256,))}
    fb = comp.init_feedback(grads)
    dec, fb, stats = comp.compress(grads, fb, "int8")
    err = np.abs(np.asarray(dec["w"] - grads["w"])).max()
    assert err < 0.05  # int8 quantization error bounded
    assert stats["wire_bytes_frac"] == 0.25
    # residual carries the quantization error
    assert np.allclose(np.asarray(fb.residual["w"]),
                       np.asarray(grads["w"] - dec["w"]), atol=1e-6)


def test_topk_compression_sparsity():
    key = jax.random.PRNGKey(1)
    grads = {"w": jax.random.normal(key, (1000,))}
    fb = comp.init_feedback(grads)
    dec, fb, _ = comp.compress(grads, fb, "topk", topk_frac=0.05)
    nz = float(jnp.sum(dec["w"] != 0))
    assert nz <= 60


def test_straggler_detector():
    d = StragglerDetector(n_workers=8)
    for w in range(8):
        for _ in range(5):
            d.record(w, 1.0 if w != 3 else 2.5)
    assert d.stragglers() == [3]
    assert d.fleet_slowdown() > 2.0


def test_straggler_detector_zero_step_time_is_a_sample():
    """Regression: a measured 0.0 step time used to look identical to
    the cold 'no samples yet' sentinel (ema == 0), silently excluding
    that worker from straggler math.  Sample counts are now explicit."""
    d = StragglerDetector(n_workers=3)
    d.record(0, 0.0)   # instant worker: a real measurement
    d.record(1, 0.1)
    d.record(2, 5.0)
    # worker 0's 0.0 participates: the median is 0.1 and worker 2 is
    # flagged against it rather than against a roster that forgot w0.
    assert d.stragglers() == [2]
    assert d.fleet_slowdown() > 10.0


def test_straggler_detector_seeded_and_cold_workers():
    # Seeded EMAs count as warm (one prior sample each)...
    d = StragglerDetector(n_workers=2, ema=np.array([1.0, 4.0]))
    assert d.stragglers() == [1]
    # ...while a cold worker (no samples) is excluded until it reports.
    d2 = StragglerDetector(n_workers=3)
    d2.record(0, 1.0)
    d2.record(1, 1.0)
    assert d2.stragglers() == []
    d2.record(2, 9.0)
    assert d2.stragglers() == [2]


def test_plan_remesh():
    assert plan_remesh(256, 16) == (16, 16)
    assert plan_remesh(240, 16) == (15, 16)  # one node lost
    assert plan_remesh(512, 16, pods=2) == (2, 16, 16)
    with pytest.raises(ValueError):
        plan_remesh(8, 16)


def test_data_determinism_and_packing():
    loader = SyntheticLM(DataConfig(vocab_size=100, seq_len=16,
                                    global_batch=2, seed=3))
    b1, b2 = loader.batch(5), loader.batch(5)
    assert (b1["tokens"] == b2["tokens"]).all()
    assert (b1["labels"][:, :-1] == b1["tokens"][:, 1:]).all()
    docs = [np.arange(10), np.arange(37), np.arange(5)]
    toks, mask, seg = pack_documents(docs, 16)
    assert toks.shape[1] == 16 and (mask[0] == 1).all()
    assert toks.shape == mask.shape == seg.shape
