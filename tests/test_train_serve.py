"""Training-loop + serving-engine + checkpoint integration tests."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.data.pipeline import DataConfig, synthetic_stream
from repro.models import model as M
from repro.optim.adamw import AdamWConfig, adamw_init
from repro.serve.engine import ServeConfig, ServingEngine
from repro.train.checkpoint import load_checkpoint, save_checkpoint
from repro.train.trainer import TrainConfig, Trainer, make_train_step


def test_loss_decreases_tiny_model():
    cfg = C.get_config("smollm-135m").reduced()
    dc = DataConfig(seq_len=64, global_batch=8, seed=0)
    tc = TrainConfig(steps=30, warmup=5, log_every=10, dtype=jnp.float32,
                     optim=AdamWConfig(lr=3e-3))
    tr = Trainer(cfg, tc, synthetic_stream(cfg, dc))
    tr.run()
    first = tr.history[0]["loss"]
    last = tr.history[-1]["loss"]
    assert last < first - 0.5, (first, last)


def test_microbatch_grad_equivalence():
    """microbatches=2 must produce the same update as microbatches=1."""
    cfg = C.get_config("smollm-135m").reduced()
    params = M.init_lm(jax.random.PRNGKey(0), cfg)
    batch = {
        "tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, cfg.vocab),
        "targets": jax.random.randint(jax.random.PRNGKey(2), (4, 32), 0, cfg.vocab),
    }
    outs = []
    for mb in (1, 2):
        tc = TrainConfig(dtype=jnp.float32, microbatches=mb, optim=AdamWConfig())
        step = jax.jit(make_train_step(cfg, tc))
        p2, _, m = step(params, adamw_init(params), batch)
        outs.append((p2, float(m["loss"])))
    (pa, la), (pb, lb) = outs
    assert la == pytest.approx(lb, rel=1e-4)
    for a, b in zip(jax.tree.leaves(pa), jax.tree.leaves(pb)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)


def test_checkpoint_roundtrip(tmp_path):
    cfg = C.get_config("smollm-135m").reduced()
    params = M.init_lm(jax.random.PRNGKey(0), cfg)
    opt = adamw_init(params)
    save_checkpoint(str(tmp_path), params, opt, step=7)
    p2, o2, step = load_checkpoint(str(tmp_path))
    assert step == 7
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(opt), jax.tree.leaves(o2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("arch", ["smollm-135m", "rwkv6-7b", "jamba-1.5-large-398b",
                                  "whisper-medium"])
def test_serving_engine_generates(arch):
    cfg = C.get_config(arch).reduced()
    params = M.init_lm(jax.random.PRNGKey(0), cfg)
    B, S = 2, 16
    sc = ServeConfig(batch=B, cache_len=64, dtype=jnp.float32,
                     enc_len=32 if cfg.enc_dec else 0)
    eng = ServingEngine(cfg, params, sc)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab)}
    if cfg.family == "vlm":
        batch["vision_embed"] = jax.random.normal(jax.random.PRNGKey(2), (B, 8, cfg.d_model)) * 0.02
        pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        batch["rope_pos"] = jnp.broadcast_to(pos[None], (3, B, S)).astype(jnp.int32)
    if cfg.enc_dec:
        batch["audio_embed"] = jax.random.normal(jax.random.PRNGKey(3), (B, 32, cfg.d_model)) * 0.02
    logits = eng.prefill_prompt(batch)
    first = logits[:, -1, :].argmax(-1)
    toks = eng.generate(first, n_tokens=5)
    assert toks.shape == (B, 5)
    assert np.all((toks >= 0) & (toks < cfg.vocab))


def test_greedy_decode_deterministic():
    cfg = C.get_config("smollm-135m").reduced()
    params = M.init_lm(jax.random.PRNGKey(0), cfg)
    outs = []
    for _ in range(2):
        sc = ServeConfig(batch=1, cache_len=32, dtype=jnp.float32)
        eng = ServingEngine(cfg, params, sc)
        batch = {"tokens": jnp.arange(8)[None] % cfg.vocab}
        logits = eng.prefill_prompt(batch)
        outs.append(eng.generate(logits[:, -1].argmax(-1), 6))
    np.testing.assert_array_equal(outs[0], outs[1])
