"""Per-architecture smoke tests: every assigned arch, reduced variant
(<=2 periods, d_model<=256, <=4 experts), one forward/train step on CPU,
asserting output shapes + no NaNs; plus decode-path consistency."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.data.pipeline import DataConfig, dec_len, synthetic_stream
from repro.models import model as M
from repro.models.config import INPUT_SHAPES
from repro.models.model import RunFlags

ARCHS = list(C.ARCHS)


def make_batch(cfg, B=2, S=32, seed=0):
    key = jax.random.PRNGKey(seed)
    batch = {
        "tokens": jax.random.randint(key, (B, S), 0, cfg.vocab),
        "targets": jax.random.randint(key, (B, S), 0, cfg.vocab),
    }
    if cfg.family == "vlm":
        batch["vision_embed"] = jax.random.normal(key, (B, 8, cfg.d_model)) * 0.02
        pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        batch["rope_pos"] = jnp.broadcast_to(pos[None], (3, B, S)).astype(jnp.int32)
    if cfg.enc_dec:
        batch["audio_embed"] = jax.random.normal(key, (B, 64, cfg.d_model)) * 0.02
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_arch_forward_step(arch):
    cfg = C.get_config(arch).reduced()
    params = M.init_lm(jax.random.PRNGKey(0), cfg)
    batch = make_batch(cfg)
    loss, metrics = M.forward_train(params, cfg, batch)
    assert loss.shape == ()
    assert bool(jnp.isfinite(loss)), f"{arch}: loss not finite"
    assert 0.0 <= float(metrics["acc"]) <= 1.0


@pytest.mark.parametrize("arch", ARCHS)
def test_arch_train_step_no_nans(arch):
    from repro.optim.adamw import AdamWConfig, adamw_init
    from repro.train.trainer import TrainConfig, make_train_step
    cfg = C.get_config(arch).reduced()
    params = M.init_lm(jax.random.PRNGKey(0), cfg)
    tc = TrainConfig(dtype=jnp.float32, optim=AdamWConfig())
    step = jax.jit(make_train_step(cfg, tc))
    opt = adamw_init(params)
    batch = make_batch(cfg)
    params2, opt2, metrics = step(params, opt, batch)
    assert bool(jnp.isfinite(metrics["loss"]))
    assert bool(jnp.isfinite(metrics["grad_norm"]))
    # params actually moved
    moved = jax.tree.reduce(
        lambda a, leaf: a + float(jnp.abs(leaf).sum()),
        jax.tree.map(lambda a, b: (a.astype(jnp.float32) - b.astype(jnp.float32)),
                     params2, params), 0.0)
    assert moved > 0.0
    for leaf in jax.tree.leaves(params2):
        assert bool(jnp.all(jnp.isfinite(leaf.astype(jnp.float32))))


@pytest.mark.parametrize("arch", ARCHS)
def test_arch_decode_matches_prefill_logits(arch):
    """serve_step(token t | cache of 0..t-1) must agree with teacher-forced
    forward logits — the cache path is exact, not approximate."""
    cfg = C.get_config(arch).reduced()
    if cfg.enc_dec:
        pytest.skip("enc-dec decode path covered in test_serve")
    B, S = 2, 16
    params = M.init_lm(jax.random.PRNGKey(1), cfg)
    flags = RunFlags()
    # generate ONE (S+1)-token batch; the S-token batch is its prefix
    batch2 = make_batch(cfg, B=B, S=S + 1, seed=1)
    batch = {k: (v[:, :S] if k in ("tokens", "targets") else
                 (v[:, :, :S] if k == "rope_pos" else v))
             for k, v in batch2.items()}
    caches = M.make_caches(cfg, B, S + 1, jnp.float32)  # room for 1 decode step
    logits_pf, caches = M.prefill(params, cfg, {k: v for k, v in batch.items()
                                                if k != "targets"},
                                  caches, flags, dtype=jnp.float32)
    # decode one token at the end and compare against a longer prefill
    caches2 = M.make_caches(cfg, B, S + 1, jnp.float32)
    tok_next = batch2["tokens"][:, S:S + 1]
    batch2_prefill = {k: v for k, v in batch2.items() if k != "targets"}
    logits_full, _ = M.prefill(params, cfg, batch2_prefill, caches2, flags,
                               dtype=jnp.float32)
    logits_dec, _ = M.decode_step(params, cfg, caches, tok_next, jnp.int32(S),
                                  flags, dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(logits_dec[:, 0]),
                               np.asarray(logits_full[:, S]),
                               rtol=2e-3, atol=2e-3)


def test_param_counts_match_configs():
    """Analytic param counts are in range of the models' advertised sizes."""
    expect = {
        "deepseek-v2-236b": (200e9, 260e9),
        "rwkv6-7b": (6e9, 9e9),
        "jamba-1.5-large-398b": (330e9, 430e9),
        "qwen2.5-14b": (12e9, 16e9),
        "whisper-medium": (0.25e9, 1.0e9),
        "qwen2-vl-2b": (1.2e9, 2.4e9),
        "grok-1-314b": (280e9, 340e9),
        "smollm-135m": (0.11e9, 0.16e9),
        "qwen1.5-110b": (95e9, 125e9),
        "deepseek-7b": (6e9, 8e9),
    }
    for arch, (lo, hi) in expect.items():
        n = C.get_config(arch).param_count()
        assert lo <= n <= hi, f"{arch}: {n / 1e9:.1f}B outside [{lo / 1e9}, {hi / 1e9}]"


def test_moe_active_params_smaller():
    for arch in ("deepseek-v2-236b", "grok-1-314b", "jamba-1.5-large-398b"):
        cfg = C.get_config(arch)
        assert cfg.active_param_count() < 0.5 * cfg.param_count()


def test_exact_config_values():
    """Spot-check the assigned architecture table values."""
    c = C.get_config("deepseek-v2-236b")
    assert (c.n_layers, c.d_model, c.n_heads, c.vocab) == (60, 5120, 128, 102400)
    assert c.moe.n_experts == 160 and c.moe.top_k == 6 and c.moe.n_shared == 2
    assert c.mla.kv_lora == 512
    c = C.get_config("jamba-1.5-large-398b")
    assert (c.n_layers, c.d_model, c.d_ff) == (72, 8192, 24576)
    assert c.moe.n_experts == 16 and c.moe.top_k == 2
    c = C.get_config("qwen1.5-110b")
    assert (c.n_layers, c.d_model, c.d_ff, c.vocab) == (80, 8192, 49152, 152064)
    assert c.qkv_bias
    c = C.get_config("rwkv6-7b")
    assert c.attn_free and (c.n_layers, c.d_model) == (32, 4096)
    c = C.get_config("whisper-medium")
    assert c.enc_dec and (c.n_layers, c.d_model, c.vocab) == (24, 1024, 51865)
    c = C.get_config("grok-1-314b")
    assert (c.n_layers, c.d_model, c.d_ff) == (64, 6144, 32768)


def test_synthetic_stream_deterministic():
    cfg = C.get_config("smollm-135m").reduced()
    dc = DataConfig(seq_len=32, global_batch=4, seed=5)
    b1 = next(synthetic_stream(cfg, dc))
    b2 = next(synthetic_stream(cfg, dc))
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    # different shards differ
    b3 = next(synthetic_stream(cfg, dc, shard=1, n_shards=2))
    assert b3["tokens"].shape[0] == 2
