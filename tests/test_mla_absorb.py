"""MLA absorbed-decode path (RunFlags.mla_absorb): the latent-space attention
rewrite must agree with the faithful reconstruct path."""

import jax
import jax.numpy as jnp
import numpy as np

import repro.configs as C
from repro.models import model as M
from repro.models.model import RunFlags


def test_mla_absorb_matches_faithful_decode():
    cfg = C.get_config("deepseek-v2-236b").reduced()
    B, S = 2, 12
    params = M.init_lm(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S + 1), 0, cfg.vocab)
    batch = {"tokens": toks[:, :S]}
    outs = {}
    for absorb in (False, True):
        flags = RunFlags(mla_absorb=absorb)
        caches = M.make_caches(cfg, B, S + 1, jnp.float32)
        _, caches = M.prefill(params, cfg, batch, caches, RunFlags(), dtype=jnp.float32)
        logits, _ = M.decode_step(params, cfg, caches, toks[:, S:S + 1],
                                  jnp.int32(S), flags, dtype=jnp.float32)
        outs[absorb] = np.asarray(logits[:, 0])
    np.testing.assert_allclose(outs[True], outs[False], rtol=2e-3, atol=2e-3)
