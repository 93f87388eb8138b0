"""Model-level kernel integration: the Pallas attention backend must agree
with the XLA online pass through the full forward (smollm + qwen2.5 GQA)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.models import model as M
from repro.models.layers import set_attention_impl


@pytest.mark.parametrize("arch", ["smollm-135m", "qwen2.5-14b"])
def test_pallas_attention_matches_xla_forward(arch):
    cfg = C.get_config(arch).reduced()
    params = M.init_lm(jax.random.PRNGKey(0), cfg)
    batch = {
        "tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, cfg.vocab),
        "targets": jax.random.randint(jax.random.PRNGKey(2), (2, 64), 0, cfg.vocab),
    }
    try:
        set_attention_impl("xla")
        loss_x, _ = M.forward_train(params, cfg, batch, dtype=jnp.float32)
        set_attention_impl("pallas")
        loss_p, _ = M.forward_train(params, cfg, batch, dtype=jnp.float32)
    finally:
        set_attention_impl("xla")
    np.testing.assert_allclose(float(loss_x), float(loss_p), rtol=1e-4)


def test_set_attention_impl_validates():
    with pytest.raises(AssertionError):
        set_attention_impl("cuda")
