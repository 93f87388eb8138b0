"""Sharding-rule unit tests (single real CPU device: rules are validated
structurally — specs must be buildable, divisible, and cover every leaf)."""

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
import numpy as np
import pytest

import repro.configs as C
from repro.distribution.sharding import (batch_shardings, cache_shardings,
                                         opt_shardings, param_shardings)
from repro.data.pipeline import make_batch_specs
from repro.models import model as M
from repro.models.config import INPUT_SHAPES
from repro.optim.adamw import adamw_init


def tiny_mesh(shape=(1, 1), axes=("data", "model")):
    devs = np.asarray(jax.devices()[:1]).reshape(shape)
    return Mesh(devs, axes)


@pytest.mark.parametrize("arch", list(C.ARCHS))
def test_param_shardings_cover_all_leaves(arch):
    cfg = C.get_config(arch).reduced()
    mesh = tiny_mesh()
    pshapes = jax.eval_shape(lambda: M.init_lm(jax.random.PRNGKey(0), cfg))
    psh = param_shardings(mesh, pshapes, fsdp=False)
    n_params = len(jax.tree.leaves(pshapes))
    n_specs = len(jax.tree.leaves(psh, is_leaf=lambda x: isinstance(x, NamedSharding)))
    assert n_specs == n_params
    # every spec is structurally valid for its leaf on a 1x1 mesh
    for leaf, sh in zip(jax.tree.leaves(pshapes),
                        jax.tree.leaves(psh, is_leaf=lambda x: isinstance(x, NamedSharding))):
        assert isinstance(sh, NamedSharding)
        assert len([a for a in sh.spec if a is not None]) <= len(leaf.shape)


def test_divisibility_on_production_axis_sizes():
    """Specs must divide evenly for the production model-axis width (16):
    build against an AbstractMesh with the real (16, 16) shape and check
    every announced 'model'-sharded dim divides by 16, on FULL configs."""
    from jax.sharding import AbstractMesh
    for arch in C.ARCHS:
        cfg = C.get_config(arch)
        pshapes = jax.eval_shape(lambda c=cfg: M.init_lm(jax.random.PRNGKey(0), c))
        mesh = AbstractMesh((16, 16), ("data", "model"))
        psh = param_shardings(mesh, pshapes, fsdp=False)
        flat_shapes = jax.tree_util.tree_flatten_with_path(pshapes)[0]
        flat_specs = jax.tree.leaves(psh, is_leaf=lambda x: isinstance(x, NamedSharding))
        for (path, leaf), sh in zip(flat_shapes, flat_specs):
            for dim, axis in enumerate(sh.spec):
                if axis == "model":
                    assert leaf.shape[dim] % 16 == 0, (arch, path, leaf.shape, dim)


def test_opt_shardings_follow_params():
    cfg = C.get_config("smollm-135m").reduced()
    mesh = tiny_mesh()
    pshapes = jax.eval_shape(lambda: M.init_lm(jax.random.PRNGKey(0), cfg))
    oshapes = jax.eval_shape(lambda: adamw_init(pshapes))
    osh = opt_shardings(mesh, oshapes, fsdp=False)
    assert len(jax.tree.leaves(osh, is_leaf=lambda x: isinstance(x, NamedSharding))) == \
        len(jax.tree.leaves(oshapes))


@pytest.mark.parametrize("shape_name", list(INPUT_SHAPES))
def test_batch_shardings_build(shape_name):
    cfg = C.get_config("smollm-135m")
    shape = INPUT_SHAPES[shape_name]
    mesh = tiny_mesh()
    specs = make_batch_specs(cfg, shape)
    bsh = batch_shardings(mesh, specs, shape)
    assert set(bsh) == set(specs)


def test_cache_shardings_long_context_seq_parallel():
    """long_500k (batch=1): KV cache must shard sequence, not batch."""
    cfg = C.get_config("smollm-135m")
    shape = INPUT_SHAPES["long_500k"]
    mesh = tiny_mesh()
    cshapes = jax.eval_shape(lambda: M.make_caches(cfg, 1, 16384, jnp.bfloat16))
    csh = cache_shardings(mesh, cshapes, shape, cfg)
    found_seq_shard = False
    flat = jax.tree_util.tree_flatten_with_path(cshapes)[0]
    specs = jax.tree.leaves(csh, is_leaf=lambda x: isinstance(x, NamedSharding))
    for (path, leaf), sh in zip(flat, specs):
        keys = "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
        if "k" in keys.split("/")[-1] or "v" in keys.split("/")[-1]:
            if any(a == "data" for a in sh.spec):
                found_seq_shard = True
    assert found_seq_shard
