"""Compile the protocol kernels for a described TPU v5e chip.

Interpret mode (tests/test_kernels*.py) checks what the kernels compute;
it cannot see what the chip's compiler refuses — block shapes that break
the (8, 128) tiling rule, primitives Mosaic has no lowering for, VMEM
overuse.  These tests lower each kernel of the protocol path at real
widths (B=32 sessions, n=2048 points per node, d ∈ {2, 10}) against a
*described* ``v5e:2x2`` topology and compile it with the TPU compiler that
ships with jaxlib, without a chip attached, then check that the compiled
program holds the Mosaic kernel (``tpu_custom_call``).  One more test
compiles a MAXMARG pool's pinned dispatch, which must carry the Pegasos
solver kernel.

The topology is described inside a module-scoped fixture (never while a
module is imported), and the persistent compilation cache is off around
these compiles: a compile for a described chip cannot be read back here.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.analysis import autotune
from repro.kernels import ops

B, N_PTS, M_DIRS, K_NODES = 32, 2048, 1024, 4
TRANSCRIPT = 256          # transcript rows appended to a fit set
V5E = "TPU v5 lite"


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


@pytest.mark.parametrize("d", [2, 10])
def test_threshold_ranges_compiles(one_chip, d):
    s = lambda *a, **k: _sds(one_chip, *a, **k)  # noqa: E731
    txt = _compiled_text(
        lambda V, X, y: ops.support_ranges_batch(V, X, y, interpret=False),
        s((M_DIRS, d)), s((B, N_PTS, d)), s((B, N_PTS), jnp.int32))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("d", [2, 10])
def test_uncertain_mask_compiles(one_chip, d):
    s = lambda *a, **k: _sds(one_chip, *a, **k)  # noqa: E731
    txt = _compiled_text(
        lambda V, ok, lo, hi, X, y: ops.support_uncertain_batch(
            V, ok, lo, hi, X, y, interpret=False),
        s((M_DIRS, d)), s((B, M_DIRS), jnp.bool_), s((B, M_DIRS)),
        s((B, M_DIRS)), s((B, N_PTS, d)), s((B, N_PTS), jnp.int32))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("m", [256, M_DIRS])
def test_median_cut_compiles(one_chip, m):
    """At the pool's 256 directions and the sweeps' 1024 (where the point
    tile shrinks to keep the (m, block_n) planes in VMEM)."""
    s = lambda *a, **k: _sds(one_chip, *a, **k)  # noqa: E731
    txt = _compiled_text(
        lambda V, ok, lo, hi, X, y: ops.support_median_cut_batch(
            V, ok, lo, hi, X, y, interpret=False),
        s((m, 2)), s((B, m)), s((B, m)), s((B, m)),
        s((B, N_PTS, 2)), s((B, N_PTS), jnp.int32))
    assert "tpu_custom_call" in txt


def test_median_extremes_compiles(one_chip):
    s = lambda *a, **k: _sds(one_chip, *a, **k)  # noqa: E731
    nW = N_PTS + TRANSCRIPT
    txt = _compiled_text(
        lambda v, X, y: ops.support_extremes_batch(v, X, y, interpret=False),
        s((B, 2)), s((B, K_NODES, nW, 2)), s((B, K_NODES, nW), jnp.int32))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("d", [2, 10])
def test_maxmarg_turn_scan_compiles(one_chip, d):
    s = lambda *a, **k: _sds(one_chip, *a, **k)  # noqa: E731
    nK = N_PTS + TRANSCRIPT
    txt = _compiled_text(
        lambda w, b, K, yK, X, y: ops.support_violation_batch(
            w, b, K, yK, X, y, interpret=False),
        s((B, d)), s((B,)), s((B, nK, d)), s((B, nK), jnp.int32),
        s((B, K_NODES, N_PTS, d)), s((B, K_NODES, N_PTS), jnp.int32))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("d", [2, 10])
def test_pegasos_stage_compiles(one_chip, d):
    s = lambda *a, **k: _sds(one_chip, *a, **k)  # noqa: E731
    nK = N_PTS + TRANSCRIPT
    tile = autotune.lookup_tile(V5E, B, nK, d)

    def stage(X, y, nv, w, b, lam, found, wb, bb):
        return ops.pegasos_stage(
            X, y, nv, w, b, lam, found, wb, bb, nsteps=2000,
            use_pallas=True, interpret=False, block_b=tile.block_b,
            block_n=tile.block_n, unroll=tile.unroll)

    txt = _compiled_text(
        stage, s((B, nK, d)), s((B, nK)), s((B,)), s((B, d)), s((B,)),
        s((B,)), s((B,), jnp.bool_), s((B, d)), s((B,)))
    assert "tpu_custom_call" in txt


def test_maxmarg_pool_dispatch_compiles(one_chip, monkeypatch):
    """The served MAXMARG path: a d=10, k=4 pool's pinned turn, traced as
    the chip traces it (the backend probe answers "tpu"), must compile with
    the Pegasos solver kernel inside."""
    import numpy as np
    from repro.engine.session_pool import PoolConfig, SessionPool

    pool = SessionPool(PoolConfig(selector="maxmarg", k=K_NODES, d=10,
                                  n_pad=N_PTS, slots=B, solver_kernel=True))
    fn, args, kw = pool.turn_call(np.arange(B))
    shapes = jax.tree_util.tree_map(
        lambda a: _sds(one_chip, a.shape, a.dtype), args)
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    jax.clear_caches()          # no trace cached with the CPU answer
    try:
        txt = fn.lower(*shapes, **kw).compile().as_text()
    finally:
        jax.clear_caches()
    assert "tpu_custom_call" in txt
