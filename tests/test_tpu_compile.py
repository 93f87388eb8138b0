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


@pytest.mark.parametrize("n,d,path", [
    (8192 + 296, 10, "resident"),     # the closed MAXMARG cell's stage
    (40000, 64, "streamed"),          # over the resident VMEM budget
])
def test_pegasos_stage_path_compiles(one_chip, n, d, path):
    """Each path of the stage kernel compiles where the shape sends it."""
    s = lambda *a, **k: _sds(one_chip, *a, **k)  # noqa: E731
    ops.PEGASOS_PATH_LOG.clear()
    txt = _compiled_text(
        lambda *a: ops.pegasos_stage(*a, nsteps=2000, use_pallas=True,
                                     interpret=False),
        s((B, n, d)), s((B, n)), s((B,)), s((B, d)), s((B,)), s((B,)),
        s((B,), jnp.bool_), s((B, d)), s((B,)))
    assert "tpu_custom_call" in txt
    assert [p for *_, p in ops.PEGASOS_PATH_LOG] == [path]


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


@pytest.mark.parametrize("L", [32, 4])
def test_sharded_sub_turn_compiles(topo, one_chip, monkeypatch, L):
    """The four-chip MAXMARG sweep's sub-batch turn
    (``maxmarg._sharded_sub_turn`` over a ("data",) mesh of the described
    2x2 host; 128 instances, 32 a chip, 8192 points a node, d=10) at a
    local batch of L rows, with the turn-scan and Pegasos kernels on: it
    compiles, holds the kernels, and the Pegasos stage inside each shard
    takes the resident path."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding
    from repro.engine import maxmarg
    from repro.engine.state import (ProtocolInstance, pack_instances_maxmarg,
                                    shard_specs)

    n_pad, d, B, width = 8192, 10, 128, 40
    mesh = Mesh(np.array(topo.devices), ("data",))
    S = mesh.shape["data"]
    shard = (np.zeros((n_pad, d), np.float32), np.ones(n_pad, np.int32))
    data, state, k, _cap = pack_instances_maxmarg(
        [ProtocolInstance([shard] * K_NODES, 0.05, "maxmarg")],
        max_epochs=16, max_support=4)
    dspec, sspec = shard_specs(data), shard_specs(state)

    def shapes(tree, specs):
        return jax.tree_util.tree_map(
            lambda a, p: jax.ShapeDtypeStruct(
                (B,) + a.shape[1:] if a.ndim else a.shape, a.dtype,
                sharding=NamedSharding(mesh, p)), tree, specs)

    lanes = NamedSharding(mesh, jax.sharding.PartitionSpec("data"))
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    jax.clear_caches()          # no trace cached with the CPU answer
    ops.PEGASOS_PATH_LOG.clear()
    try:
        _full, sub = maxmarg._sharded_dispatches(
            mesh, dspec, sspec, (k, 4, 2000, 3, 1e-3, True, True), True)
        txt = sub.lower(
            shapes(data, dspec), shapes(state, sspec),
            jax.ShapeDtypeStruct((S * L,), jnp.int32, sharding=lanes),
            jax.ShapeDtypeStruct((S,), jnp.int32, sharding=lanes),
            trans_width=width, warm=True, per_node=True).compile().as_text()
    finally:
        jax.clear_caches()
    assert "tpu_custom_call" in txt
    assert "pegasos_stage_batched" in txt
    log = list(ops.PEGASOS_PATH_LOG)
    assert log and all(b == L and path == "resident"
                       for b, _n, _d, path in log), log


def test_sweep_slab_assembly_compiles(one_chip):
    """The four-chip sweep's batch assembly on one chip at the cell's
    per-chip shape (32 instances of k=4 shards, 8192 points a node, d=10):
    the upload buffers of (640, 128) f32 and (64, 128) i32 pieces become
    the engine's X and y, and its temporaries stay under one slab (no
    lane-padded d-minor copy of the whole slab)."""
    from repro.engine.state import _CHUNK_BYTES, _assemble_slab

    L, n_pad, d = 32, 8192, 10

    def buffers(rows, dtype):
        per = _CHUNK_BYTES // (rows * 128 * 4)
        return [_sds(one_chip, (min(per, L * K_NODES - i) * rows, 128), dtype)
                for i in range(0, L * K_NODES, per)]

    xs, ys = buffers(n_pad * d // 128, jnp.float32), buffers(64, jnp.int32)
    assert len(xs) == 3 and len(ys) == 1
    compiled = _assemble_slab.lower(xs, ys, k=K_NODES, n_pad=n_pad, d=d,
                                    rows=L).compile()
    X, y = compiled.out_info
    assert (X.shape, X.dtype) == ((L, K_NODES, n_pad, d), jnp.float32)
    assert (y.shape, y.dtype) == ((L, K_NODES, n_pad), jnp.int32)
    slab = L * K_NODES * n_pad * d * 4
    assert compiled.memory_analysis().temp_size_in_bytes < slab
