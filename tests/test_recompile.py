"""Recompile-count regression gate for the hot loop (ISSUE 6 satellite).

The compacted hot path promises its compile cache keys only on
``(n_pad, width, warm)`` (plus the constant-folded first turn): widths are
monotone per sweep and batch padding is quantized, so a multi-epoch sweep
compiles a handful of step variants and *re-running the same sweep compiles
nothing*.  Every compacted dispatch appends its key to
``hotloop.KEY_LOG``; this module pins

* lowering count ≤ distinct logged keys (no hidden cache dimension — e.g.
  shard-aware padding reintroducing a per-turn recompile), and
* a second identical sweep adds zero lowerings (perfect cross-sweep reuse).

Counts come from the jit caches themselves (``_cache_size()``), so the gate
holds for whatever the dispatches lower, not a wrapper's opinion of it.
"""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

from repro import engine
from repro.core import datasets
from repro.engine import hotloop, maxmarg, median, unified

N_ANGLES = 256
MAX_EPOCHS = 24
_GENS = (datasets.data1, datasets.data2, datasets.data3)


def _grid(n, selector="median"):
    """Staggered convergence (mixed datasets/eps/seeds) so the sweep walks
    several width buckets and batch-compaction sizes."""
    return [engine.ProtocolInstance(
        _GENS[i % 3](n_per_node=40, k=2, seed=i),
        (0.1, 0.05, 0.02)[i % 3], selector) for i in range(n)]


def _lowerings(family):
    """Lowerings of a family's single-device programs (full and sub turn)
    as the factory memoises them."""
    return sum(f._cache_size() for f in hotloop.programs(family))


def _median_lowerings():
    return _lowerings(median.FAMILY)


def _maxmarg_lowerings():
    return _lowerings(maxmarg.FAMILY)


def _unified_lowerings():
    return _lowerings(unified.FAMILY)


def test_median_cache_keys_only_on_npad_width_warm():
    jax.clear_caches()
    hotloop.KEY_LOG.clear()
    # tight near-margin bands → multi-turn sweeps that walk several width
    # buckets and batch-compaction sizes
    insts = [engine.ProtocolInstance(
        datasets.data_mixed_hardness(n_per_node=60, k=4, seed=s), eps)
        for s in range(5) for eps in (0.05, 0.02)]
    first = engine.run_instances(insts, n_angles=N_ANGLES,
                                 max_epochs=MAX_EPOCHS)
    keys = set(hotloop.KEY_LOG)
    assert len(keys) >= 3, "grid too easy to exercise the cache"
    n_low = _median_lowerings()
    assert 0 < n_low <= len(keys), (n_low, sorted(keys))

    # identical sweep again: every dispatch hits the cache
    hotloop.KEY_LOG.clear()
    second = engine.run_instances(insts, n_angles=N_ANGLES,
                                  max_epochs=MAX_EPOCHS)
    assert set(hotloop.KEY_LOG) == keys
    assert _median_lowerings() == n_low, "re-running the same sweep recompiled"
    for a, b in zip(first, second):
        assert a.comm == b.comm and a.rounds == b.rounds


def test_maxmarg_cache_keys_only_on_npad_width_warm():
    jax.clear_caches()
    hotloop.KEY_LOG.clear()
    insts = _grid(10, selector="maxmarg")
    engine.maxmarg.run_instances(insts, max_epochs=MAX_EPOCHS)
    keys = set(hotloop.KEY_LOG)
    n_low = _maxmarg_lowerings()
    assert 0 < n_low <= len(keys), (n_low, sorted(keys))
    # the warm gate is part of the key: both branches may appear, nothing else
    assert all(isinstance(w, (bool, np.bool_)) for (_, _, w, _) in keys)

    hotloop.KEY_LOG.clear()
    engine.maxmarg.run_instances(insts, max_epochs=MAX_EPOCHS)
    assert _maxmarg_lowerings() == n_low, \
        "re-running the same sweep recompiled"


def test_unified_mixed_cache_ignores_selector_mix():
    """The unified dispatch's whole point: the compiled variants key on
    shapes and statics, NEVER on which rows run which protocol — so a
    permuted admission order of the same mixed grid (different selector
    interleaving, same per-row data) adds zero lowerings."""
    jax.clear_caches()
    hotloop.KEY_LOG.clear()
    insts = [engine.ProtocolInstance(
        _GENS[i % 3](n_per_node=40, k=2, seed=i),
        (0.1, 0.05, 0.05)[i % 3],
        ("median", "maxmarg", "sampling")[i % 3], seed=i)
        for i in range(9)]
    first = engine.run_sweep(insts, n_angles=64, max_epochs=8,
                             unified_dispatch=True)
    keys = set(hotloop.KEY_LOG)
    n_low = _unified_lowerings()
    assert 0 < n_low <= len(keys), (n_low, sorted(keys))

    # reversed mix: the hot loop's width/compaction choices are functions
    # of the *set* of live rows, so every dispatch hits the cache
    hotloop.KEY_LOG.clear()
    perm = list(reversed(insts))
    second = engine.run_sweep(perm, n_angles=64, max_epochs=8,
                              unified_dispatch=True)
    assert set(hotloop.KEY_LOG) == keys
    assert _unified_lowerings() == n_low, \
        "re-ordering the selector mix recompiled"
    for a, b in zip(first, reversed(second)):
        assert a.comm == b.comm and a.rounds == b.rounds


def test_unified_pool_single_pinned_key_zero_steady_recompiles():
    """ISSUE 10 acceptance: a mixed MEDIAN+MAXMARG+SAMPLING stream through
    ONE SessionPool uses one pinned dispatch key, and a second pool with a
    different admission order adds zero lowerings."""
    from repro.engine.session_pool import PoolConfig, SessionPool

    jax.clear_caches()
    hotloop.KEY_LOG.clear()
    insts = [engine.ProtocolInstance(
        _GENS[i % 3](n_per_node=16, k=2, seed=i),
        (0.1, 0.05, 0.05)[i % 3],
        ("median", "maxmarg", "sampling")[i % 3], seed=i)
        for i in range(6)]

    def run(order):
        pool = SessionPool(PoolConfig(slots=4, k=2, n_pad=16,
                                      selector="unified", n_angles=64,
                                      max_epochs=8))
        for inst in order:
            pool.submit(inst.shards, eps=inst.eps, selector=inst.selector,
                        seed=inst.seed)
        pool.run()
        return pool

    run(insts)
    keys = set(hotloop.KEY_LOG)
    assert len(keys) == 1, sorted(keys)     # the single pinned dispatch key
    n_low = hotloop.programs(unified.FAMILY)[1]._cache_size()
    assert n_low == 1

    hotloop.KEY_LOG.clear()
    run(list(reversed(insts)))
    assert set(hotloop.KEY_LOG) == keys
    assert hotloop.programs(unified.FAMILY)[1]._cache_size() == n_low, \
        "a second mixed pool recompiled"


@pytest.mark.skipif(len(jax.devices()) < 2,
                    reason="sharded recompile gate needs >1 device")
def test_sharded_cache_keys_stable():
    """The shard-balanced index pads every slice to a common L, so the
    sharded sub-dispatch keys on (S·L, width, warm) exactly like the
    single-device path keys on n_pad — and a rerun compiles nothing."""
    from repro.launch.mesh import make_data_mesh

    from repro.engine.state import shard_specs

    mesh = make_data_mesh()
    jax.clear_caches()
    hotloop.KEY_LOG.clear()
    insts = _grid(len(mesh.devices) + 3)
    engine.run_instances(insts, n_angles=N_ANGLES, max_epochs=MAX_EPOCHS,
                         mesh=mesh)
    keys = set(hotloop.KEY_LOG)
    S = len(mesh.devices)
    # every key's n_pad is a whole number of equal per-shard slices
    assert all(n_pad % S == 0 for (n_pad, _w, _warm, _first) in keys), keys

    # the factory caches per (mesh, specs, opts, donate) — re-resolving with
    # the sweep's own arguments returns the very jits the run used
    data, state0, k, _cap = engine.pack_instances(
        insts, n_angles=N_ANGLES, max_epochs=MAX_EPOCHS, mesh=mesh)
    opts = dict(k=k, cut_kernel=False, extremes_kernel=False)
    full_j, sub_j = hotloop.programs(
        median.FAMILY, True, mesh, shard_specs(data), shard_specs(state0),
        tuple(sorted(opts.items())))
    n_low = full_j._cache_size() + sub_j._cache_size()
    assert 0 < n_low <= len(keys), (n_low, sorted(keys))

    hotloop.KEY_LOG.clear()
    engine.run_instances(insts, n_angles=N_ANGLES, max_epochs=MAX_EPOCHS,
                         mesh=mesh)
    assert set(hotloop.KEY_LOG) == keys
    assert full_j._cache_size() + sub_j._cache_size() == n_low, \
        "re-running the same sharded sweep recompiled"
