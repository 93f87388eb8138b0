"""Interpret-mode parity coverage for the batch-grid protocol kernels.

The sweep engine's TPU data plane (``support_margin`` batched kernels, the
``median_cut`` scan, and the fused MAXMARG support/violation kernel) must be
testable in CPU CI, not just on TPU hardware.  This module forces Pallas
interpretation — ``pltpu.force_tpu_interpret_mode`` plus per-call
``interpret=True`` — and checks every kernel
against its pure-jnp oracle on engine-shaped inputs (label-0 padding rows,
disallowed directions, ±inf range sentinels).

These tests run in the CI ``bench-smoke`` job alongside the BENCH schema
gate, so a kernel regression cannot hide behind a TPU-only test plan.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ops, ref


def _sweep_inputs(B=4, m=96, n=200, d=2, seed=7):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    V = jax.random.normal(ks[0], (m, d))
    V = V / jnp.linalg.norm(V, axis=1, keepdims=True)
    X = jax.random.normal(ks[1], (B, n, d))
    y = jnp.where(jax.random.bernoulli(ks[2], 0.5, (B, n)), 1, -1)
    y = y * jax.random.bernoulli(ks[3], 0.8, (B, n))     # label-0 pads
    ok = jax.random.bernoulli(ks[4], 0.7, (B, m))
    lo = jnp.where(jax.random.bernoulli(ks[5], 0.8, (B, m)),
                   jax.random.normal(ks[5], (B, m)), -jnp.inf)
    hi = jnp.where(jax.random.bernoulli(ks[4], 0.8, (B, m)),
                   lo + jax.random.uniform(ks[1], (B, m)), jnp.inf)
    return V, X, y, ok, lo, hi


def test_threshold_ranges_batched_interpret():
    V, X, y, *_ = _sweep_inputs()
    with pltpu.force_tpu_interpret_mode():
        lo, hi = ops.support_ranges_batch(V, X, y, interpret=True)
    loe, hie = ref.threshold_ranges_batch_ref(V, X, y)
    for got, want in ((lo, loe), (hi, hie)):
        fin = np.isfinite(np.asarray(want))
        np.testing.assert_allclose(np.asarray(got)[fin],
                                   np.asarray(want)[fin], rtol=1e-5)


def test_uncertain_mask_batched_interpret():
    V, X, y, ok, lo, hi = _sweep_inputs()
    with pltpu.force_tpu_interpret_mode():
        mask = ops.support_uncertain_batch(V, ok, lo, hi, X, y,
                                           interpret=True)
    want = ref.uncertain_mask_batch_ref(V, ok, lo, hi, X, y)
    assert bool(jnp.all(mask == want))


def test_median_cut_batched_interpret_bit_for_bit():
    V, X, y, ok, lo, hi = _sweep_inputs()
    with pltpu.force_tpu_interpret_mode():
        got = ops.support_median_cut_batch(V, ok.astype(jnp.float32), lo, hi,
                                           X, y, interpret=True)
    want = ref.median_cut_scores_batch_ref(V, ok, lo, hi, X, y)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_median_extremes_batched_interpret_bit_for_bit():
    """The MEDIAN hot path's fill-capped per-turn extremes kernel: integer
    row choices must match the jnp reference exactly, including the
    absent-class and fully-padded-node fallbacks."""
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    B, k, nW, d = 4, 3, 60, 2
    XW = jax.random.normal(ks[0], (B, k, nW, d))
    yW = jnp.where(jax.random.bernoulli(ks[1], 0.5, (B, k, nW)), 1, -1)
    yW = yW * jax.random.bernoulli(ks[2], 0.8, (B, k, nW))  # label-0 pads
    yW = yW.at[0, 0].set(1)      # a node with no negative class
    yW = yW.at[1, 2].set(0)      # a fully padded node
    v = jax.random.normal(ks[3], (B, d))
    with pltpu.force_tpu_interpret_mode():
        got = ops.support_extremes_batch(v, XW, yW, interpret=True)
    want = ref.median_extremes_batch_ref(v, XW, yW)
    for g, e in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(e))


@pytest.mark.parametrize("max_support,viol_ship", [(4, 2), (8, 2), (2, 1)])
def test_maxmarg_turn_scan_interpret_bit_for_bit(max_support, viol_ship):
    ks = jax.random.split(jax.random.PRNGKey(11), 8)
    B, N, k, n, d = 5, 72, 3, 40, 2
    K = jax.random.normal(ks[0], (B, N, d))
    yK = jnp.where(jax.random.bernoulli(ks[1], 0.5, (B, N)), 1, -1)
    yK = yK * jax.random.bernoulli(ks[2], 0.8, (B, N))
    X = jax.random.normal(ks[3], (B, k, n, d))
    y = jnp.where(jax.random.bernoulli(ks[4], 0.5, (B, k, n)), 1, -1)
    y = y * jax.random.bernoulli(ks[5], 0.8, (B, k, n))
    w = jax.random.normal(ks[6], (B, d))
    b = jax.random.normal(ks[7], (B,))
    with pltpu.force_tpu_interpret_mode():
        got = ops.support_violation_batch(
            w, b, K, yK, X, y, max_support=max_support, viol_ship=viol_ship,
            interpret=True)
    want = ref.maxmarg_turn_batch_ref(
        w, b, K, yK, X, y, max_support=max_support, viol_ship=viol_ship)
    for g, e in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(e))


def _pegasos_inputs(B, N, d, seed=3, found_frac=0.3):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    X = jax.random.normal(ks[0], (B, N, d), jnp.float32)
    y = jnp.where(jax.random.bernoulli(ks[1], 0.5, (B, N)), 1.0, -1.0)
    y = y * jax.random.bernoulli(ks[2], 0.85, (B, N))    # label-0 pads
    nv = jnp.maximum(jnp.sum(y != 0, axis=1), 1).astype(jnp.float32)
    w = jnp.zeros((B, d), jnp.float32)
    b = jnp.zeros((B,), jnp.float32)
    lam = jnp.full((B,), 1e-2, jnp.float32)
    found = jax.random.bernoulli(ks[3], found_frac, (B,))
    w_best = jax.random.normal(ks[4], (B, d), jnp.float32)
    b_best = jax.random.normal(ks[5], (B,), jnp.float32)
    return X, y, nv, w, b, lam, found, w_best, b_best


def test_pegasos_stage_interpret_bit_for_bit():
    """Lane-aligned point axis + single N-tile: the kernel's op sequence is
    exactly the jnp twin's (points sit on the 128-lane axis, so N=128 needs
    no padding), and every output (including the fused latch) must match
    bit-for-bit through the interpreter."""
    args = _pegasos_inputs(B=6, N=128, d=8)
    want = ref.pegasos_stage_batch_ref(*args, nsteps=60)
    with pltpu.force_tpu_interpret_mode():
        got = ops.pegasos_stage(*args, nsteps=60, use_pallas=True,
                                interpret=True, block_b=8, block_n=128,
                                unroll=1)
    for g, e in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(e))


def test_pegasos_stage_interpret_tiled_grid():
    """Multi-block grid with unaligned N: the VMEM gradient accumulation
    across N-tiles and the lane padding of the point axis reassociate the
    sums, so floats are allclose while the latch decisions (found / which
    w_best was taken) stay bit-equal."""
    args = _pegasos_inputs(B=5, N=70, d=12, seed=9)
    want = ref.pegasos_stage_batch_ref(*args, nsteps=60)
    with pltpu.force_tpu_interpret_mode():
        got = ops.pegasos_stage(*args, nsteps=60, use_pallas=True,
                                interpret=True, block_b=2, block_n=16,
                                unroll=1)
    names = ("w", "b", "mmin", "found", "w_best", "b_best")
    for name, g, e in zip(names, got, want):
        if name == "found":
            np.testing.assert_array_equal(np.asarray(g), np.asarray(e))
        else:
            np.testing.assert_allclose(np.asarray(g), np.asarray(e),
                                       rtol=1e-5, atol=1e-6)


def test_pegasos_stage_interpret_warm_offset_and_latch():
    """t0 (the warm polish eta offset) threads through both paths
    identically, and an already-latched instance's w_best is never
    overwritten by a later separating stage."""
    args = _pegasos_inputs(B=4, N=128, d=8, seed=5, found_frac=1.0)
    want = ref.pegasos_stage_batch_ref(*args, nsteps=40, t0=1024.0)
    with pltpu.force_tpu_interpret_mode():
        got = ops.pegasos_stage(*args, nsteps=40, t0=1024.0,
                                use_pallas=True, interpret=True,
                                block_b=8, block_n=128, unroll=1)
    for g, e in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(e))
    # all instances entered latched -> w_best must be the input w_best
    np.testing.assert_array_equal(np.asarray(got[4]), np.asarray(args[7]))
    np.testing.assert_array_equal(np.asarray(got[3]),
                                  np.ones(4, bool))
