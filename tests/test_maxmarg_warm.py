"""Differential gate for the MAXMARG hot path (warm-started, compacted
refits) against the cold padded execution model.

The hard-margin optimum each turn is determined by the transcript alone, so
warm-starting (polishing the previous turn's separator) and compaction
(solving at the live transcript width, dropping finished instances) may only
change *solve cost*, never a protocol decision.  This module pins that down:
across the engine test grid, warm+compacted and cold+padded runs must agree
exactly on comm totals, rounds, and convergence, and produce the same final
separator up to canonicalization.
"""

import os
import sys

import numpy as np
import jax.numpy as jnp
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from repro import engine
from repro.core import classifiers as clf
from repro.core import datasets

MAX_EPOCHS = 24


def _grid():
    """The engine MAXMARG test grid (same as tests/test_engine_maxmarg.py)."""
    out = []
    for gen in (datasets.data1, datasets.data2, datasets.data3):
        for eps in (0.05, 0.02):
            for seed in (0, 1):
                out.append(engine.ProtocolInstance(
                    gen(n_per_node=100, k=2, seed=seed), eps, "maxmarg"))
    return out


def _canon(h):
    """Canonical direction of a separator: unit-norm augmented (w, b)."""
    v = np.concatenate([h.w, [h.b]])
    return v / (np.linalg.norm(v) + 1e-30)


@pytest.fixture(scope="module")
def warm_cold_runs():
    insts = _grid()
    hot = engine.maxmarg.run_instances(insts, max_epochs=MAX_EPOCHS,
                                       warm=True, compact=True)
    cold = engine.maxmarg.run_instances(insts, max_epochs=MAX_EPOCHS,
                                        warm=False, compact=False)
    return insts, hot, cold


def test_warm_cold_identical_comm_rounds_convergence(warm_cold_runs):
    insts, hot, cold = warm_cold_runs
    assert len(insts) >= 12
    for i, (rh, rc) in enumerate(zip(hot, cold)):
        assert rh.comm == rc.comm, (i, rh.comm, rc.comm)
        assert rh.rounds == rc.rounds, i
        assert rh.converged == rc.converged and rh.converged, i


def test_warm_cold_same_separator_up_to_canonicalization(warm_cold_runs):
    """Both paths approximate the same transcript-determined hard-margin
    optimum; after canonicalization the directions must agree closely and
    predict identically on every shard."""
    insts, hot, cold = warm_cold_runs
    for inst, rh, rc in zip(insts, hot, cold):
        vh, vc = _canon(rh.classifier), _canon(rc.classifier)
        assert abs(float(vh @ vc)) > 1.0 - 1e-4, (vh, vc)
        X = np.concatenate([s[0] for s in inst.shards])
        np.testing.assert_array_equal(rh.classifier.predict(X),
                                      rc.classifier.predict(X))


def test_warm_cold_parity_kparty():
    """k=4 multi-party case — the regime where the warm polish actually
    engages (a later coordinator's shard is often already cleanly
    classified)."""
    for seed, eps in ((0, 0.1), (1, 0.05)):
        shards = datasets.data3(n_per_node=75, k=4, seed=seed)
        inst = [engine.ProtocolInstance(shards, eps, "maxmarg")]
        rh = engine.maxmarg.run_instances(inst, max_epochs=MAX_EPOCHS,
                                          warm=True, compact=True)[0]
        rc = engine.maxmarg.run_instances(inst, max_epochs=MAX_EPOCHS,
                                          warm=False, compact=False)[0]
        assert rh.comm == rc.comm
        assert rh.rounds == rc.rounds and rh.converged == rc.converged


def test_compaction_alone_is_decision_exact():
    """Width+batch compaction without warm-starting: same decisions as the
    cold padded path (only float reassociation across padding changes)."""
    insts = _grid()[:6]
    comp = engine.maxmarg.run_instances(insts, max_epochs=MAX_EPOCHS,
                                        warm=False, compact=True)
    cold = engine.maxmarg.run_instances(insts, max_epochs=MAX_EPOCHS,
                                        warm=False, compact=False)
    for rh, rc in zip(comp, cold):
        assert rh.comm == rc.comm
        assert rh.rounds == rc.rounds and rh.converged == rc.converged


def test_solver_warm_entry_with_untrusted_init_is_cold_bit_for_bit():
    """The warm entry's fall-through: when no instance may latch
    (warm_ok=False), the anneal from zeros must be bit-identical to the
    cold entry — the polish only ever *adds* a latched prefix."""
    rng = np.random.default_rng(0)
    w_true = np.array([1.0, -0.5]) / np.linalg.norm([1.0, -0.5])
    X = rng.normal(size=(120, 2)).astype(np.float32)
    X = X[np.abs(X @ w_true) > 0.2]
    y = np.where(X @ w_true > 0, 1.0, -1.0).astype(np.float32)
    Xb, yb = jnp.asarray(X[None]), jnp.asarray(y[None])
    w_c, b_c, ok_c = clf._svm_solve_batch(Xb, yb, jnp.float32(1e-3), 500, 2)
    w_w, b_w, ok_w = clf._svm_solve_batch(
        Xb, yb, jnp.float32(1e-3), 500, 2,
        w0=jnp.asarray(rng.normal(size=(1, 2)), jnp.float32),
        b0=jnp.zeros((1,), jnp.float32),
        warm_ok=jnp.zeros((1,), bool))
    assert bool(ok_c[0]) and bool(ok_w[0])
    np.testing.assert_array_equal(np.asarray(w_c), np.asarray(w_w))
    np.testing.assert_array_equal(np.asarray(b_c), np.asarray(b_w))


def test_solver_polish_latches_clean_carried_separator():
    """A clean carried separator must latch through the polish (skipping
    every annealing stage) with preserved margin quality."""
    rng = np.random.default_rng(3)
    n = 150
    Xp = np.stack([-0.5 - rng.random(n), rng.normal(0, 2.0, n)], axis=1)
    Xn = np.stack([+0.5 + rng.random(n), rng.normal(0, 2.0, n)], axis=1)
    X = np.concatenate([Xp, Xn]).astype(np.float32)
    y = np.concatenate([np.ones(n), -np.ones(n)]).astype(np.float32)
    w0, b0, ok0 = clf.anneal_hard_margin(X, y)
    assert ok0
    Xb, yb = jnp.asarray(X[None]), jnp.asarray(y[None])
    w, b, ok = clf._svm_solve_batch(
        Xb, yb, jnp.float32(1e-3), 2000, 3,
        w0=jnp.asarray(w0[None], jnp.float32),
        b0=jnp.asarray([b0], jnp.float32),
        warm_ok=jnp.ones((1,), bool))
    assert bool(ok[0])
    m = y * (X @ np.asarray(w[0], np.float64) + float(b[0]))
    assert m.min() > 0                       # still separates
    geo = m.min() / np.linalg.norm(np.asarray(w[0]))
    assert geo >= 0.9 * 0.5                  # margin quality preserved


def test_per_node_latches_end_to_end_with_identical_decisions():
    """A multi-epoch k-party sweep where the per-node warm carry actually
    latches (an easy node adopts a clean mid-epoch proposal and polishes
    from it at its next turn) — and every protocol decision still matches
    the cold padded model."""
    inst = [engine.ProtocolInstance(
        datasets.data_mixed_hardness(seed=0), 0.05, "maxmarg")]
    rp = engine.maxmarg.run_instances(inst, max_epochs=6)[0]
    rs = engine.maxmarg.run_instances(inst, max_epochs=6, per_node=False)[0]
    rc = engine.maxmarg.run_instances(inst, max_epochs=6,
                                      warm=False, compact=False)[0]
    assert rp.rounds >= 2, "grid must be multi-epoch for carries to exist"
    assert rp.extra["warm_latches"] >= 1, "per-node polish never latched"
    assert rp.extra["warm_latches"] >= rs.extra["warm_latches"]
    for r in (rp, rs):
        assert r.comm == rc.comm
        assert r.rounds == rc.rounds and r.converged == rc.converged
    assert rp.converged


def test_per_node_latch_where_single_carry_provably_falls_through():
    """The step-level differential the per-node upgrade exists for: a
    crafted mid-protocol state whose coordinator carries a *verified-clean*
    separator (per-node mode) while the previous turn's proposal (the
    single-carry init) misclassifies its fit set.  The per-node polish must
    latch, the single-carry path must fall through to the cold anneal, the
    latch counters must differ — and every protocol decision (comm deltas,
    transcript appends, termination) must be identical across per-node,
    single-carry, and fully cold execution."""
    from repro.engine import hotloop, maxmarg as MM

    rng = np.random.default_rng(5)
    half = 30
    shards = []
    for cx in (-1.0, 0.0, 1.0):     # three easy blob pairs, separator x=0
        Xp = np.stack([rng.uniform(-2.0, -0.6, half),
                       rng.uniform(cx - 0.5, cx + 0.5, half)], 1)
        Xn = np.stack([rng.uniform(0.6, 2.0, half),
                       rng.uniform(cx - 0.5, cx + 0.5, half)], 1)
        X = np.concatenate([Xp, Xn]).astype(np.float32)
        y = np.concatenate([np.ones(half), -np.ones(half)]).astype(np.int32)
        shards.append((X, y))
    inst = [engine.ProtocolInstance(shards, 0.05, "maxmarg")]
    data, state0, k, _cap = engine.pack_instances_maxmarg(
        inst, max_epochs=8, max_support=4)

    # mid-protocol: node 0 holds two received support points, turn 3 (its
    # second coordination), carries the true separator as verified-clean;
    # the "previous turn's proposal" is orthogonal — dirty on everything
    wx = np.asarray(state0.wx).copy()
    wy = np.asarray(state0.wy).copy()
    w_fill = np.asarray(state0.w_fill).copy()
    wx[0, 0, 0], wy[0, 0, 0] = (-0.7, 0.3), 1
    wx[0, 0, 1], wy[0, 0, 1] = (0.7, -0.3), -1
    w_fill[0, 0] = 2
    base = state0._replace(
        wx=jnp.asarray(wx), wy=jnp.asarray(wy), w_fill=jnp.asarray(w_fill),
        turn=jnp.full((1,), 3, jnp.int32),   # per-instance (B,) turn
        h_w=jnp.asarray([[0.0, 1.0]], jnp.float32),      # dirty prev proposal
        h_b=jnp.zeros((1,), jnp.float32),
        h_valid=jnp.ones((1,), bool),
        warm_turn=jnp.ones((1,), bool),                  # host would attempt
        c_w=jnp.asarray(np.broadcast_to(
            np.asarray([[-1.0, 0.0]], np.float32)[:, None], (1, 3, 2)).copy()),
        c_b=jnp.zeros((1, 3), jnp.float32),
        c_valid=jnp.ones((1, 3), bool),
        warm_node=jnp.ones((1, 3), bool))

    opts = dict(k=k, max_support=4, steps=500, stages=2, lam0=1e-3,
                trans_width=None, fused_kernel=False)
    step_j = hotloop.programs(MM.FAMILY)[0]
    pn = step_j(data, base, warm=True, per_node=True, **opts)
    sg = step_j(data, base, warm=True, per_node=False, **opts)
    cold = step_j(data, base, warm=False, per_node=True, **opts)

    assert int(pn.latches[0]) == 1          # clean carry -> polish latch
    assert int(sg.latches[0]) == 0          # dirty init -> provable gate fail
    assert int(cold.latches[0]) == 0
    for other in (sg, cold):
        for a, b in zip(pn.comm, other.comm):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(np.asarray(pn.wy), np.asarray(other.wy))
        np.testing.assert_array_equal(np.asarray(pn.w_fill),
                                      np.asarray(other.w_fill))
        assert bool(pn.done[0]) == bool(other.done[0])
        assert bool(pn.converged[0]) == bool(other.converged[0])


def test_hot_path_is_default_and_flagged():
    shards = datasets.data1(n_per_node=80, k=2, seed=0)
    r = engine.maxmarg.run_instances(
        [engine.ProtocolInstance(shards, 0.05, "maxmarg")])[0]
    assert r.extra["warm"] and r.extra["compact"]
    assert r.extra["per_node"] and "warm_latches" in r.extra
    r_cold = engine.maxmarg.run_instances(
        [engine.ProtocolInstance(shards, 0.05, "maxmarg")],
        warm=False, compact=False)[0]
    assert not r_cold.extra["warm"] and not r_cold.extra["compact"]
    assert r.comm == r_cold.comm


def test_run_sweep_accepts_warm_compact_options():
    shards = datasets.data1(n_per_node=60, k=2, seed=1)
    insts = [engine.ProtocolInstance(shards, 0.05, "maxmarg")]
    r_hot = engine.run_sweep(insts, warm=True, compact=True)[0]
    r_cold = engine.run_sweep(insts, warm=False, compact=False)[0]
    assert r_hot.comm == r_cold.comm


def test_solver_kernel_warm_cold_decisions_bit_exact():
    """The tiled-solver dispatch (`solver_kernel=True`; jnp twin on CPU)
    must leave every MAXMARG protocol decision bit-exact — against its own
    warm/cold pair AND against the default classic-solver run.  This is the
    engine-level acceptance gate for `_svm_solve_batch(kernel=True)`: a
    solver path that changed comm, rounds or convergence anywhere on the
    paper grid would be a different protocol, not a faster solver."""
    insts = _grid()[:6]
    hot_k = engine.maxmarg.run_instances(insts, max_epochs=MAX_EPOCHS,
                                         warm=True, compact=True,
                                         solver_kernel=True)
    cold_k = engine.maxmarg.run_instances(insts, max_epochs=MAX_EPOCHS,
                                          warm=False, compact=False,
                                          solver_kernel=True)
    classic = engine.maxmarg.run_instances(insts, max_epochs=MAX_EPOCHS,
                                           warm=True, compact=True,
                                           solver_kernel=False)
    for i, (rh, rc, rd) in enumerate(zip(hot_k, cold_k, classic)):
        assert rh.comm == rc.comm == rd.comm, i
        assert rh.rounds == rc.rounds == rd.rounds, i
        assert rh.converged and rc.converged and rd.converged, i
        # same decision boundary direction across all three runs
        ch, cc, cd = _canon(rh.classifier), _canon(rc.classifier), \
            _canon(rd.classifier)
        assert min(abs(float(ch @ cc)), abs(float(ch @ cd))) > 1.0 - 1e-4, i


def test_solver_kernel_highd_sweep_converges():
    """The d ≫ 2 regime the kernel targets, end-to-end through the engine:
    a d=16 separable sweep with solver_kernel on/off converges identically
    (decision-exact), exercising the bucketed high-d dispatch path."""
    insts = [engine.ProtocolInstance(
        datasets.data_highd(n_per_node=80, k=2, d=16, seed=s, margin=0.2),
        0.05, "maxmarg") for s in (0, 1)]
    rk = engine.maxmarg.run_instances(insts, max_epochs=MAX_EPOCHS,
                                      solver_kernel=True)
    rc = engine.maxmarg.run_instances(insts, max_epochs=MAX_EPOCHS,
                                      solver_kernel=False)
    for i, (a, b) in enumerate(zip(rk, rc)):
        assert a.converged and b.converged, i
        assert a.comm == b.comm and a.rounds == b.rounds, i
