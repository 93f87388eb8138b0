"""Donation sanity gate (ISSUE 6 satellite).

Buffer donation lets the per-turn dispatch's scatter-back reuse the input
state's memory in place — but a donated handle is *invalidated*: touching it
afterwards raises.  The hot loop's contract (``hotloop.run_hot``) is a
strict single-consumer chain — each state handle feeds exactly one
dispatch, and the packed host view of a handle is enqueued before the
dispatch that donates it.  This module pins

* jax really does invalidate donated buffers on this backend (so the
  contract is load-bearing, not vacuous),
* a donated sweep runs end-to-end without a use-after-donate — with and
  without the double-buffered loop — and matches the non-donating default
  bit-for-bit (MEDIAN) / decision-for-decision (MAXMARG),
* the cold padded oracle is untouched by donated runs sharing the process.
"""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from repro import engine
from repro.core import datasets, geometry as geo
from repro.engine import hotloop, median, maxmarg

N_ANGLES = 256
MAX_EPOCHS = 24
_GENS = (datasets.data1, datasets.data2, datasets.data3)


def _grid(n, selector="median"):
    return [engine.ProtocolInstance(
        _GENS[i % 3](n_per_node=40, k=2, seed=i),
        (0.1, 0.05)[i % 2], selector) for i in range(n)]


def test_use_after_donate_raises():
    """A donated dispatch must invalidate its input state: reading any leaf
    of the consumed handle afterwards raises instead of silently aliasing.
    (If donation were silently ignored — e.g. numpy inputs — the in-place
    scatter-back would be a no-op copy and the perf win fictitious.)"""
    insts = _grid(4)
    data, st, k, cap = engine.pack_instances(
        insts, n_angles=N_ANGLES, max_epochs=MAX_EPOCHS)
    V = jnp.asarray(geo.direction_grid(N_ANGLES), jnp.float32)
    step_don, step_plain = (hotloop.programs(median.FAMILY, donate)[0]
                            for donate in (True, False))
    out = step_don(data, V, st, k=k, first_turn=True, cut_kernel=False,
                   extremes_kernel=False, trans_width=8)
    jax.block_until_ready(out.wx)
    with pytest.raises((RuntimeError, ValueError)):
        np.asarray(st.wx)
    # the non-donating twin leaves its input untouched (fresh pack — the
    # first handle is dead)
    _, st2, _, _ = engine.pack_instances(
        insts, n_angles=N_ANGLES, max_epochs=MAX_EPOCHS)
    step_plain(data, V, st2, k=k, first_turn=True, cut_kernel=False,
               extremes_kernel=False, trans_width=8)
    np.asarray(st2.wx)


def test_median_donated_sweep_bitexact():
    """donate=True (with and without the double-buffered loop) must complete
    without a use-after-donate and reproduce the default hot path exactly —
    the pin that the loop's single-consumer chain really holds."""
    insts = _grid(10)
    ref = engine.run_instances(insts, n_angles=N_ANGLES,
                               max_epochs=MAX_EPOCHS)
    for overlap in (False, True):
        don = engine.run_instances(insts, n_angles=N_ANGLES,
                                   max_epochs=MAX_EPOCHS,
                                   donate=True, overlap=overlap)
        for i, (a, b) in enumerate(zip(don, ref)):
            assert a.comm == b.comm, (overlap, i)
            assert a.rounds == b.rounds and a.converged == b.converged
            np.testing.assert_array_equal(a.classifier.w, b.classifier.w)
            assert a.classifier.b == b.classifier.b


def test_maxmarg_donated_sweep_decision_exact():
    insts = _grid(10, selector="maxmarg")
    ref = engine.maxmarg.run_instances(insts, max_epochs=MAX_EPOCHS)
    for overlap in (False, True):
        don = engine.maxmarg.run_instances(insts, max_epochs=MAX_EPOCHS,
                                           donate=True, overlap=overlap)
        for i, (inst, a, b) in enumerate(zip(insts, don, ref)):
            assert a.comm == b.comm, (overlap, i)
            assert a.rounds == b.rounds and a.converged == b.converged
            X = np.concatenate([s[0] for s in inst.shards])
            np.testing.assert_array_equal(a.classifier.predict(X),
                                          b.classifier.predict(X))


def test_cold_oracle_unaffected_by_donated_runs():
    """The cold padded while_loop path never donates; interleaving it with
    donated sweeps in one process must leave it bit-exact vs the hot path
    (the PR 4/5 differential standard)."""
    insts = _grid(6)
    engine.run_instances(insts, n_angles=N_ANGLES, max_epochs=MAX_EPOCHS,
                         donate=True, overlap=True)
    cold = engine.run_instances(insts, n_angles=N_ANGLES,
                                max_epochs=MAX_EPOCHS, compact=False)
    hot = engine.run_instances(insts, n_angles=N_ANGLES,
                               max_epochs=MAX_EPOCHS)
    for a, b in zip(hot, cold):
        assert a.comm == b.comm and a.rounds == b.rounds
        np.testing.assert_array_equal(a.classifier.w, b.classifier.w)
        assert a.classifier.b == b.classifier.b
