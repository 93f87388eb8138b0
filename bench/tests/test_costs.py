"""The Pegasos stage count is a function of the algorithm's shapes: the
kernel path and its jnp twin are called with the same fit sets by the
pool's turn, and the count of either equals the one the roofline reader
computes from the configuration, whatever padding the kernel adds."""

import functools
import os
import sys

import numpy as np
import pytest

sys.path[:0] = [os.path.join(os.path.dirname(__file__), "..", ".."),
                os.path.join(os.path.dirname(__file__), "..", "..", "src")]

jax = pytest.importorskip("jax")

from bench import costs  # noqa: E402

POOL = {"selector": "maxmarg", "k": 4, "d": 10, "n_pad": 64, "slots": 4,
        "eps": 0.05, "max_epochs": 16, "max_support": 4, "svm_steps": 5,
        "svm_stages": 3}


def _calls(use_pallas: bool):
    """Shapes the pool's turn passes to the solver stage, traced."""
    from repro.engine.session_pool import PoolConfig, SessionPool
    from repro.kernels import ops, pegasos

    seen = {"wrapper": [], "kernel": []}
    wrapper, kernel = ops.pegasos_stage, pegasos.pegasos_stage_batched

    def rec_wrapper(X, y, *a, nsteps, **kw):
        seen["wrapper"].append((X.shape, nsteps))
        kw.update(use_pallas=use_pallas, interpret=True)
        return wrapper(X, y, *a, nsteps=nsteps, **kw)

    def rec_kernel(XT, *a, nsteps, **kw):
        seen["kernel"].append((XT.shape, nsteps))
        return kernel(XT, *a, nsteps=nsteps, **kw)

    jax.clear_caches()
    pool = SessionPool(PoolConfig(**POOL, solver_kernel=True))
    fn, args, kw = pool.turn_call(np.arange(POOL["slots"]))
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "pegasos_stage", rec_wrapper)
        mp.setattr(ops._pg, "pegasos_stage_batched", rec_kernel)
        jax.make_jaxpr(functools.partial(fn, **kw))(*args)
    jax.clear_caches()
    return seen


def test_count_same_for_kernel_and_twin_call_shapes():
    B, N, d, nsteps = costs.pool_stage_shape(POOL)
    want = costs.pegasos_stage(B, N, d, nsteps)
    for use_pallas in (False, True):
        seen = _calls(use_pallas)
        assert seen["wrapper"], "the turn never called the solver stage"
        for (b, n, dd), steps in seen["wrapper"]:
            assert costs.pegasos_stage(b, n, dd, steps) == want
        if use_pallas:
            # the kernel's own operands are padded; the count is not
            assert seen["kernel"]
            for (bp, dp, np_), steps in seen["kernel"]:
                assert bp >= B and dp == d and np_ >= N and steps == nsteps
        else:
            assert not seen["kernel"]


def test_count_by_hand():
    ops_, nbytes = costs.pegasos_stage(2, 10, 3, 4)
    assert ops_ == 2 * (4 * (10 * 15 + 23) + 10 * 8)
    assert nbytes == 4 * 2 * (10 * 4 + 15)
    assert costs.maxmarg_transcript_rows(4, 16, 4) == 296
    t, bound = costs.least_time(197e12, 1.0, {"flops_bf16": 197e12,
                                              "hbm_bw": 819e9})
    assert (t, bound) == (1.0, "compute")
