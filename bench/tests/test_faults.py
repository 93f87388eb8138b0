"""A run whose timed path is broken underneath reports ``correct`` false.

Each test drives a whole tiny run on the CPU (``tiny.run``: only the look
for a chip and the sizes differ from a chip run) with one fault planted in
the program: a turn that returns its state unchanged, a separator altered
where the pool produces the result, a communication record altered
there, an ingest that hands the pool a corrupted point, a solver stage
that leaves half of each fit set out and takes its mean over the rest,
and a solver that leaves out what a coordinator received, so that every
turn past the first goes wrong."""

import contextlib
import os
import sys
from unittest import mock

import numpy as np
import pytest

sys.path[:0] = [os.path.join(os.path.dirname(__file__), "..", ".."),
                os.path.join(os.path.dirname(__file__), "..", "..", "src")]

pytest.importorskip("jax")

from bench.entries import service  # noqa: E402
from bench.tests import tiny  # noqa: E402

CELLS = ["median-k2-d2.stream-poisson", "maxmarg-k4-d10.closed-mixed"]


def _alter_results(change):
    from repro.engine.session_pool import SessionPool
    evict = SessionPool._evict

    def bad_evict(self, slots):
        before = set(self.results)
        evict(self, slots)
        for sid in set(self.results) - before:
            change(self.results[sid])
    return mock.patch.object(SessionPool, "_evict", bad_evict)


def _flip(r):
    r.classifier.w = -r.classifier.w
    r.classifier.b = -float(r.classifier.b)


def _extra_point(r):
    r.comm["points"] += 1


def _unchanged_turn():
    from repro.engine.session_pool import SessionPool

    def no_turn(self, rows):
        self.stats["dispatches"] += 1
    return mock.patch.object(SessionPool, "_dispatch", no_turn)


def _bad_ingest():
    from repro.serve.service import ProtocolService
    close = ProtocolService.close

    def bad_close(self, handle):
        for r in self._open[handle].reservoirs:
            r.X[0] = r.X[0] + 0.5
        return close(self, handle)
    return mock.patch.object(ProtocolService, "close", bad_close)


def _on_kernel_path(stage):
    """The pool put on the solver's kernel entry (``ops.pegasos_stage``,
    the path a TPU runs; its jnp twin on the CPU), with ``stage`` planted
    there."""
    import jax
    from bench import common
    from repro.kernels import ops

    def kernel_config(path):
        cfg = tiny.small_config(path)
        cfg["pool"]["solver_kernel"] = True
        return cfg

    @contextlib.contextmanager
    def planted():
        jax.clear_caches()
        with mock.patch.object(ops, "pegasos_stage", stage), \
                mock.patch.object(common, "load_json", kernel_config):
            yield
        jax.clear_caches()
    return planted()


def _half_batch():
    """The MAXMARG solver stage on every other row of each fit set, its
    mean hinge taken over those alone."""
    import jax.numpy as jnp
    from repro.kernels import ops
    stage = ops.pegasos_stage

    def half(X, y, nv, *a, **kw):
        keep = (jnp.arange(y.shape[1]) % 2 == 0)[None, :]
        y = jnp.where(keep, y, 0.0)
        nv = jnp.maximum(jnp.sum(y != 0.0, axis=1), 1).astype(nv.dtype)
        return stage(X, y, nv, *a, **kw)
    return _on_kernel_path(half)


def _fit_own_only():
    """The MAXMARG solver leaving out the rows a coordinator received, so
    every turn past the first fits the coordinator's own shard alone.
    Planted where ``_half_batch`` plants its fault."""
    import jax.numpy as jnp
    from repro.kernels import ops
    stage = ops.pegasos_stage
    own = tiny.SMALL_POOL["n_pad"]

    def own_only(X, y, nv, *a, **kw):
        y = jnp.where((jnp.arange(y.shape[1]) < own)[None, :], y, 0.0)
        nv = jnp.maximum(jnp.sum(y != 0.0, axis=1), 1).astype(nv.dtype)
        return stage(X, y, nv, *a, **kw)
    return _on_kernel_path(own_only)


def _run(cell, fault):
    with mock.patch.object(service, "DRAIN_SECONDS", 1.0):
        res, out, err = tiny.run(cell, seconds=0.6, extra_patches=[fault])
    return res


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    res, _out, err = tiny.run(cell, seconds=0.6)
    assert res["correct"], err


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault,number", [
    ("unchanged_turn", "unfinished_sessions"),
    ("flipped_separator", "guarantee_violations"),
    ("extra_point", "inconsistent_records"),
])
def test_fault_is_caught(cell, fault, number):
    plant = {"unchanged_turn": _unchanged_turn,
             "flipped_separator": lambda: _alter_results(_flip),
             "extra_point": lambda: _alter_results(_extra_point)}[fault]()
    res = _run(cell, plant)
    assert res is not None and res["correct"] is False
    c = res["checks"][number]
    assert c["value"] > c["limit"]


def test_half_batch_is_caught():
    res = _run("maxmarg-k4-d10.closed-mixed", _half_batch())
    assert res is not None and res["correct"] is False
    assert (res["checks"]["mismatched_sessions"]["value"] > 0
            or res["checks"]["separator_gap"]["value"]
            > res["checks"]["separator_gap"]["limit"])


def test_later_turn_fault_is_caught():
    res = _run("maxmarg-k4-d10.closed-mixed", _fit_own_only())
    assert res is not None and res["correct"] is False
    c = res["checks"]["multi_turn_mismatch_pct"]
    assert c["value"] > c["limit"]


def test_bad_ingest_is_caught():
    res = _run("median-k2-d2.stream-poisson", _bad_ingest())
    assert res["correct"] is False
    assert (res["checks"]["mismatched_sessions"]["value"] > 0
            or res["checks"]["separator_gap"]["value"]
            > res["checks"]["separator_gap"]["limit"])


def test_no_tpu_exits_without_result(tmp_path):
    import subprocess
    root = os.path.join(os.path.dirname(__file__), "..", "..")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout


def test_seed_gives_same_work():
    from bench import generator
    t = {"rate_per_s": 50.0, "gaps_seed": 5}
    a = generator.arrivals(t, 2.0, 1)
    b = generator.arrivals(t, 2.0, 2**31 + 12345)
    assert len(a) == len(b) == 100
    assert a[0] == b[0] == 0.0 and max(a[-1], b[-1]) < 2.0
    # the same gaps in another order: all but one gap are shared
    shared = np.intersect1d(np.round(np.diff(a), 12), np.round(np.diff(b), 12))
    assert len(shared) >= 98
    assert not np.allclose(a, b)
