"""The sweep cell (``bench/entries/sweep.py``) end to end on the CPU.

A tiny run of ``maxmarg-k4-d10-mesh4.sweep`` (``tiny.run``: the
committed configuration and mix at small sizes, one CPU device in the
mesh) must be correct; its readers must give a number from the counters
and trace names of this program and ``None`` from a record without them,
as a commit before the sweep's counters gives; and a sweep that drops one
shard's results must make ``correct`` false.
"""

import contextlib
import os
import sys
from unittest import mock

import pytest

sys.path[:0] = [os.path.join(os.path.dirname(__file__), "..", ".."),
                os.path.join(os.path.dirname(__file__), "..", "..", "src")]

pytest.importorskip("jax")

from bench import common, generator  # noqa: E402
from bench.tests import tiny  # noqa: E402

CELL = "maxmarg-k4-d10-mesh4.sweep"
SMALL_SWEEP = {"devices": 1, "batch": 8}          # and the cell's "warm"
METRICS = ("sweep_turn_ms.sweep", "view_wait_ms.sweep",
           "shard_live_pct.sweep", "turn_device_ms.sweep",
           "pegasos_roofline.sweep", "device_idle_pct.sweep")


def _config(path):
    cfg = tiny.small_config(path)
    if "sweep" in cfg:
        cfg["sweep"].update(SMALL_SWEEP)
    return cfg


def _traffic(name):
    t = tiny.small_traffic(name)
    if t["kind"] == "sweep":
        t["sweep_size"] = SMALL_SWEEP["batch"]
    return t


def _small():
    return [mock.patch.object(common, "load_json", _config),
            mock.patch.object(generator, "load", _traffic)]


def _run(extra=()):
    return tiny.run(CELL, seconds=2.0, extra_patches=_small() + list(extra))


def test_tiny_sweep_run_is_correct():
    result, out, err = _run()
    assert result is not None, err[-2000:]
    assert result["correct"], err[-2000:]
    assert "programs_in_window=0" in out
    checks = result["checks"]
    assert checks["repeat_mismatches"]["value"] == 0.0
    assert checks["unfinished_sessions"]["value"] == 0.0
    assert set(result["metrics"]) == {"sessions_per_s", "setup_s"}
    assert result["metrics"]["sessions_per_s"]["value"] > 0
    assert result["attempted"] > 0 and result["failed"] == 0


class _Trace:
    """A reduced trace with what a chip trace of a sweep holds."""
    window_s, busy_s, n_devices = 2.0, 1.5, 4

    def __init__(self, modules):
        self.modules = modules

    def module(self, name):
        return self.modules.get(name, (0, 0.0))

    def op(self, prefix):
        return (24, 0.12) if prefix == "pegasos_stage_batched" else (0, 0.0)


def _entry_host():
    """The host record of a tiny sweep window, driven directly."""
    import jax
    from bench.entries import sweep
    from bench.run import Context
    from bench.tracing import Tracer
    bench = common.load_benchmark()
    wl = common.find(bench["workloads"], CELL, "workload")
    with contextlib.ExitStack() as stack:
        for p in _small():
            stack.enter_context(p)
        cfg = common.load_json(common.find(
            bench["configs"], wl["config"], "config")["file"])
        traffic = generator.load(wl["traffic"])
    entry = sweep.Entry(Context(wl, cfg, traffic, 5, jax.devices()[:1]))
    entry.setup({})
    entry.window(1.0, Tracer(False, ""))
    return cfg, entry.host


def test_readers_with_and_without_the_counters():
    from bench.run import read_metric
    cfg, host = _entry_host()
    assert host["sweeps"] and all("turns" in s["stats"]
                                  for s in host["sweeps"])
    named = _Trace({"jit__sharded_full_turn": (4, 0.02),
                    "jit__sharded_sub_turn": (20, 0.08)})
    full = common.RunRecord({}, cfg, {}, host, named, "TPU v5 lite")
    for name in METRICS:
        value = read_metric(name, full)
        assert isinstance(value, float) and value > 0, name
        if name.endswith("_pct.sweep") or "roofline" in name:
            assert value <= 100.0, name
    assert read_metric("turn_device_ms.sweep", full) == pytest.approx(
        1e3 * 0.10 / 24)
    # what a commit before the counters and the renamed programs records
    old = {"sweeps": [dict(s, stats={k: v for k, v in s["stats"].items()
                                     if k.startswith("shard_")})
                      for s in host["sweeps"]]}
    parent = common.RunRecord({}, cfg, {}, old,
                              _Trace({"jit_full": (4, 0.02),
                                      "jit_sub": (20, 0.08)}),
                              "TPU v5 lite")
    empty = common.RunRecord({}, cfg, {}, {}, None, "TPU v5 lite")
    for name in METRICS:
        assert read_metric(name, empty) is None, name
        if name != "device_idle_pct.sweep":     # reads the trace alone
            assert read_metric(name, parent) is None, name


def _drop_one_shard():
    """The sweep's results of one quarter of the batch (one chip's shard
    on the cell's four chips) never come back: those rows read back as
    they were packed."""
    import jax
    import numpy as np
    from repro.engine import maxmarg
    run_hot = maxmarg.run_hot

    def dropped(data, state, **kw):
        packed = jax.tree_util.tree_map(np.array, state)   # before donation
        final = jax.tree_util.tree_map(np.array, run_hot(data, state, **kw))
        q = len(packed.done) // 4
        for f, p in zip(jax.tree_util.tree_leaves(final),
                        jax.tree_util.tree_leaves(packed)):
            f[:q] = p[:q]
        return final
    return mock.patch.object(maxmarg, "run_hot", dropped)


def test_dropped_shard_is_caught():
    result, _out, err = _run([_drop_one_shard()])
    assert result is not None, err[-2000:]
    assert not result["correct"]
