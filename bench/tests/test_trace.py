"""The trace reduction, checked against a trace recorded on the chip.

``fixtures/maxmarg_step.xplane.pb.gz`` is the profiler's trace of one
MAXMARG pool step at the cell's size (one v5e, 32 slots, 8192 points a
node), inside the benchmark's ``bench.window`` and ``bench.pool_step``
spans; ``fixtures/maxmarg_step.trace.json.gz`` is the same trace as the
profiler wrote it in the Chrome trace format.  The reduction reads the
first; this test recomputes the same numbers from the second with a
separate, plain parser.
"""

import gzip
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(HERE, "..", "..")]
pytest.importorskip("jax")

from bench import trace  # noqa: E402

XPLANE = os.path.join(HERE, "fixtures", "maxmarg_step.xplane.pb.gz")
CHROME = os.path.join(HERE, "fixtures", "maxmarg_step.trace.json.gz")


def _chrome():
    """Window, device module intervals and kernel time from the Chrome
    trace: processes and threads are named by metadata events."""
    with gzip.open(CHROME, "rt") as f:
        events = json.load(f)["traceEvents"]
    pname, tname = {}, {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            pname[e["pid"]] = e["args"]["name"]
        if e.get("ph") == "M" and e.get("name") == "thread_name":
            tname[(e["pid"], e["tid"])] = e["args"]["name"]
    window, mods, kernel = None, [], []
    for e in events:
        if e.get("ph") != "X":
            continue
        proc = pname.get(e["pid"], "")
        thread = tname.get((e["pid"], e["tid"]), "")
        span = (e["ts"], e["ts"] + e.get("dur", 0.0))
        if e["name"] == "bench.window":
            window = span
        elif proc.startswith("/device:TPU:0") and thread == "XLA Modules":
            mods.append((e["name"], span))
        elif (proc.startswith("/device:TPU:0") and thread == "XLA Ops"
              and e["name"].startswith("pegasos_stage_batched")):
            kernel.append(span)
    return window, mods, kernel


def test_names():
    assert trace.op_name("%pegasos_stage_batched.3 = (f32[32,10,1]) "
                         "custom-call(...)") == "pegasos_stage_batched"
    assert trace.op_name("%copy-start.50 = (s32[1024])") == "copy-start"
    assert trace.op_name("%fusion = f32[8]") == "fusion"
    assert trace.module_name("jit__hot_turn_impl(17732527993821715717)") \
        == "jit__hot_turn_impl"


def test_reduction_matches_chrome_trace():
    s = trace.reduce(trace.load(XPLANE))
    window, mods, kernel = _chrome()
    assert window is not None and mods and kernel
    lo, hi = window
    assert s.window_s == pytest.approx((hi - lo) * 1e-6, abs=2e-6)

    def clip(a, b):
        return max(a, lo), min(b, hi)
    spans = sorted(clip(a, b) for _n, (a, b) in mods if clip(a, b)[1]
                   > clip(a, b)[0])
    busy, end = 0.0, float("-inf")
    for a, b in spans:
        if b > end:
            busy += b - max(a, end)
            end = b
    assert s.n_devices == 1
    assert s.busy_s == pytest.approx(busy * 1e-6, rel=1e-4, abs=5e-6)
    turns = [b - a for n, (a, b) in mods if n.startswith("jit__hot_turn_impl")]
    n, secs = s.module("jit__hot_turn_impl")
    assert n == len(turns) == 1
    assert secs == pytest.approx(sum(turns) * 1e-6, rel=1e-4)
    n, secs = s.op("pegasos_stage_batched")
    assert n == len(kernel) >= 1
    assert secs == pytest.approx(sum(b - a for a, b in kernel) * 1e-6,
                                 rel=1e-4)
    # the step's idle time lies inside the benchmark's pool-step span
    assert set(s.idle_by_host) <= {"bench.pool_step", "bench.collect",
                                   "untraced host"}
    assert sum(s.idle_by_host.values()) == pytest.approx(
        s.window_s - s.busy_s, rel=1e-6, abs=1e-6)


def test_readers_on_recorded_trace():
    """Every per-layer metric's reader gives a number from a record that
    holds the recorded trace and host timings, and nothing from an empty
    one."""
    from bench import common
    from bench.run import read_metric
    bench = common.load_benchmark()
    cfg = common.load_json("bench/configs/maxmarg-k4-d10.json")
    host = {"step_s": [0.2, 0.25], "occupancy": [1.0, 0.5],
            "lag_s": [0.001, 0.003], "ingest_s": [0.0003, 0.0004]}
    full = common.RunRecord({}, cfg, {}, host,
                            trace.reduce(trace.load(XPLANE)), "TPU v5 lite")
    empty = common.RunRecord({}, cfg, {}, {}, None, "TPU v5 lite")
    for m in bench["per_layer"]:
        value = read_metric(m["name"], full)
        assert isinstance(value, float) and value > 0, m["name"]
        if m["unit"] == "%":
            assert value <= 100.0, m["name"]
        assert read_metric(m["name"], empty) is None, m["name"]
