"""Drive ``bench/run.py`` on the CPU at a tiny size, for the tests.

The harness's look for a TPU is replaced by the CPU devices, the peaks by
the v5e row, and ``BENCHMARK.json``, the configurations and the mixes by
small copies of the committed ones (the same keys, smaller sizes), so
everything else a run does is driven for real.
"""

from __future__ import annotations

import contextlib
import copy
import io
import json
import os
import sys
from unittest import mock

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [p for p in (ROOT, os.path.join(ROOT, "src"))
                if p not in sys.path]

from bench import common, generator  # noqa: E402

SMALL_POOL = {"n_pad": 64, "slots": 4, "n_angles": 32, "svm_steps": 60}
SMALL_TRAFFIC = {"bank_size": 16, "points_per_node": 64, "feed_batch": 16,
                 "clients": 8}
SMALL_RATE = {"open": 40.0}
_load_json, _load_traffic = common.load_json, generator.load


def small_config(path: str) -> dict:
    cfg = copy.deepcopy(_load_json(path))
    if "pool" in cfg:
        for key, v in SMALL_POOL.items():
            if key in cfg["pool"]:
                cfg["pool"][key] = v
        cfg["check"]["sample"] = 8
    return cfg


def small_traffic(name: str) -> dict:
    t = copy.deepcopy(_load_traffic(name))
    for key, v in SMALL_TRAFFIC.items():
        if key in t:
            t[key] = v
    if t["kind"] == "open":
        t["rate_per_s"] = SMALL_RATE["open"]
    return t


def run(workload: str, *, seed: int = 3, seconds: float = 1.0,
        trace: int = 0, extra_patches=()) -> tuple:
    """``(result dict or None, stdout, stderr)`` of one tiny run."""
    import jax
    from bench import run as bench_run

    cpu = jax.devices("cpu")
    v5e = json.load(open(os.path.join(ROOT, "bench", "peaks.json")))[
        "devices"]["TPU v5 lite"]
    out, err = io.StringIO(), io.StringIO()
    with contextlib.ExitStack() as stack:
        stack.enter_context(mock.patch.object(
            common, "devices_or_exit", lambda chips: cpu[:chips]))
        stack.enter_context(mock.patch.object(
            common, "load_peaks", lambda kind: v5e))
        stack.enter_context(mock.patch.object(
            common, "load_json", small_config))
        stack.enter_context(mock.patch.object(
            generator, "load", small_traffic))
        for p in extra_patches:
            stack.enter_context(p)
        stack.enter_context(contextlib.redirect_stdout(out))
        stack.enter_context(contextlib.redirect_stderr(err))
        bench_run.main(["--workload", workload, "--seed", str(seed),
                        "--seconds", str(seconds), "--trace", str(trace)])
    lines = out.getvalue().strip().splitlines()
    result = json.loads(lines[-1]) if lines else None
    return result, out.getvalue(), err.getvalue()
