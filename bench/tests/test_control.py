"""The control comes out not correct, at a size a test run can hold.

The control is the plain reference put in the program's place and
computed at the precision below the configuration's (the configuration's
``check.control``: ``high``, every product of a point with a direction
from bfloat16 heads and tails, as a TPU runs a float32 dot at that
precision; or ``bf16``, one bfloat16 pass, as at default precision).  At
a test's size the gaps are smaller than at the cells' own, so the test
holds the control to what does not depend on size: on every seed its
separator gap is at least three times the program's widest, while the
program passes every limit of the configuration.  The chip's readings at
the cells' sizes, from which the limits were set and against which the
control fails them, are in PERF.md.
"""

import os
import sys
from unittest import mock

import pytest

sys.path[:0] = [os.path.join(os.path.dirname(__file__), "..", ".."),
                os.path.join(os.path.dirname(__file__), "..", "..", "src")]
jax = pytest.importorskip("jax")

from bench import common, generator  # noqa: E402
from bench.tests import tiny  # noqa: E402
from bench.tools import control  # noqa: E402


def _fails(reading: dict, limits: dict) -> bool:
    """Whether a reading fails any of the configuration's limits."""
    return any(reading[k] > limits[k] for k in limits)


@pytest.mark.parametrize("cell,seeds", [
    ("median-k2-d2.stream-poisson", (5, 6, 7)),
    ("maxmarg-k4-d10.closed-mixed", (5, 6)),
])
def test_control_fails_and_program_passes(cell, seeds, capsys):
    cpu = jax.devices("cpu")
    with mock.patch.object(common, "devices_or_exit", lambda c: cpu[:c]), \
            mock.patch.object(common, "load_json", tiny.small_config), \
            mock.patch.object(generator, "load", tiny.small_traffic):
        control.main(["--workload", cell, "--seconds", "0.6", "--seeds",
                      ",".join(map(str, seeds))])
        bench = common.load_benchmark()
        wl = common.find(bench["workloads"], cell, "workload")
        cfg = common.load_json(common.find(bench["configs"], wl["config"],
                                           "config")["file"])
    import json
    rows = [json.loads(line) for line in capsys.readouterr().out.splitlines()
            if line.startswith("{")]
    assert len(rows) == len(seeds)
    limits = cfg["check"]["limits"]
    widest = max(r["program"]["separator_gap"] for r in rows)
    assert all(r["control"]["separator_gap"] >= 3 * widest
               or r["control"]["mismatched_sessions"] > 0 for r in rows)
    for r in rows:
        assert not _fails(r["program"], limits), r
