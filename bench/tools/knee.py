#!/usr/bin/env python3
"""Sweep the offered rate of an open-loop cell, to find its knee.

    python bench/tools/knee.py --workload <cell> --rates 100,200,400 \
        [--seed N] [--seconds 10]

One process, one set-up; each rate runs the cell's own window at that
rate and prints offered and completed rates, latency percentiles, the
in-flight count early and late in the window, and how many sessions had
not finished when arrivals stopped.  The knee is the highest rate whose
completed rate keeps up with the offered one and whose in-flight count
does not grow through the window; the cell runs at 0.8 of it, written
as a number into its traffic file.  Needs the chip, like ``run.py``.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

import numpy as np  # noqa: E402

from bench import common, generator  # noqa: E402
from bench.entries import service  # noqa: E402
from bench.run import Context  # noqa: E402
from bench.tracing import Tracer  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)

    bench = common.load_benchmark()
    wl = common.find(bench["workloads"], args.workload, "workload")
    cfg = common.load_json(common.find(bench["configs"], wl["config"],
                                       "config")["file"])
    traffic = generator.load(wl["traffic"])
    devs = common.devices_or_exit(int(wl["chips"]))
    common.enable_compile_cache()
    mod = importlib.import_module(f"bench.entries.{cfg['entry']}")
    entry = mod.Entry(Context(wl, cfg, traffic, args.seed, devs))
    entry.setup({})
    service.DRAIN_SECONDS = 5.0
    for rate in [float(r) for r in args.rates.split(",")]:
        entry.traffic = dict(traffic, rate_per_s=rate)
        entry.sessions, entry.host = {}, {}
        e2e = entry.window(args.seconds, Tracer(False, ""))
        recs = list(entry.sessions.values())
        t0 = min(r["due"] for r in recs)
        end = t0 + args.seconds
        done_in = sum(1 for r in recs if r.get("done", np.inf) <= end)
        late = sum(1 for r in recs if r.get("done", np.inf) > end)
        occ = entry.host["inflight"]
        q = max(1, len(occ) // 4)
        print(json.dumps({
            "rate_offered": rate, "sessions": len(recs),
            "completed_per_s": done_in / args.seconds,
            "unfinished_at_close": late,
            "p50_ms": e2e["session_p50_ms"], "p95_ms": e2e["session_p95_ms"],
            "inflight_q2": float(np.mean(occ[q:2 * q])),
            "inflight_q4": float(np.mean(occ[-q:])),
            "pool_steps": len(entry.host["step_s"]),
            "step_ms": 1e3 * float(np.mean(entry.host["step_s"])),
            "lag_p95_ms": 1e3 * float(np.percentile(entry.host["lag_s"], 95)),
        }), flush=True)
        entry.svc = None
    return 0


if __name__ == "__main__":
    sys.exit(main())
