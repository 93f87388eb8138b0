#!/usr/bin/env python3
"""Run one cell of the benchmark on the chip.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration (``bench/configs``), its traffic mix
(``bench/traffic``), its entry (``bench/entries/<entry>.py``) and its
per-layer metrics (``bench/metrics/<metric>.py``) are found by name from
``BENCHMARK.json``.  The run loads, warms up (``setup_s``), measures for
``--seconds``, checks what the window produced against the plain
references (``bench/reference``), and prints one JSON line last.  With
``--trace 0`` the line holds the cell's end-to-end metrics; with
``--trace 1`` a profiler trace of part of the window gives its per-layer
metrics.  Exits non-zero, printing no result line, when JAX finds no TPU
or fewer chips than the cell asks for.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

from bench import common, generator  # noqa: E402
from bench.tracing import Tracer, now  # noqa: E402


@dataclasses.dataclass
class Context:
    workload: dict
    config: dict
    traffic: dict
    seed: int
    devices: list


def applies(metric: dict, workload: str) -> bool:
    """Whether a metric is reported in a cell: listed there, or, with no
    list, in every cell."""
    return workload in metric.get("workloads", [workload])


def read_metric(name: str, record) -> object:
    path = os.path.join(common.BENCH_DIR, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(record)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = common.load_benchmark()
    wl = common.find(bench["workloads"], args.workload, "workload")
    cfg_entry = common.find(bench["configs"], wl["config"], "config")
    config = common.load_json(cfg_entry["file"])
    traffic = generator.load(wl["traffic"])

    devs = common.devices_or_exit(int(wl["chips"]))
    kind = devs[0].device_kind
    common.load_peaks(kind)                  # an unknown device is an error
    common.enable_compile_cache()
    counter = common.CompileCounter()
    parts = {"import_init_s": now() - T_PROCESS}

    entry_mod = importlib.import_module(f"bench.entries.{config['entry']}")
    ctx = Context(wl, config, traffic, args.seed, devs)
    entry = entry_mod.Entry(ctx)
    entry.setup(parts)
    setup_s = now() - T_PROCESS
    print(f"setup_s={setup_s!r} parts={json.dumps(parts)} "
          f"programs={counter.compiles} cache_hits={counter.hits}",
          flush=True)

    tracer = Tracer(bool(args.trace),
                    os.path.join(common.OUT_DIR, "traces", args.workload))
    c0 = counter.compiles
    e2e = entry.window(args.seconds, tracer)
    print(f"programs_in_window={counter.compiles - c0}", flush=True)
    memory = common.peak_memory(devs)
    entry.release()

    t = now()
    checks, notes = entry.check()
    print(f"reference_s={now() - t!r}", flush=True)
    for line in notes:
        print(line, flush=True)
    attempted, failed = entry.counts()

    metrics = {}
    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": memory}
    extra = {}
    if args.trace:
        from bench import trace
        summary = trace.summarize(tracer.log_dir)
        record = common.RunRecord(wl, config, traffic, entry.host, summary,
                                  kind)
        for m in bench["per_layer"]:
            if applies(m, args.workload):
                value = read_metric(m["name"], record)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        extra["breakdown"] = summary.breakdown()
    else:
        e2e["setup_s"] = setup_s
        for m in bench["end_to_end"]:
            if applies(m, args.workload):
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}

    print(common.check_lines(checks), file=sys.stderr, flush=True)
    result = {"correct": all(c.ok for c in checks), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    result.update(extra)
    result["checks"] = common.checks_json(checks)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
