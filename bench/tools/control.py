#!/usr/bin/env python3
"""Readings of the program and of the control, for setting the limits.

    python bench/tools/control.py --workload <cell> --seeds 11,12,13 \
        [--seconds 3] [--witness 11,12]

For each seed, in one process: the cell's own set-up and a short window
at its own load, then the same sample of finished sessions a run checks,
compared with the reference (``bench/reference``), run whole, twice: the
program's results, and the control's, which is the reference itself
computed at the precision below the configuration's (its
``check.control``).  Prints one JSON line per seed with both readings of
every compared number.  The lower reading of a limit is the largest of
the program's, the upper the smallest of the control's.

For MAXMARG, the seeds named by ``--witness`` also read, each against
the reference: the reference on the same sessions with each shard's rows
in another order (its float32 sums reordered, nothing else), the
reference at precision ``high``, the reference with a planted later-turn
fault (a coordinator past the first turn fits its own shard alone,
leaving out what it received), the program with its solver's classic
XLA loop (``solver_kernel=False``) and with its kernel, each serving the
sampled sessions once; and, from the window, how often the program gave
one instance two different records.  Each line also lists every sampled
session's decisions under each reading.  Needs the chip, like ``run.py``.
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
from bench.reference import check  # noqa: E402
from bench.run import Context  # noqa: E402
from bench.tracing import Tracer  # noqa: E402


def _reading(protocol, pool, inputs, res, ref, failed=0) -> dict:
    n = check.numbers(protocol, pool, inputs, res, ref, failed)
    cmp = n.pop("_cmp")
    # the separators of the sessions past their first turn whose
    # decisions agree with the reference's
    alike = [check.separator_gap(protocol, g, r) for g, r in zip(res, ref)
             if check.turns(g) > 1 and check.turns(r) > 1
             and check.decisions(g) == check.decisions(r)]
    return dict(n, compared=cmp["compared"], multi_turn=cmp["multi_turn"],
                gap_multi_alike=max(alike, default=0.0),
                lines=cmp["lines"][:3])


def _fit_own_only(gen, shards):
    """A session whose coordinator, past the first turn, fits its own
    shard alone: what it received is left out of the fit."""
    n_own = [len(y) for _X, y in shards]
    req, turn = next(gen), 0
    while True:
        X, y = req
        if turn:
            ci = turn % len(shards)
            X, y = X[:n_own[ci]], y[:n_own[ci]]
        wb = yield X, y
        turn += 1
        try:
            req = gen.send(wb)
        except StopIteration as stop:
            return stop.value


def _permuted(inputs, seed):
    rng = np.random.default_rng((seed, 11))
    out = []
    for shards in inputs:
        perm = []
        for X, y in shards:
            p = rng.permutation(len(y))
            perm.append((X[p], y[p]))
        out.append(perm)
    return out


def _served(config, inputs, kernel):
    """The program's results of ``inputs``, each served once by a pool of
    the cell's configuration with the given solver path."""
    from repro.engine.session_pool import PoolConfig
    from repro.serve.service import ProtocolService
    svc = ProtocolService(PoolConfig(**dict(config["pool"],
                                            solver_kernel=kernel)))
    sids = [svc.submit(s) for s in inputs]
    svc.run()
    out = []
    for sid in sids:
        r = svc.result(sid)
        out.append({"w": np.asarray(r.classifier.w, np.float64),
                    "b": float(r.classifier.b), "converged": r.converged,
                    "rounds": r.rounds, "comm": dict(r.comm)})
    return out


def _self_disagreement(entry) -> dict:
    """Instances the window served more than once, past their first turn,
    and how many of them got two different records."""
    by_inst = {}
    for sid, r in entry.results.items():
        by_inst.setdefault(entry.sessions[sid]["bank"], set()).add(
            check.decisions(r))
    multi = [v for v in by_inst.values()
             if any(dec[1] > 1 or dict(dec[2])["rounds"] > 1 for dec in v)]
    return {"instances": len(multi),
            "with_two_records": sum(len(v) > 1 for v in multi)}


def _brief(r: dict) -> list:
    c = r["comm"]
    return [int(bool(r["converged"])), int(c["rounds"]), int(c["points"]),
            int(c["messages"])]


def readings(entry, config: dict, witness: bool) -> dict:
    protocol = config["protocol"]
    pool = config["pool"]
    sids = entry.sample()
    inputs = [entry.inputs(s) for s in sids]
    ref = check.reference(protocol, pool, inputs, "highest")
    got = [entry.results[s] for s in sids]
    ctl = check.reference(protocol, pool, inputs, config["check"]["control"])
    out = {"checked": len(sids),
           "program": _reading(protocol, pool, inputs, got, ref,
                               entry.counts()[1]),
           "control": _reading(protocol, pool, inputs, ctl, ref)}
    rows = {"reference": ref, "program": got, "control": ctl}
    if witness and protocol == "maxmarg":
        more = {
            "reordered": check.reference(protocol, pool,
                                         _permuted(inputs, entry.ctx.seed),
                                         "highest"),
            "high": check.reference(protocol, pool, inputs, "high"),
            "fault_fit_own_only": check.reference(protocol, pool, inputs,
                                                  "highest",
                                                  wrap=_fit_own_only),
            "classic_solver": _served(config, inputs, False),
            "kernel_solver": _served(config, inputs, True),
        }
        for name, res in more.items():
            out[name] = _reading(protocol, pool, inputs, res, ref)
        rows.update(more)
        out["program_self"] = _self_disagreement(entry)
    if protocol == "maxmarg":
        out["sessions"] = {name: [_brief(r) for r in res]
                           for name, res in rows.items()}
        out["bank"] = [entry.sessions[s]["bank"] for s in sids]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--witness", default="")
    args = ap.parse_args(argv)

    bench = common.load_benchmark()
    wl = common.find(bench["workloads"], args.workload, "workload")
    cfg = common.load_json(common.find(bench["configs"], wl["config"],
                                       "config")["file"])
    traffic = generator.load(wl["traffic"])
    devs = common.devices_or_exit(int(wl["chips"]))
    common.enable_compile_cache()
    mod = importlib.import_module(f"bench.entries.{cfg['entry']}")
    witness = {int(s) for s in args.witness.split(",") if s}
    for seed in [int(s) for s in args.seeds.split(",")]:
        entry = mod.Entry(Context(wl, cfg, traffic, seed, devs))
        entry.setup({})
        entry.window(args.seconds, Tracer(False, ""))
        entry.release()
        print(json.dumps(dict(seed=seed, **readings(entry, cfg,
                                                    seed in witness))),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
