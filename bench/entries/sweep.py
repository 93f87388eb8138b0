"""Entry ``sweep``: MAXMARG sweeps through ``repro.engine.run_sweep`` over a
("data",) mesh of the configuration's ``sweep.devices`` chips.

The bank splits into blocks of ``sweep_size`` consecutive instances, in
bank order: the grid order a user's sweep has, so each chip holds a run
of consecutive instances.  Set-up runs one whole sweep of each block, so
every program the window runs is compiled or loaded.  The window runs
sweeps back to back; the seed draws which block starts, and the blocks
alternate from there.  Reports ``sessions_per_s``: the instances returned
by the sweeps that started in the window, over the time from the
window's start to the return of the last of them.

Each sweep records its host time (``sweep_s``) and the program's own
counters for that sweep (``run_sweep(stats=...)``, a fresh dict each
time) in ``host["sweeps"]``.  After the window the sample of
``bench/entries/service.py`` (one result for each bank instance, and the
one of most turns) is replayed through the plain references in
``bench/reference``, and ``repeat_mismatches`` counts the bank instances
that two sweeps of the run gave different records.
"""

from __future__ import annotations

import gc
from collections import Counter
from typing import Dict, List

import numpy as np

from bench import common, generator
from bench.entries import service
from bench.tracing import now

# the last sweeps are traced whole: a sweep that may be the last to start
# in the window starts the profiler (the harness's plan starts it in the
# window's last seconds, which can fall inside a sweep)
TRACE_MARGIN = 1.5


class Entry:
    def __init__(self, ctx):
        self.ctx = ctx
        self.cfg = ctx.config
        self.traffic = ctx.traffic
        self.pool = self.cfg["pool"]
        size = int(self.traffic["sweep_size"])
        if size != int(self.cfg["sweep"]["batch"]):
            raise ValueError(f"the mix's sweep_size {size} is not the "
                             f"configuration's batch "
                             f"{self.cfg['sweep']['batch']}")
        self.host: Dict[str, object] = {"sweeps": []}
        self.sessions: Dict[int, dict] = {}     # sid -> {"bank", "sweep"}
        self.results: Dict[int, dict] = {}
        self.turns: Dict[int, int] = {}
        self.records: Dict[int, set] = {}       # bank index -> decisions

    # -- set-up ----------------------------------------------------------

    def setup(self, parts: dict):
        from repro.engine import ProtocolInstance
        from repro.launch.mesh import make_data_mesh
        t = now()
        self.bank = generator.make_bank(self.traffic, self.pool["k"],
                                        self.pool["d"])
        size = int(self.traffic["sweep_size"])
        n, rest = divmod(len(self.bank.instances), size)
        if rest or not n:
            raise ValueError("a sweep mix's bank_size must be a multiple "
                             "of its sweep_size")
        self.blocks = [
            [ProtocolInstance(s, self.pool["eps"], "maxmarg")
             for s in self.bank.instances[b * size:(b + 1) * size]]
            for b in range(n)]
        parts["bank_s"] = now() - t
        t = now()
        self.mesh = make_data_mesh(int(self.cfg["sweep"]["devices"]))
        for b in range(n):
            self._remember(b, self._sweep(b, {}))
        parts["warm_s"] = now() - t

    def _sweep(self, b: int, stats: dict):
        from repro import engine
        p = self.pool
        return engine.run_sweep(
            self.blocks[b], mesh=self.mesh, stats=stats,
            max_epochs=p["max_epochs"], max_support=p["max_support"],
            steps=p["svm_steps"], stages=p["svm_stages"], lam=p["lam0"],
            warm=bool(self.cfg["sweep"]["warm"]))

    def _remember(self, b: int, results) -> List[dict]:
        """Each result as the check reads it, with its decisions noted
        under its bank instance."""
        from bench.reference import check
        size = len(self.blocks[0])
        out = []
        for i, r in enumerate(results):
            rec = {"w": np.asarray(r.classifier.w, np.float64),
                   "b": float(r.classifier.b), "converged": r.converged,
                   "rounds": r.rounds, "comm": dict(r.comm)}
            self.records.setdefault(b * size + i, set()).add(
                check.decisions(rec))
            out.append(rec)
        return out

    # -- the window --------------------------------------------------------

    def window(self, seconds: float, tracer) -> dict:
        n = len(self.blocks)
        first = int(np.random.default_rng((self.ctx.seed, 2)).integers(n))
        self.longest = 0.0           # the longest sweep of the window
        t0 = now()
        tracer.plan(t0, seconds)
        end, done, j = t0, 0, 0
        while True:
            t = now()
            if t - t0 >= seconds:
                break
            if t + TRACE_MARGIN * self.longest >= t0 + seconds:
                tracer.start = min(tracer.start, t)
            tracer.tick(t)
            b = (first + j) % n
            end, results = self._timed_sweep(tracer, b)
            self._record(b, results, j)
            done += len(results)
            j += 1
        if tracer.enabled and tracer.state == "idle":
            # a sweep ran longer than any before it and crossed the end
            # unplanned: trace one more, outside the window's count
            tracer.start = now()
            tracer.tick(tracer.start)
            self._timed_sweep(tracer, (first + j) % n, counted=False)
        tracer.finish()
        window_s = end - t0
        self.host.update(window_s=window_s, completed=done)
        return {"sessions_per_s": done / window_s}

    def _timed_sweep(self, tracer, b: int, counted: bool = True):
        stats = {}
        ts = now()
        with tracer.span("bench.sweep"):
            results = self._sweep(b, stats)
        end = now()
        self.longest = max(self.longest, end - ts)
        if counted:
            self.host["sweeps"].append({"block": b, "sweep_s": end - ts,
                                        "stats": stats})
        return end, results

    def _record(self, b: int, results, j: int):
        size = len(self.blocks[0])
        for i, rec in enumerate(self._remember(b, results)):
            sid = len(self.sessions)
            self.sessions[sid] = {"bank": b * size + i, "sweep": j}
            self.results[sid] = rec
            self.turns[sid] = int(rec["comm"]["rounds"])

    # -- after the window ----------------------------------------------

    def release(self):
        """Nothing of a sweep stays on the devices but compiled programs."""
        self.blocks = None
        gc.collect()

    sample = service.Entry.sample

    def inputs(self, sid: int):
        return self.bank.instances[self.sessions[sid]["bank"]]

    def counts(self):
        attempted = len(self.sessions)
        return attempted, attempted - len(self.results)

    def repeat_mismatches(self) -> int:
        """Bank instances that two sweeps of the run (set-up's included)
        gave different decisions: converged flag, rounds or record."""
        return sum(len(v) > 1 for v in self.records.values())

    def check(self):
        from bench.reference import check
        checks, notes = check.run(self, self.cfg)
        limit = float(self.cfg["check"]["limits"]["repeat_mismatches"])
        checks.append(common.Check("repeat_mismatches",
                                   float(self.repeat_mismatches()), limit))
        from repro.kernels import ops
        per_block = Counter(s["block"] for s in self.host["sweeps"])
        notes.append("window sweeps of each block: "
                     f"{sorted(per_block.items())} (and one each in set-up)")
        notes.append("Pegasos stage paths traced (B, N, d, path): "
                     f"{sorted(Counter(ops.PEGASOS_PATH_LOG).items())}")
        return checks, notes
