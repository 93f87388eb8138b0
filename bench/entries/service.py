"""Entry ``service``: sessions served by ``repro.serve.ProtocolService``.

One pool (``PoolConfig`` from the configuration's ``pool``) serves the
traffic mix:

* ``closed``: ``clients`` clients submit ready shards
  (``ProtocolService.submit``); each submits its next instance as soon as
  its result is there.  Client ``c`` cycles through its own block of
  ``bank_size / clients`` consecutive bank instances, so every seed
  serves the same sessions; the seed draws the order in which the
  clients first submit.  Reports ``sessions_per_s``: sessions finished
  with a result during the window over the window's length.
* ``open``: sessions arrive at their due times and stream every node's
  points in ``feed_batch`` batches through ``open``/``feed``/``close``.
  Arrivals stop at ``--seconds`` and the pool drains.  Reports the 50th
  and 95th percentiles of latency, from the time a session was due to the
  end of the pool step after which its result was there, over every
  session due in the window (one that never finishes counts as infinite).

The server loop is the program's own model: one thread that ingests what
is due and steps the pool.  After the window, a sample of finished
sessions drawn from the seed, with the one of most pool turns among them,
is replayed through the plain references in ``bench/reference``.
"""

from __future__ import annotations

import gc
import time
from typing import Dict, List

import numpy as np

from bench import common, generator
from bench.reference import ingest as ref_ingest
from bench.tracing import now

DRAIN_SECONDS = 60.0      # a session due in the window may finish this late
WARM_SESSIONS = 2


class Entry:
    def __init__(self, ctx):
        from repro.engine.session_pool import PoolConfig
        self.ctx = ctx
        self.cfg = ctx.config
        self.traffic = ctx.traffic
        self.pool_cfg = PoolConfig(**self.cfg["pool"])
        self.host: Dict[str, object] = {}
        self.sessions: Dict[int, dict] = {}     # sid -> record
        self.svc = None

    # -- set-up ----------------------------------------------------------

    def setup(self, parts: dict):
        t = now()
        self.bank = generator.make_bank(self.traffic, self.pool_cfg.k,
                                        self.pool_cfg.d)
        parts["bank_s"] = now() - t
        t = now()
        # the warm stream: the cell's own entry and shapes, drained, so
        # every program the window runs is compiled or loaded
        svc = self._service()
        for i in range(WARM_SESSIONS):
            if self.traffic.get("ingest") == "stream":
                self._stream(svc, i)
            else:
                svc.submit(self.bank.instances[i])
        svc.run()
        parts["warm_s"] = now() - t

    def _service(self):
        from repro.serve.service import ProtocolService
        return ProtocolService(self.pool_cfg, ingest_seed=self.ctx.seed)

    def _stream(self, svc, idx: int):
        """Stream bank instance ``idx`` through ``open``/``feed``/``close``;
        returns the ingest handle and the session id."""
        fb = int(self.traffic["feed_batch"])
        h = svc.open()
        for node, (X, y) in enumerate(self.bank.instances[idx]):
            for s in range(0, len(y), fb):
                svc.feed(h, node, X[s:s + fb], y[s:s + fb])
        return h, svc.close(h)

    # -- the window --------------------------------------------------------

    def window(self, seconds: float, tracer) -> dict:
        self.svc = self._service()
        if self.traffic["kind"] == "closed":
            return self._closed(seconds, tracer)
        return self._open(seconds, tracer)

    def _finished(self, sid: int) -> bool:
        return (self.svc.result(sid) is not None
                or self.svc.status(sid) == "quarantined")

    def _step(self, tracer, steps: List[float], occ: List[float],
              inflight: int):
        occ.append(min(inflight, self.pool_cfg.slots) / self.pool_cfg.slots)
        self.host.setdefault("inflight", []).append(inflight)
        t = now()
        with tracer.span("bench.pool_step"):
            self.svc.step()
        t1 = now()
        steps.append(t1 - t)
        return t1

    def _closed(self, seconds, tracer) -> dict:
        svc = self.svc
        clients = int(self.traffic["clients"])
        per, rest = divmod(len(self.bank.instances), clients)
        if rest or not per:
            raise ValueError("a closed mix's bank_size must be a multiple "
                             "of its clients")
        sent = [0] * clients           # sessions each client has submitted
        inflight = {}                  # sid -> client
        steps, occ = [], []

        def submit(c, t):
            idx = c * per + sent[c] % per
            sent[c] += 1
            sid = svc.submit(self.bank.instances[idx])
            self.sessions[sid] = {"bank": idx, "handle": None, "due": t}
            inflight[sid] = c

        t0 = now()
        tracer.plan(t0, seconds)
        for c in generator.order(clients, self.ctx.seed, 2):
            submit(int(c), t0)
        done_in_window = 0
        end = t0
        while True:
            t = now()
            tracer.tick(t)
            if t - t0 >= seconds:
                break
            end = self._step(tracer, steps, occ, len(inflight))
            with tracer.span("bench.collect"):
                for sid in [s for s in inflight if self._finished(s)]:
                    c = inflight.pop(sid)
                    self.sessions[sid]["done"] = end
                    done_in_window += 1
                    submit(c, end)
        tracer.finish()
        window_s = end - t0
        self._drain(list(inflight), deadline=now() + DRAIN_SECONDS)
        self.host.update(step_s=steps, occupancy=occ, window_s=window_s,
                         completed=done_in_window)
        return {"sessions_per_s": done_in_window / window_s}

    def _open(self, seconds, tracer) -> dict:
        svc = self.svc
        order = generator.order(len(self.bank.instances), self.ctx.seed, 2)
        n_bank = len(order)
        due = generator.arrivals(self.traffic, seconds, self.ctx.seed)
        inflight = {}
        steps, occ, lag, ingest_s = [], [], [], []
        nxt = 0
        t0 = now()
        tracer.plan(t0, seconds)
        deadline = t0 + seconds + DRAIN_SECONDS
        while True:
            t = now()
            tracer.tick(t)
            while nxt < len(due) and t0 + due[nxt] <= t:
                idx = int(order[nxt % n_bank])
                ts = now()
                lag.append(ts - (t0 + due[nxt]))
                with tracer.span("bench.ingest"):
                    h, sid = self._stream(svc, idx)
                ingest_s.append(now() - ts)
                self.sessions[sid] = {"bank": idx, "handle": h,
                                      "due": t0 + due[nxt]}
                inflight[sid] = True
                nxt += 1
                t = now()
            if inflight:
                end = self._step(tracer, steps, occ, len(inflight))
                with tracer.span("bench.collect"):
                    for sid in [s for s in inflight if self._finished(s)]:
                        del inflight[sid]
                        self.sessions[sid]["done"] = end
            elif nxt < len(due):
                with tracer.span("bench.wait"):
                    time.sleep(max(0.0, t0 + due[nxt] - now()))
            else:
                break
            if now() > deadline:
                break
        tracer.finish()
        # a session that never finished waited at least until now
        end = now()
        lat = [(r.get("done", end) - r["due"]) * 1e3
               for r in self.sessions.values()]
        self.host.update(step_s=steps, occupancy=occ, lag_s=lag,
                         ingest_s=ingest_s, latency_ms=lat)
        return {"session_p50_ms": common.percentile(lat, 50),
                "session_p95_ms": common.percentile(lat, 95)}

    def _drain(self, sids, deadline):
        while any(not self._finished(s) for s in sids) and now() < deadline:
            self.svc.step()
        for s in sids:
            if self._finished(s):
                self.sessions[s].setdefault("done", now())

    # -- after the window ----------------------------------------------

    def release(self):
        """Take what the check needs from the service, then free it."""
        self.results, self.turns = {}, {}
        for sid in self.sessions:
            r = self.svc.result(sid)
            if r is not None:
                self.results[sid] = {
                    "w": np.asarray(r.classifier.w, np.float64),
                    "b": float(r.classifier.b), "converged": r.converged,
                    "rounds": r.rounds, "comm": dict(r.comm)}
                self.turns[sid] = self.svc.session(sid)["turns"]
        self.svc = None
        gc.collect()

    def sample(self) -> List[int]:
        """The finished sessions to check, drawn from the seed: one for
        each of ``check.sample`` distinct bank instances (or every one
        served), and the one of most pool turns."""
        done = sorted(self.results)
        if not done:
            return []
        rng = np.random.default_rng((self.ctx.seed, 3))
        first: Dict[int, int] = {}
        for s in rng.permutation(done):
            first.setdefault(self.sessions[int(s)]["bank"], int(s))
        distinct = sorted(first.values())
        n = min(int(self.cfg["check"]["sample"]), len(distinct))
        pick = set(int(s) for s in rng.choice(distinct, size=n,
                                              replace=False))
        pick.add(max(done, key=lambda s: (self.turns[s], -s)))
        return sorted(pick)

    def inputs(self, sid: int):
        """What ingest handed the pool for ``sid``, recomputed."""
        rec = self.sessions[sid]
        shards = self.bank.instances[rec["bank"]]
        if rec["handle"] is None:
            return shards
        return ref_ingest.streamed_shards(
            shards, capacity=self.pool_cfg.n_pad,
            feed_batch=int(self.traffic["feed_batch"]),
            ingest_seed=self.ctx.seed, handle=rec["handle"])

    def counts(self):
        attempted = len(self.sessions)
        return attempted, attempted - len(self.results)

    def check(self):
        from bench.reference import check
        return check.run(self, self.cfg)
