"""Spans the served path writes into the profiler's trace.

A tiny ``ProtocolService`` streams sessions through ``open``/``feed``/
``close`` and drains, once under ``jax.profiler`` and once without it.
The traced run's ``serve.*`` and ``pool.*`` spans are read back from the
``.xplane.pb`` (``/host:CPU`` plane, counts as the events' stats) and
checked for nesting and for counts that agree with the pool's own
ledger; the two runs must agree on every result, stat and session record.
"""

import glob
import os
import sys

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from repro.engine import hotloop, session_pool  # noqa: E402
from repro.serve import PoolConfig, ProtocolService  # noqa: E402

K, N_PAD, FEED, SESSIONS = 2, 32, 8, 6
POOLS = {
    "median": dict(selector="median", n_angles=64),
    "maxmarg": dict(selector="maxmarg", svm_steps=200),
}
PHASES = ("pool.admit", "pool.dispatch", "pool.view", "pool.evict")


def _shards(seed):
    rng = np.random.default_rng(seed)
    w = rng.normal(size=2)
    w /= np.linalg.norm(w)
    out = []
    for _ in range(K):
        X = rng.normal(size=(N_PAD, 2)).astype(np.float32)
        out.append((X, np.where(X @ w > 0, 1, -1).astype(np.int32)))
    return out


def _serve(selector):
    """Stream SESSIONS sessions in two batches around a few pool turns,
    then drain; 4 slots and 2-row admission waves, so a step can admit in
    more than one wave."""
    svc = ProtocolService(PoolConfig(slots=4, k=K, n_pad=N_PAD, d=2,
                                     max_epochs=6, admit_block=2,
                                     **POOLS[selector]), ingest_seed=5)
    for i in range(SESSIONS):
        h = svc.open()
        for node, (X, y) in enumerate(_shards(i)):
            for s in range(0, N_PAD, FEED):
                svc.feed(h, node, X[s:s + FEED], y[s:s + FEED])
        svc.close(h)
        if i == SESSIONS // 2:
            svc.step()
            svc.step()
    svc.run()
    return svc


def _read_spans(log_dir):
    """(name, start_ns, end_ns, stats) of every program span in the
    newest trace under ``log_dir``."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("pool.", "serve.")):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return out


def _inside(child, parents):
    return any(s <= child[1] and child[2] <= e for _n, s, e, _a in parents)


def _total(spans, name, arg):
    return sum(a[arg] for n, _s, _e, a in spans if n == name)


@pytest.mark.parametrize("selector", sorted(POOLS))
def test_served_path_spans(selector, tmp_path, monkeypatch):
    handed = []                      # bytes of each _admit_rows call
    admit_rows = session_pool._admit_rows

    def spy(data, state, idx, dblk, sblk):
        handed.append(sum(x.nbytes for x in
                          jax.tree_util.tree_leaves((idx, dblk, sblk))))
        return admit_rows(data, state, idx, dblk, sblk)

    monkeypatch.setattr(session_pool, "_admit_rows", spy)
    with jax.profiler.trace(str(tmp_path)):
        traced = _serve(selector)
    monkeypatch.undo()
    spans = _read_spans(str(tmp_path))
    by = {}
    for sp in spans:
        by.setdefault(sp[0], []).append(sp)
    stats = traced.stats

    # nesting: the phases inside a pool step
    steps = by["pool.step"]
    assert len(steps) == stats["pool_turns"]
    for name in PHASES:
        assert by[name], name
        assert all(_inside(sp, steps) for sp in by[name]), name
    assert len(by["pool.view"]) == len(steps)
    assert len(by["serve.open"]) == len(by["serve.close"]) == SESSIONS

    # counts agree with the pool's ledger and with what was handed over
    assert _total(spans, "pool.admit", "rows") == stats["admitted"] \
        == SESSIONS
    assert len(handed) > len(by["pool.admit"])       # multi-wave steps
    assert _total(spans, "pool.admit", "nbytes") == sum(handed)
    assert _total(spans, "pool.admit", "wait_us") > 0
    turns = sum(traced.session(s)["turns"] for s in range(SESSIONS))
    assert _total(spans, "pool.dispatch", "rows") == turns
    block = session_pool._round_up(4, hotloop.BATCH_MULT)
    assert _total(spans, "pool.dispatch", "block") \
        == len(by["pool.dispatch"]) * block
    assert _total(spans, "pool.evict", "rows") == SESSIONS
    assert set(by) == set(PHASES) | {"pool.step", "serve.open",
                                     "serve.close"}

    # tracing changes nothing the pool decides or reports
    plain = _serve(selector)
    assert plain.stats == traced.stats
    assert plain.pool.sessions == traced.pool.sessions
    for sid in range(SESSIONS):
        a, b = traced.result(sid), plain.result(sid)
        assert np.array_equal(np.asarray(a.classifier.w),
                              np.asarray(b.classifier.w))
        assert float(a.classifier.b) == float(b.classifier.b)
        assert (a.comm, a.rounds, a.converged) \
            == (b.comm, b.rounds, b.converged)
