"""Session-pool robustness gates (ISSUE 7 tentpole).

The pool's contract (DESIGN.md §session pool & failure model) in test
form:

* **bit-exactness by construction** — every dispatch runs at ONE pinned
  compile key, so results are a pure function of a session's own data:
  streaming order, batch composition, fault delays and checkpoint/restore
  must all leave results bitwise identical, and chaos survivors must match
  the fault-free pool bit for bit;
* **engine parity** — the fault-free pool agrees with the sweep-path
  ``engine.run_instances`` oracle on every decision and metered bit
  (separators allclose; the two paths' compile keys may move floats by
  ulps — the engine's own hot-vs-cold caveat);
* **supervision** — each forced corruption kind trips exactly its paired
  invariant, dropouts escalate retry/backoff to a ``retry_budget``
  quarantine on schedule, stragglers delay without charging retries;
* **determinism** — same seed ⇒ identical eviction sets, retry counters
  and per-session ledgers across runs and across restore;
* **steady state** — a second identical run adds zero jit cache entries
  (admission refills slots at pinned keys).

Forced-fault cases use a duck-typed schedule (the pool only reads
``draws`` / ``straggle_max`` / ``any_faults``), pinning faults to exact
(sid, pool turn) coordinates instead of fishing for seeds.
"""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from repro.engine import hotloop, median, run_instances, session_pool
from repro.engine.faults import (
    CORRUPT_COMM,
    CORRUPT_FILL,
    CORRUPT_NAN,
    FaultSchedule,
)
from repro.engine.session_pool import (
    ST_BUDGET,
    ST_CONVERGED,
    ST_QUARANTINED,
    PoolConfig,
    SessionPool,
)
from repro.engine.state import ProtocolInstance

K = 2
N_PAD = 16
N_ANGLES = 64
MAX_EPOCHS = 8

CHAOS = FaultSchedule(seed=3, p_dropout=0.08, p_drop_msg=0.04,
                      p_straggle=0.08, p_corrupt=0.03)


def _cfg(**kw):
    base = dict(slots=4, k=K, n_pad=N_PAD, n_angles=N_ANGLES,
                max_epochs=MAX_EPOCHS)
    base.update(kw)
    return PoolConfig(**base)


def _workload(n, seed=0, n_pad=N_PAD, k=K, separable=True):
    """Shared-separator instances, every shard exactly n_pad real rows so
    the pool and the run_instances oracle see identical data and budgets."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        w = rng.normal(size=2)
        w /= np.linalg.norm(w)
        shards = []
        for _ in range(k):
            X = rng.normal(size=(n_pad, 2)).astype(np.float32)
            if separable:
                yy = np.where(X @ w > 0, 1, -1).astype(np.int32)
            else:
                yy = rng.choice(np.array([-1, 1], np.int32), size=n_pad)
            shards.append((X, yy))
        out.append(shards)
    return out


def _run_pool(workload, cfg=None, schedule=None):
    pool = SessionPool(cfg or _cfg(), schedule)
    for shards in workload:
        pool.submit(shards)
    pool.run()
    return pool


def _res_bitwise(a, b):
    return (np.array_equal(np.asarray(a.classifier.w),
                           np.asarray(b.classifier.w))
            and float(a.classifier.b) == float(b.classifier.b)
            and a.comm == b.comm and a.rounds == b.rounds
            and a.converged == b.converged)


class ForcedSchedule:
    """Duck-typed fault schedule: fire exactly at the given (sid, turn)
    coordinates; a ``(sid, None)`` key fires on every turn."""

    straggle_max = 3
    any_faults = True

    def __init__(self, dropout=(), drop_msg=(), straggle=None, corrupt=None):
        self._drop = set(dropout)
        self._msg = set(drop_msg)
        self._str = dict(straggle or {})
        self._cor = dict(corrupt or {})

    @staticmethod
    def _hit(table, s, t):
        return (s, t) in table or (s, None) in table

    @staticmethod
    def _get(table, s, t, default):
        return table.get((s, t), table.get((s, None), default))

    def draws(self, sids, t):
        sids = [int(s) for s in np.asarray(sids)]
        return {
            "dropout": np.asarray(
                [self._hit(self._drop, s, t) for s in sids], bool),
            "drop_msg": np.asarray(
                [self._hit(self._msg, s, t) for s in sids], bool),
            "straggle": np.asarray(
                [self._get(self._str, s, t, 0) for s in sids], np.int32),
            "corrupt": np.asarray(
                [self._get(self._cor, s, t, -1) for s in sids], np.int32),
        }


# ---------------------------------------------------------------------------
# engine parity & composition invariance
# ---------------------------------------------------------------------------


def test_fault_free_pool_matches_engine_oracle():
    workload = _workload(10, seed=1)
    pool = _run_pool(workload)
    oracle = run_instances(
        [ProtocolInstance(shards=s, eps=pool.cfg.eps) for s in workload],
        n_angles=N_ANGLES, max_epochs=MAX_EPOCHS)
    for sid, o in enumerate(oracle):
        r = pool.results[sid]
        assert r.converged == o.converged and r.rounds == o.rounds
        assert r.comm == o.comm
        np.testing.assert_allclose(np.asarray(r.classifier.w),
                                   np.asarray(o.classifier.w),
                                   rtol=1e-5, atol=1e-6)
        assert np.isclose(float(r.classifier.b), float(o.classifier.b),
                          rtol=1e-5, atol=1e-6)
        assert r.extra["session_pool"] and r.extra["sid"] == sid


def test_streaming_order_is_bitwise_invariant():
    """All-at-once vs trickled submission changes admission timing and
    batch composition — with one pinned dispatch key neither may move a
    single bit of any result."""
    workload = _workload(9, seed=2)
    a = _run_pool(workload)

    b = SessionPool(_cfg())
    it = iter(workload)
    exhausted = False
    while True:
        while not exhausted and len(b.pending) < 1:
            try:
                b.submit(next(it))
            except StopIteration:
                exhausted = True
        if exhausted and b.drained():
            break
        b.step_pool()
    for sid in a.results:
        assert _res_bitwise(a.results[sid], b.results[sid]), sid


def test_budget_exhausted_sessions_still_report():
    workload = _workload(3, seed=4, separable=False)
    pool = _run_pool(workload)
    assert any(pool.sessions[s]["status"] == ST_BUDGET for s in range(3))
    for sid in range(3):
        r = pool.results[sid]
        if pool.sessions[sid]["status"] == ST_BUDGET:
            assert not r.converged and r.rounds == MAX_EPOCHS


def test_maxmarg_pool_smoke():
    cfg = _cfg(selector="maxmarg", slots=2, max_epochs=6)
    workload = _workload(4, seed=5)
    pool = _run_pool(workload, cfg=cfg)
    from repro.engine import maxmarg
    oracle = maxmarg.run_instances(
        [ProtocolInstance(shards=s, eps=cfg.eps, selector="maxmarg")
         for s in workload], max_epochs=6)
    for sid, o in enumerate(oracle):
        r = pool.results[sid]
        assert r.converged == o.converged and r.rounds == o.rounds
        assert r.comm == o.comm


def test_submit_validation():
    pool = SessionPool(_cfg())
    X = np.zeros((4, 2), np.float32)
    ok = np.ones((4,), np.int32)
    with pytest.raises(ValueError, match="expected 2 shards"):
        pool.submit([(X, ok)])
    with pytest.raises(ValueError, match="rows > pinned"):
        pool.submit([(np.zeros((N_PAD + 1, 2), np.float32),
                      np.ones((N_PAD + 1,), np.int32)), (X, ok)])
    with pytest.raises(ValueError, match="labels"):
        pool.submit([(X, np.array([1, 0, 1, 1])), (X, ok)])


# ---------------------------------------------------------------------------
# chaos determinism & graceful degradation
# ---------------------------------------------------------------------------


def test_chaos_two_runs_identical():
    """Same seed ⇒ identical eviction sets, retry counts, ledgers and
    bitwise-identical results — across two fresh pools."""
    workload = _workload(12, seed=3)
    a = _run_pool(workload, schedule=CHAOS)
    b = _run_pool(workload, schedule=CHAOS)
    assert a.stats == b.stats
    assert a.sessions == b.sessions
    assert set(a.results) == set(b.results)
    for sid in a.results:
        assert _res_bitwise(a.results[sid], b.results[sid]), sid
    # the run must actually have been chaotic
    assert a.stats["dropouts"] + a.stats["drop_msgs"] > 0
    assert a.stats["straggles"] > 0


def test_chaos_survivors_bitwise_vs_fault_free():
    workload = _workload(12, seed=3)
    chaos = _run_pool(workload, schedule=CHAOS)
    clean = _run_pool(workload)
    quarantined = 0
    for sid in range(len(workload)):
        rec = chaos.sessions[sid]
        if rec["status"] == ST_QUARANTINED:
            quarantined += 1
            assert sid not in chaos.results
            assert rec["quarantine_reason"] is not None
        else:
            assert _res_bitwise(chaos.results[sid], clean.results[sid]), sid
    assert quarantined == chaos.stats["quarantined"]


@pytest.mark.parametrize("kind,reason", [
    (CORRUPT_NAN, "nan_separator"),
    (CORRUPT_FILL, "fill_regression"),
    (CORRUPT_COMM, "comm_blowout"),
])
def test_corruption_kind_trips_its_invariant(kind, reason):
    # non-separable data keeps every session running its full turn budget,
    # so the mid-run corruption at pool turn 1 cannot race a same-turn
    # convergence (a finished session's transcript legitimately stops
    # growing, so the fill screen only covers still-running rows)
    workload = _workload(3, seed=6, separable=False)
    sched = ForcedSchedule(corrupt={(1, 1): kind})
    pool = _run_pool(workload, schedule=sched)
    rec = pool.sessions[1]
    assert rec["status"] == ST_QUARANTINED
    assert rec["quarantine_reason"] == reason
    assert rec["corrupt_kind"] == kind
    assert 1 not in pool.results
    assert pool.stats["quarantined"] == 1
    assert pool.stats["corruptions"] == 1
    # bystanders in the same batch are untouched
    clean = _run_pool(workload)
    for sid in (0, 2):
        assert pool.sessions[sid]["status"] == \
            clean.sessions[sid]["status"]
        assert _res_bitwise(pool.results[sid], clean.results[sid])


def test_dropout_escalates_to_retry_budget_quarantine():
    """A permanently-dropped session walks the exponential backoff ladder
    (1, 2, 4 pool turns for backoff_base=1) and quarantines when retries
    exceed the budget — on an exactly predictable pool turn."""
    workload = _workload(2, seed=7)
    pool = _run_pool(workload, schedule=ForcedSchedule(dropout={(0, None)}))
    rec = pool.sessions[0]
    budget = pool.cfg.retry_budget
    assert rec["status"] == ST_QUARANTINED
    assert rec["quarantine_reason"] == "retry_budget"
    assert rec["retries"] == budget + 1
    assert rec["backoffs"] == budget
    assert rec["dropouts"] == budget + 1
    assert rec["turns"] == 0 and 0 not in pool.results
    # retries land at t = 0, 2, 5, 10: gaps of 1 + 2^i, quarantined and
    # evicted on the turn the (budget+1)-th retry fires
    assert rec["evicted_turn"] == sum(1 + (1 << i) for i in range(budget))
    # the healthy neighbour is oblivious
    assert pool.sessions[1]["status"] == ST_CONVERGED


def test_drop_msg_retries_once_then_finishes_bitexact():
    workload = _workload(2, seed=8)
    pool = _run_pool(workload, schedule=ForcedSchedule(drop_msg={(0, 1)}))
    clean = _run_pool(workload)
    rec = pool.sessions[0]
    assert rec["status"] == ST_CONVERGED
    assert rec["drop_msgs"] == 1 and rec["dropouts"] == 0
    assert rec["retries"] == 1 and rec["backoffs"] == 1
    assert _res_bitwise(pool.results[0], clean.results[0])
    assert rec["evicted_turn"] > clean.sessions[0]["evicted_turn"]


def test_straggler_delays_without_charging_retries():
    workload = _workload(2, seed=9)
    pool = _run_pool(workload, schedule=ForcedSchedule(straggle={(0, 1): 2}))
    clean = _run_pool(workload)
    rec = pool.sessions[0]
    assert rec["status"] == ST_CONVERGED
    assert rec["straggles"] == 1
    assert rec["retries"] == 0 and rec["backoffs"] == 0
    assert _res_bitwise(pool.results[0], clean.results[0])
    assert rec["evicted_turn"] == clean.sessions[0]["evicted_turn"] + 3


# ---------------------------------------------------------------------------
# checkpoint / restore
# ---------------------------------------------------------------------------


def test_checkpoint_restore_resumes_bitexact(tmp_path):
    """Snapshot a chaotic pool mid-stream (live slots, pending queue,
    partial results); the restored pool and the original must finish with
    identical ledgers, stats and bitwise-identical results."""
    workload = _workload(12, seed=10)
    a = SessionPool(_cfg(), CHAOS)
    for shards in workload:
        a.submit(shards)
    for _ in range(6):
        a.step_pool()
    assert not a.drained()          # the snapshot must be mid-stream
    assert a.pending                # ... with sessions still queued
    a.checkpoint(str(tmp_path))

    b = SessionPool.restore(str(tmp_path))
    assert b.pool_turn == a.pool_turn
    a.run()
    b.run()
    assert a.stats == b.stats
    assert a.sessions == b.sessions
    assert set(a.results) == set(b.results)
    for sid in a.results:
        assert _res_bitwise(a.results[sid], b.results[sid]), sid
        assert a.results[sid].extra == b.results[sid].extra


def test_periodic_checkpoint_from_config(tmp_path):
    cfg = _cfg(checkpoint_every=4, checkpoint_dir=str(tmp_path))
    workload = _workload(5, seed=11)
    a = _run_pool(workload, cfg=cfg)
    assert os.path.exists(tmp_path / "latest.json")
    # the last periodic snapshot mid-run restores and finishes identically
    b = SessionPool.restore(str(tmp_path))
    b.run()
    for sid in a.results:
        assert _res_bitwise(a.results[sid], b.results[sid]), sid


# ---------------------------------------------------------------------------
# steady-state recompiles
# ---------------------------------------------------------------------------


def _pool_cache_entries():
    fns = (median._hot_turn, session_pool._admit_rows,
           session_pool._corrupt_median, session_pool._view_median,
           session_pool._mark_done)
    return sum(f._cache_size() for f in fns)


def test_second_identical_run_compiles_nothing():
    """The admission contract: slots refill at pinned cache keys, so a
    warmed pool re-running the same traffic adds zero jit cache entries
    and dispatches at exactly one compile key."""
    workload = _workload(10, seed=12)
    _run_pool(workload, schedule=CHAOS)       # warm every pinned key
    entries0 = _pool_cache_entries()
    keys0 = len(hotloop.KEY_LOG)
    _run_pool(workload, schedule=CHAOS)
    assert _pool_cache_entries() - entries0 == 0
    assert len(set(hotloop.KEY_LOG[keys0:])) == 1


def test_key_log_records_the_pinned_key_once_per_pool():
    """The pinned dispatch key goes into ``hotloop.KEY_LOG`` at a pool's
    first dispatch only, so a long-lived pool's log does not grow with
    its turns."""
    keys0 = len(hotloop.KEY_LOG)
    pool = _run_pool(_workload(6, seed=4))
    assert pool.stats["dispatches"] > 1
    assert len(hotloop.KEY_LOG) - keys0 == 1
