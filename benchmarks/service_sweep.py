"""Fault-tolerant streaming service benchmark (BENCH_service.json).

The ROADMAP's persistent-service north star, measured end to end: ≥256
protocol sessions *streamed* through the ring-buffer session pool
(``repro.engine.session_pool`` behind ``repro.serve.ProtocolService``'s
``SessionPool``), with slots freed by converged/evicted sessions refilled
from the pending queue between turns.  Three arms:

  chaos (warmup)   a seeded ``FaultSchedule`` with dropout, lost-message,
                   straggler and corruption rates **all nonzero** drives the
                   full workload once — this run compiles every pinned
                   (n_pad, width) dispatch key and is the correctness
                   source: statuses, retry/backoff counters, quarantines;
  chaos (steady)   the *identical* run again on the warm caches — its
                   wall-clock is the reported faulted throughput, and the
                   jit cache-size delta across every pool entry point is
                   the headline ``steady_state_recompiles`` (gated == 0 by
                   ``check_bench_schema.py``: admission refills slots at
                   pinned cache keys, so a saturated pool never recompiles);
  fault-free       the same workload with the zero-probability schedule —
                   baseline throughput, and every result is checked
                   **bit-exact** against an ``engine.run_instances`` oracle.

The bit-exactness gate (``oracle_mismatches``, gated empty): every session
the chaos run reports as cleanly finished (converged / budget-exhausted)
must match the fault-free pool oracle bit for bit — separator, convergence,
rounds and metered comm — because transient faults only ever *delay* a
session's turns, never change what they compute, and the pool dispatches
every turn at ONE pinned compile key, so batch composition cannot leak
into results (DESIGN.md §session pool & failure model).  Quarantined
sessions are exactly the corrupted ones and carry no result.  The same
gate also holds every fault-free pool session to decision- and comm-exact
parity against a sweep-path ``engine.run_instances`` oracle (separators
there may differ by f32 ulps across the two paths' compile keys — the
engine's own hot-vs-cold caveat; ``engine_bitwise`` counts how many match
bitwise anyway).  The two chaos arms must also agree with each other
(``determinism_ok``): the fault schedule is a pure hash, so same seed ⇒
same decisions.

PR 10 adds the ``mixed_traffic`` series: the same streamed-admission loop
with MEDIAN + MAXMARG + SAMPLING sessions interleaved through ONE
``PoolConfig(selector="unified")`` pool, measured against three
per-family pools serving the identical sessions (equal counts, warm
caches both sides).  Gated: the unified pool's steady run adds zero jit
cache entries, dispatches every mixed turn at exactly ONE pinned compile
key, and every session's result matches its per-family pool twin —
MEDIAN and SAMPLING bitwise, MAXMARG decision/comm-exact with separators
allclose (the two paths fit at different padded transcript widths, the
engine's own unified-vs-per-selector caveat; ``bitwise`` counts how many
match bitwise anyway).

Usage:
  python benchmarks/service_sweep.py            # full size, BENCH_service.json
  python benchmarks/service_sweep.py --tiny     # CI chaos-smoke sizes,
                                                # BENCH_service.tiny.json
"""

from __future__ import annotations

import argparse
import json
import os
import time
from typing import Dict, List, Tuple

import numpy as np

from repro.engine import hotloop, median, run_instances, session_pool, unified
from repro.engine.faults import FaultSchedule
from repro.engine.session_pool import PoolConfig, SessionPool
from repro.engine.state import ProtocolInstance
from repro.launch import compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = os.path.join(ROOT, "BENCH_service.json")

NOTES = (
    "Streamed session-pool service benchmark: chaos arm (dropout + lost "
    "message + straggler + corruption all nonzero, seeded) vs fault-free "
    "arm over the same workload.  Wall-clocks are machine-local and not "
    "gated; the gates are steady_state_recompiles == 0 (second identical "
    "chaos run adds zero jit cache entries across every pool entry point) "
    "and oracle_mismatches == [] (every cleanly-finished chaos session is "
    "bit-exact vs the fault-free pool oracle — guaranteed by the pool's "
    "single pinned dispatch key — and every fault-free session is "
    "decision- and comm-exact vs the engine.run_instances sweep oracle, "
    "whose differently-keyed compiles may move separator floats by ulps; "
    "engine_bitwise counts how many match bitwise anyway).  The "
    "mixed_traffic series streams interleaved MEDIAN+MAXMARG+SAMPLING "
    "sessions through ONE unified pool vs three per-family pools at equal "
    "session counts (warm caches both sides); gated on zero steady-state "
    "recompiles, exactly one pinned dispatch key, and empty mismatches "
    "(MEDIAN/SAMPLING bitwise, MAXMARG decision/comm-exact).  Produced by "
    "benchmarks/service_sweep.py; schema-gated by check_bench_schema.py."
)

# the chaos schedule: every channel nonzero (CI asserts the stats show
# every channel actually fired at full size)
CHAOS = dict(seed=11, p_dropout=0.05, p_drop_msg=0.03, p_straggle=0.06,
             p_corrupt=0.01, straggle_max=3)


def build_workload(n_sessions: int, k: int, n_pad: int,
                   seed: int = 0) -> List[List[Tuple[np.ndarray, np.ndarray]]]:
    """Separable 2-D instances, every shard exactly n_pad real points (no
    label-0 padding anywhere, so the pool and the oracle see byte-identical
    data and error budgets)."""
    rng = np.random.default_rng(seed)
    workload = []
    for _ in range(n_sessions):
        w = rng.normal(size=2)
        w /= np.linalg.norm(w)
        shards = []
        for _ in range(k):
            X = rng.normal(size=(n_pad, 2))
            yy = np.where(X @ w > 0, 1, -1).astype(np.int32)
            shards.append((X.astype(np.float32), yy))
        workload.append(shards)
    return workload


def run_streaming(pool: SessionPool, workload, low_water: int) -> float:
    """Stream the workload through the pool — submissions trickle in as the
    pending queue drains below ``low_water``, so admission interleaves with
    live mid-epoch sessions (the mixed-phase case).  Returns wall seconds."""
    it = iter(workload)
    exhausted = False
    t0 = time.perf_counter()
    guard = 0
    while True:
        while not exhausted and len(pool.pending) < low_water:
            try:
                pool.submit(next(it))
            except StopIteration:
                exhausted = True
                break
        if exhausted and pool.drained():
            break
        pool.step_pool()
        guard += 1
        if guard > 100_000:
            raise RuntimeError("service benchmark failed to drain")
    return time.perf_counter() - t0


def _pool_cache_entries() -> int:
    """Total jit cache entries across every entry point a MEDIAN pool turn
    can hit (dispatch, admission scatter, corruption, supervision view,
    eviction mark)."""
    fns = (median._hot_turn, session_pool._admit_rows,
           session_pool._corrupt_median, session_pool._view_median,
           session_pool._mark_done)
    return sum(f._cache_size() for f in fns)


def _unified_cache_entries() -> int:
    """Same census for a unified pool turn — ``_corrupt_unified`` /
    ``_view_unified`` are aliases of the maxmarg jits (jit re-keys on the
    pytree structure, and UnifiedState shares the leaf names they touch),
    so counting the alias targets counts them."""
    fns = (unified._hot_turn, session_pool._admit_rows,
           session_pool._corrupt_maxmarg, session_pool._view_maxmarg,
           session_pool._mark_done)
    return sum(f._cache_size() for f in fns)


MIXED_SELECTORS = ("median", "maxmarg", "sampling")


def build_mixed_workload(n_sessions: int, k: int, n_pad: int,
                         seed: int = 1000) -> List[dict]:
    """The mixed-traffic workload: the same separable 2-D instances, with
    the three protocol families interleaved round-robin and a per-session
    seed (feeds the SAMPLING reservoir chain; both arms use the same one,
    so SAMPLING results are bitwise-comparable)."""
    base = build_workload(n_sessions, k, n_pad, seed=seed)
    return [{"shards": s, "selector": MIXED_SELECTORS[i % 3], "seed": i}
            for i, s in enumerate(base)]


def run_streaming_mixed(pool: SessionPool, entries: List[dict],
                        low_water: int) -> float:
    """``run_streaming`` for per-session selector/seed submissions."""
    it = iter(entries)
    exhausted = False
    t0 = time.perf_counter()
    guard = 0
    while True:
        while not exhausted and len(pool.pending) < low_water:
            try:
                e = next(it)
            except StopIteration:
                exhausted = True
                break
            pool.submit(e["shards"], selector=e["selector"], seed=e["seed"])
        if exhausted and pool.drained():
            break
        pool.step_pool()
        guard += 1
        if guard > 100_000:
            raise RuntimeError("mixed service benchmark failed to drain")
    return time.perf_counter() - t0


def mixed_traffic_series(tiny: bool) -> Tuple[List[str], dict]:
    """One unified pool carrying interleaved MEDIAN/MAXMARG/SAMPLING
    sessions vs three per-family pools carrying the identical sessions.
    Both arms are timed on warm caches; the unified arm's warm run must
    add zero jit cache entries and dispatch at exactly one pinned key."""
    if tiny:
        sessions, slots, n_pad, n_angles, max_epochs = 12, 4, 16, 64, 8
    else:
        sessions, slots, n_pad, n_angles, max_epochs = 48, 12, 32, 128, 8
    k = 2
    low_water = max(2, slots // 2)
    entries = build_mixed_workload(sessions, k, n_pad)
    ucfg = PoolConfig(slots=slots, k=k, n_pad=n_pad, selector="unified",
                      n_angles=n_angles, max_epochs=max_epochs)
    lines = [f"mixed traffic: {sessions} sessions interleaved over "
             f"{MIXED_SELECTORS}, {slots} slots, one unified pool"]

    # -- unified arm: warmup compiles the ONE pinned key, warm run timed --
    run_streaming_mixed(SessionPool(ucfg), entries, low_water)
    entries0 = _unified_cache_entries()
    keys0 = len(hotloop.KEY_LOG)
    pool_u = SessionPool(ucfg)
    unified_s = run_streaming_mixed(pool_u, entries, low_water)
    recompiles = _unified_cache_entries() - entries0
    keys = sorted(set(hotloop.KEY_LOG[keys0:]))
    lines.append(f"unified pool: {unified_s:.2f}s, {recompiles} steady "
                 f"recompiles over {len(keys)} distinct dispatch keys")

    # -- per-family baseline: three pools, same sessions, warm too --------
    # (no pinned SAMPLING pool exists; a unified pool fed only SAMPLING
    # sessions is that family's dedicated path)
    fam_entries = {sel: [e for e in entries if e["selector"] == sel]
                   for sel in MIXED_SELECTORS}
    fam_cfg = {
        "median": PoolConfig(slots=slots, k=k, n_pad=n_pad,
                             n_angles=n_angles, max_epochs=max_epochs),
        "maxmarg": PoolConfig(slots=slots, k=k, n_pad=n_pad,
                              selector="maxmarg", max_epochs=max_epochs),
        "sampling": ucfg,
    }
    fam_s: Dict[str, float] = {}
    fam_results: Dict[str, dict] = {}
    for sel in MIXED_SELECTORS:
        run_streaming_mixed(SessionPool(fam_cfg[sel]), fam_entries[sel],
                            low_water)
        p = SessionPool(fam_cfg[sel])
        fam_s[sel] = run_streaming_mixed(p, fam_entries[sel], low_water)
        fam_results[sel] = p.results
    per_family_total = sum(fam_s.values())
    lines.append("per-family pools: " + ", ".join(
        f"{sel} {fam_s[sel]:.2f}s" for sel in MIXED_SELECTORS)
        + f" (total {per_family_total:.2f}s)")

    # -- parity: every unified-pool session vs its per-family twin --------
    mismatches = []
    bitwise = 0
    checked = 0
    fam_pos = {sel: 0 for sel in MIXED_SELECTORS}
    for sid, e in enumerate(entries):
        sel = e["selector"]
        fid = fam_pos[sel]
        fam_pos[sel] += 1
        r, o = pool_u.results[sid], fam_results[sel][fid]
        checked += 1
        wr = np.asarray(r.classifier.w)
        wo = np.asarray(o.classifier.w)
        decisions = (r.converged == o.converged and r.rounds == o.rounds
                     and r.comm == o.comm)
        exact = (decisions and np.array_equal(wr, wo)
                 and float(r.classifier.b) == float(o.classifier.b))
        if exact:
            bitwise += 1
        # MEDIAN is width-invariant bitwise; SAMPLING runs the identical
        # unified step both sides; MAXMARG fits at two transcript widths,
        # so its separators are held to allclose + decision/comm equality
        if sel == "maxmarg":
            ok = (decisions
                  and np.allclose(wr, wo, rtol=1e-5, atol=1e-6)
                  and np.isclose(float(r.classifier.b),
                                 float(o.classifier.b),
                                 rtol=1e-5, atol=1e-6))
        else:
            ok = exact
        if not ok:
            mismatches.append({"sid": sid, "selector": sel,
                               "arm": "unified_vs_per_family"})
    lines.append(f"mixed parity: {checked} sessions checked, "
                 f"{len(mismatches)} mismatches, {bitwise} bitwise")

    section = {
        "sessions": sessions,
        "slots": slots,
        "per_family_sessions": {sel: len(fam_entries[sel])
                                for sel in MIXED_SELECTORS},
        "unified_s": round(unified_s, 4),
        "per_family_s": {sel: round(fam_s[sel], 4)
                         for sel in MIXED_SELECTORS},
        "per_family_total_s": round(per_family_total, 4),
        "steady_state_recompiles": int(recompiles),
        "steady_state_dispatch_keys": [list(kk) for kk in keys],
        "checked": checked,
        "bitwise": bitwise,
        "mismatches": mismatches,
    }
    return lines, section


def _statuses(pool: SessionPool) -> Dict[str, int]:
    out = {"converged": 0, "budget_exhausted": 0, "quarantined": 0}
    for rec in pool.sessions.values():
        out[rec["status"]] += 1
    return out


def main(tiny: bool = False) -> List[str]:
    if tiny:
        sessions, slots, n_pad, n_angles, max_epochs = 24, 8, 16, 64, 8
    else:
        sessions, slots, n_pad, n_angles, max_epochs = 256, 32, 32, 128, 8
    k = 2
    cfg = PoolConfig(slots=slots, k=k, n_pad=n_pad, n_angles=n_angles,
                     max_epochs=max_epochs)
    chaos = FaultSchedule(**CHAOS)
    workload = build_workload(sessions, k, n_pad)
    low_water = max(2, slots // 2)

    lines = [f"service sweep: {sessions} sessions, {slots} slots, "
             f"k={k}, n_pad={n_pad}, selector=median"]

    # -- arm 1: chaos warmup (compiles every pinned key; correctness arm) --
    pool_a = SessionPool(cfg, chaos)
    run_streaming(pool_a, workload, low_water)
    stat_a = _statuses(pool_a)
    lines.append(f"chaos warmup: {stat_a}  stats={pool_a.stats}")

    # -- arm 2: identical chaos run on warm caches ------------------------
    entries0 = _pool_cache_entries()
    keys0 = len(hotloop.KEY_LOG)
    pool_b = SessionPool(cfg, chaos)
    faulted_s = run_streaming(pool_b, workload, low_water)
    steady_recompiles = _pool_cache_entries() - entries0
    steady_keys = sorted(set(hotloop.KEY_LOG[keys0:]))
    stat_b = _statuses(pool_b)
    determinism_ok = (
        stat_a == stat_b
        and pool_a.stats == pool_b.stats
        and all(pool_a.sessions[s] == pool_b.sessions[s]
                for s in pool_a.sessions))
    lines.append(f"chaos steady: {faulted_s:.2f}s, "
                 f"{steady_recompiles} recompiles over "
                 f"{len(steady_keys)} distinct dispatch keys, "
                 f"determinism_ok={determinism_ok}")

    # -- arm 3: fault-free baseline (warm too) ----------------------------
    pool_f = SessionPool(cfg)
    fault_free_s = run_streaming(pool_f, workload, low_water)
    lines.append(f"fault-free:   {fault_free_s:.2f}s, "
                 f"{_statuses(pool_f)}")

    # -- bit-exactness: chaos survivors vs the fault-free pool oracle -----
    mismatches = []
    checked = 0
    for sid in range(sessions):
        if pool_b.sessions[sid]["status"] not in ("converged",
                                                  "budget_exhausted"):
            continue
        r, o = pool_b.results[sid], pool_f.results[sid]
        checked += 1
        exact = (np.array_equal(np.asarray(r.classifier.w),
                                np.asarray(o.classifier.w))
                 and float(r.classifier.b) == float(o.classifier.b)
                 and r.converged == o.converged
                 and r.rounds == o.rounds
                 and r.comm == o.comm)
        if not exact:
            mismatches.append({"sid": sid, "arm": "chaos_vs_fault_free"})

    # -- engine cross-check: decision/comm parity vs run_instances --------
    insts = [ProtocolInstance(shards=s, eps=cfg.eps) for s in workload]
    oracle = run_instances(insts, n_angles=n_angles, max_epochs=max_epochs)
    engine_bitwise = 0
    for sid in range(sessions):
        r, o = pool_f.results[sid], oracle[sid]
        checked += 1
        if not (r.converged == o.converged and r.rounds == o.rounds
                and r.comm == o.comm
                and np.allclose(np.asarray(r.classifier.w),
                                np.asarray(o.classifier.w),
                                rtol=1e-5, atol=1e-6)
                and np.isclose(float(r.classifier.b),
                               float(o.classifier.b),
                               rtol=1e-5, atol=1e-6)):
            mismatches.append({"sid": sid, "arm": "fault_free_vs_engine"})
        elif (np.array_equal(np.asarray(r.classifier.w),
                             np.asarray(o.classifier.w))
              and float(r.classifier.b) == float(o.classifier.b)):
            engine_bitwise += 1
    lines.append(f"oracle: {checked} comparisons, "
                 f"{len(mismatches)} mismatches, "
                 f"{engine_bitwise}/{sessions} engine-bitwise")

    # -- mixed-traffic series: one unified pool vs three per-family pools -
    mixed_lines, mixed = mixed_traffic_series(tiny)
    lines += mixed_lines

    report = {
        "notes": NOTES,
        "tiny": tiny,
        "sessions": sessions,
        "slots": slots,
        "k": k,
        "n_pad": n_pad,
        "selector": cfg.selector,
        "n_angles": n_angles,
        "max_epochs": max_epochs,
        "schedule": chaos.to_json(),
        "statuses": stat_b,
        "stats": {kk: v for kk, v in pool_b.stats.items()
                  if isinstance(v, (int, float))},
        "fault_free_s": round(fault_free_s, 4),
        "faulted_s": round(faulted_s, 4),
        "sessions_per_s_fault_free": round(sessions / fault_free_s, 2),
        "sessions_per_s_faulted": round(sessions / faulted_s, 2),
        "steady_state_recompiles": int(steady_recompiles),
        "steady_state_dispatch_keys": [list(kk) for kk in steady_keys],
        "determinism_ok": bool(determinism_ok),
        "engine_bitwise": engine_bitwise,
        "oracle_checked": checked,
        "oracle_mismatches": mismatches,
        "mixed_traffic": mixed,
    }
    out = OUT.replace(".json", ".tiny.json") if tiny else OUT
    with open(out, "w") as f:
        json.dump(report, f, indent=1)
    lines.append(f"wrote {out}")
    return lines


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="CI chaos-smoke sizes (24 sessions, 8 slots)")
    args = ap.parse_args()
    compile_cache.enable()
    for line in main(tiny=args.tiny):
        print(line)
