"""Batched engine MAXMARG vs the retired host loop (BENCH_maxmarg.json).

Counterpart of ``engine_sweep.py`` for the second compiled selector: the
same ≥12-instance paper-style grid (dataset × ε × seed, two-party MAXMARG)
runs three ways:

  sequential  the pre-engine execution model — a host-side Python round
              loop with one ``fit_max_margin`` device call per turn
              (benchmarks/legacy_maxmarg.py);
  engine B=1  the public per-instance API (engine at B=1) in a Python loop;
  batched     one ``repro.engine.maxmarg`` sweep on the hot path
              (warm-started, compacted refits — the default).

Two additional batched series isolate the hot path's layers (DESIGN.md
§warm-start & transcript compaction): ``batched_cold_padded_s`` replays the
pre-hot-path execution model (cold refits at worst-case padding, one
while_loop dispatch — the PR 2 number on this machine, and the ≥1.5×
acceptance baseline), and the ``warm_vs_cold`` / ``compacted_vs_padded``
series toggle one layer each.

It asserts exact parity (converged flags + comm totals + rounds) between
the batched sweep and the engine's B=1 path AND between warm and cold
execution, cross-checks the legacy host loop as a differential oracle, and
records wall-clocks to BENCH_maxmarg.json at the repo root.  ``--tiny``
shrinks the grid for the CI smoke job and writes BENCH_maxmarg.tiny.json
instead (same schema, including every warm/compaction field), so a smoke
run can never clobber the committed full-size acceptance record.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import List

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from repro import engine
from repro.core import datasets
from repro.core.protocols import kparty
from repro.launch import compile_cache

from benchmarks import _timing as timing
from benchmarks.legacy_maxmarg import kparty_maxmarg_hostloop

# MAXMARG converges in 1-4 epochs on every paper grid; a tight epoch bound
# keeps the engine's static transcript capacity (and with it the padded
# per-turn refit width n_max + cap) proportionate.  The sweep regime is the
# engine's target: many small-to-mid instances, where the host loop's
# per-instance fit dispatches dominate (BENCH notes).
MAX_EPOCHS = 8
MAX_SUPPORT = 4   # pinned and passed to all three execution models below
OUT = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "BENCH_maxmarg.json")


def build_instances(n_per_node: int = 128,
                    seeds=(0, 1, 2)) -> List[engine.ProtocolInstance]:
    """Two-party MAXMARG grid: 3 datasets × 3 ε × seeds (≥12 instances)."""
    insts = []
    for gen in (datasets.data1, datasets.data2, datasets.data3):
        for eps in (0.05, 0.02, 0.01):
            for seed in seeds:
                insts.append(engine.ProtocolInstance(
                    gen(n_per_node=n_per_node, k=2, seed=seed), eps,
                    "maxmarg"))
    return insts


def build_pn_instances(n_per_node: int = 100) -> List[engine.ProtocolInstance]:
    """k=4 multi-epoch grid for the per-node warm-carry series.  The k=2
    headline grid cannot exercise it (per-node adoption at k=2 provably
    implies termination, so the mechanism is statically skipped there);
    these mixed hard/easy partitions run ≥ 2 epochs and actually latch."""
    return [engine.ProtocolInstance(
                datasets.data_mixed_hardness(n_per_node=n_per_node, k=4,
                                             seed=0), eps, "maxmarg")
            for eps in (0.05, 0.02)]


def _run_hostloop(insts):
    """The sequential loop the engine replaced: one host-side Python round
    loop per instance, one solver dispatch per round."""
    return [kparty_maxmarg_hostloop(inst.shards, eps=inst.eps,
                                    max_epochs=MAX_EPOCHS,
                                    max_support=MAX_SUPPORT)
            for inst in insts]


def _run_engine_b1(insts):
    """Per-instance public API (engine with B=1), in a Python loop."""
    return [kparty.iterative_support_kparty(
                inst.shards, eps=inst.eps, max_epochs=MAX_EPOCHS,
                selector="maxmarg", max_support=MAX_SUPPORT)
            for inst in insts]


def _run_batched(insts, warm=True, compact=True, per_node=True):
    return engine.maxmarg.run_instances(insts, max_epochs=MAX_EPOCHS,
                                        max_support=MAX_SUPPORT,
                                        warm=warm, compact=compact,
                                        per_node=per_node)


def main(tiny: bool = False) -> List[str]:
    insts = build_instances(n_per_node=40, seeds=(0,)) if tiny \
        else build_instances()
    pn_insts = build_pn_instances(n_per_node=40 if tiny else 100)
    B = len(insts)

    # warm up every engine program shape (hot/cold × padded/compacted, B=1,
    # the k=4 per-node grid in all three warm modes) and the host loop's
    # solver cache, then time everything on the shared interleaved harness
    # (benchmarks/_timing.py).
    for w, c in ((True, True), (False, True), (False, False)):
        _run_batched(insts, warm=w, compact=c)
    for pn in (True, False):
        _run_batched(pn_insts, per_node=pn)
    _run_engine_b1(insts[:1])
    _run_hostloop(insts[:1])

    # the hot/cold batched dispatches are tens of ms — take enough repeats
    # that the recorded minima are stable against machine noise
    repeats = 1 if tiny else 15
    series = {
        "seq": lambda: _run_hostloop(insts),
        "b1": lambda: _run_engine_b1(insts),
        "bat": lambda: _run_batched(insts),               # hot: warm+compact
        "cold_c": lambda: _run_batched(insts, warm=False, compact=True),
        "cold_p": lambda: _run_batched(insts, warm=False, compact=False),
        # per-node-vs-single warm carries, on the k=4 multi-epoch grid
        # where the mechanism actually engages
        "pn": lambda: _run_batched(pn_insts),
        "pn_single": lambda: _run_batched(pn_insts, per_node=False),
    }
    out, times = timing.interleaved(series, repeats)
    seq, t_seq = out["seq"], timing.tmin(times, "seq")
    b1, t_b1 = out["b1"], timing.tmin(times, "b1")
    bat, t_bat = out["bat"], timing.tmin(times, "bat")
    cold_c, t_cold_c = out["cold_c"], timing.tmin(times, "cold_c")
    cold_p, t_cold_p = out["cold_p"], timing.tmin(times, "cold_p")
    pn_res, t_pn = out["pn"], timing.tmin(times, "pn")
    pn_single, t_pn_single = out["pn_single"], timing.tmin(times, "pn_single")

    def ratio(num, den):
        return timing.ratio(times, num, den)

    mismatches = []          # engine batched vs engine B=1 — must be exact
    legacy_disagree = []     # retired host loop — differential oracle
    warm_cold_bad = []       # warm vs cold decisions — must be exact
    per_node_bad = []        # per-node grid: both warm modes vs cold — exact
    pn_cold = _run_batched(pn_insts, warm=False, compact=False)
    for i, (rp, rn, rc) in enumerate(zip(pn_res, pn_single, pn_cold)):
        for r in (rp, rn):
            if not (r.converged == rc.converged and r.comm == rc.comm
                    and r.rounds == rc.rounds):
                per_node_bad.append(i)
                break
    per_instance = []
    for i, (inst, rs, r1, rb, rc) in enumerate(
            zip(insts, seq, b1, bat, cold_p)):
        X = np.concatenate([s[0] for s in inst.shards])
        y = np.concatenate([s[1] for s in inst.shards])
        err = float(np.mean(rb.classifier.predict(X) != y))
        ok = (r1.converged == rb.converged and r1.comm == rb.comm
              and r1.rounds == rb.rounds)
        if not ok:
            mismatches.append(i)
        if not (rs.converged == rb.converged and rs.comm == rb.comm
                and rs.rounds == rb.rounds):
            legacy_disagree.append(i)
        if not (rc.converged == rb.converged and rc.comm == rb.comm
                and rc.rounds == rb.rounds):
            warm_cold_bad.append(i)
        per_instance.append({
            "eps": inst.eps,
            "converged": bool(rb.converged),
            "rounds": rb.rounds,
            "points": rb.comm["points"],
            "bytes": rb.comm["bytes"],
            "global_err": err,
            "err_within_eps": bool(err <= inst.eps),
            "parity_b1": ok,
        })

    speedup = ratio("seq", "bat")
    speedup_cold_padded = ratio("cold_p", "bat")
    report = {
        "notes": (
            "sequential_s = the retired per-instance execution model for the "
            "MAXMARG selector (host-side Python round loop, one "
            "fit_max_margin dispatch per turn; benchmarks/legacy_maxmarg.py)."
            "  batched_s = the engine hot path for the whole sweep "
            "(warm-started refits + width/batch-compacted dispatches, "
            "repro.engine.maxmarg.run_hot).  batched_cold_padded_s replays "
            "the pre-hot-path engine (cold refits at worst-case padded "
            "width, one while_loop dispatch) — the PR 2 execution model on "
            "this machine, so speedup_vs_cold_padded is the hot path's "
            "acceptance number (bar: >= 1.5).  warm_vs_cold and "
            "compacted_vs_padded each toggle one hot-path layer at a time.  "
            "per_node_warm compares the default per-node warm-carry mode "
            "(each node polishes the last proposal it verified clean; "
            "latches_* total the solver's warm-gate hits) against the PR 4 "
            "single previous-turn carry, measured on a separate k=4 "
            "multi-epoch mixed-hardness grid "
            "(datasets.data_mixed_hardness) — per-node adoption at k=2 "
            "provably implies termination, so the headline grid cannot "
            "engage the mechanism.  engine_b1_loop_s = the public "
            "per-instance API (engine at B=1) "
            "in a Python loop.  legacy_oracle_disagreements, "
            "warm_cold_mismatch_indices and per_node_mismatch_indices list "
            "instances whose comm totals / "
            "rounds / convergence differ from the host-loop oracle resp. "
            "between warm modes — the acceptance bar is all "
            "empty.  Timings are minima of interleaved repeats on a warm "
            "cache (one-sided scheduler noise only inflates wall-clocks, "
            "and interleaving shows every series the same machine phases, "
            "stabilizing the recorded ratios)."),
        "instances": B,
        "tiny": tiny,
        "max_epochs": MAX_EPOCHS,
        "max_support": MAX_SUPPORT,
        "sequential_s": round(t_seq, 4),       # legacy host round loop
        "batched_s": round(t_bat, 4),          # hot path (the default)
        "speedup": round(speedup, 2),
        "engine_b1_loop_s": round(t_b1, 4),    # per-instance engine loop
        "speedup_vs_engine_b1": round(ratio("b1", "bat"), 2),
        "batched_cold_padded_s": round(t_cold_p, 4),   # PR 2 model
        "speedup_vs_cold_padded": round(speedup_cold_padded, 2),
        "warm_vs_cold": {
            "warm_s": round(t_bat, 4),
            "cold_s": round(t_cold_c, 4),      # compacted either way
            "speedup": round(ratio("cold_c", "bat"), 2),
        },
        "compacted_vs_padded": {
            "compacted_s": round(t_cold_c, 4),  # cold either way
            "padded_s": round(t_cold_p, 4),
            "speedup": round(ratio("cold_p", "cold_c"), 2),
        },
        "per_node_warm": {
            "instances": len(pn_insts),         # the k=4 multi-epoch grid
            "rounds": [r.rounds for r in pn_res],
            "per_node_s": round(t_pn, 4),       # default warm-carry mode
            "single_carry_s": round(t_pn_single, 4),
            "speedup": round(ratio("pn_single", "pn"), 2),
            "latches_per_node": sum(r.extra["warm_latches"] for r in pn_res),
            "latches_single_carry": sum(r.extra["warm_latches"]
                                        for r in pn_single),
        },
        "per_node_mismatch_indices": per_node_bad,
        "parity_b1_ok": not mismatches,
        "parity_b1_mismatch_indices": mismatches,
        "legacy_oracle_disagreements": legacy_disagree,
        "warm_cold_mismatch_indices": warm_cold_bad,
        "all_converged": all(p["converged"] for p in per_instance),
        "all_err_within_eps": all(p["err_within_eps"] for p in per_instance),
        "per_instance": per_instance,
    }
    out = OUT.replace(".json", ".tiny.json") if tiny else OUT
    with open(out, "w") as f:
        json.dump(report, f, indent=1)

    print(f"maxmarg sweep: {B} instances  sequential(host loop) {t_seq:.2f}s  "
          f"batched(hot) {t_bat:.3f}s  cold-padded {t_cold_p:.3f}s  "
          f"hot-vs-PR2 {report['speedup_vs_cold_padded']:.2f}x  "
          f"B=1-parity={'OK' if not mismatches else mismatches}")
    print(f"(engine B=1 loop {t_b1:.2f}s; legacy-oracle disagreements: "
          f"{legacy_disagree or 'none'}; warm-cold mismatches: "
          f"{warm_cold_bad or 'none'})")
    print(f"wrote {out}")
    return [f"maxmarg_sweep/batched,{t_bat * 1e6 / B:.0f},"
            f"speedup={speedup:.2f};instances={B};"
            f"hot_vs_cold_padded={report['speedup_vs_cold_padded']:.2f}",
            f"maxmarg_sweep/sequential,{t_seq * 1e6 / B:.0f},"
            f"parity_b1={'ok' if not mismatches else 'FAIL'}"]


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke sizes (small shards, 1 repeat)")
    compile_cache.enable()
    main(tiny=ap.parse_args().tiny)
