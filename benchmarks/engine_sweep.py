"""Batched engine vs sequential per-instance sweeps (BENCH_engine.json).

The paper's experiment grids are sweeps of independent protocol instances;
the engine runs a whole sweep as one compiled dispatch.  This benchmark runs
the same ≥32-instance grid (dataset × ε × seed, two-party MEDIAN) three
ways:

  sequential  the public per-instance API in a Python loop — one engine
              dispatch per instance (B=1), the pre-batching execution model;
  batched     one ``repro.engine`` sweep on the hot path (fill-capped
              transcript reads + batch compaction via the shared
              ``engine.hotloop`` — the default);
  cold        the same sweep on the cold padded ``run_compiled`` model (one
              while_loop dispatch at worst-case shapes — the pre-hot-path
              engine, and the in-file baseline for the ``hot_vs_cold``
              acceptance series).

It asserts exact parity (converged flags + comm totals) between the batched
sweep and the engine's B=1 path, **bit-exact** parity (including the final
separator) between hot and cold, cross-checks the legacy float64 host loop
as a differential oracle, and records wall-clocks to BENCH_engine.json at
the repo root.

A fourth series times the **sharded** hot loop (DESIGN.md §sharded hot
loop): the same engine sweep with its leading B axis split over a 1-D
("data",) mesh with donated state buffers and the double-buffered host loop
— against the unchanged single-device hot path on a wide grid with an
engineered convergence tail.  ``--devices N`` (script mode only) sets
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` before jax
initializes; when imported (``benchmarks/run.py``) the series runs on
whatever devices the process already has.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import List

# --devices must take effect before jax initializes, so script-mode argument
# parsing happens *above* the repro imports.  Importers (benchmarks/run.py)
# skip this block and call main() with the process's existing devices.
_ARGS = None
if __name__ == "__main__":
    _ap = argparse.ArgumentParser()
    _ap.add_argument("--tiny", action="store_true",
                     help="CI smoke sizes (small shards, 1 repeat)")
    _ap.add_argument("--devices", type=int, default=8,
                     help="fake host devices for the sharded series "
                          "(sets XLA_FLAGS before jax init; default 8)")
    _ARGS = _ap.parse_args()
    if _ARGS.devices > 1 and "jax" not in sys.modules:
        os.environ.setdefault(
            "XLA_FLAGS",
            f"--xla_force_host_platform_device_count={_ARGS.devices}")

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

from repro import engine
from repro.core import datasets
from repro.core.protocols import kparty
from repro.launch import compile_cache
from repro.launch.mesh import make_data_mesh

from benchmarks import _timing as timing
from benchmarks.legacy_median import kparty_median_hostloop

N_ANGLES = 1024
MAX_EPOCHS = 32
# sharded series: wide grid, coarser angle net, engineered long tail
SHARDED_B = 12288
SHARDED_B_TINY = 64
SHARDED_N_ANGLES = 256
SHARDED_MAX_EPOCHS = 24
OUT = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "BENCH_engine.json")


def build_instances(n_per_node: int = 1000,
                    seeds=(0, 1, 2)) -> List[engine.ProtocolInstance]:
    """Two-party MEDIAN instances: 3 datasets × 4 ε × seeds."""
    insts = []
    for gen in (datasets.data1, datasets.data2, datasets.data3):
        for eps in (0.2, 0.1, 0.05, 0.025):
            for seed in seeds:
                insts.append(engine.ProtocolInstance(
                    gen(n_per_node=n_per_node, k=2, seed=seed), eps))
    return insts


def build_sharded_instances(B: int, n_per_node: int = 24,
                            noisy_every: int = 24,
                            noise: float = 0.1) -> List[engine.ProtocolInstance]:
    """Wide MEDIAN grid with an engineered convergence tail.

    Separable instances converge in one round, so a uniform grid never
    exercises the compacted tail where donation and the double-buffered
    loop pay off.  Every ``noisy_every``-th instance gets label noise and a
    sub-resolution ε (mistake budget ⌊0.02·48⌋ = 0 → never converges), so
    the sweep runs to max_epochs on a shrinking live set — the shape the
    sharded path is built for.
    """
    gens = (datasets.data1, datasets.data2, datasets.data3)
    insts = []
    for i in range(B):
        shards = gens[i % 3](n_per_node=n_per_node, k=2, seed=i)
        if i % noisy_every == 0:
            shards = datasets.add_label_noise(shards, noise, seed=i)
            eps = 0.02
        else:
            eps = (0.1, 0.05)[i % 2]
        insts.append(engine.ProtocolInstance(shards, eps))
    return insts


def _run_hostloop(insts):
    """The sequential loop the engine replaced: one host-side Python round
    loop per instance, a device round-trip per round."""
    return [kparty_median_hostloop(inst.shards, eps=inst.eps,
                                   max_epochs=MAX_EPOCHS, n_angles=N_ANGLES)
            for inst in insts]


def _run_engine_b1(insts):
    """Per-instance public API (engine with B=1), in a Python loop."""
    return [kparty.iterative_support_kparty(
                inst.shards, eps=inst.eps, max_epochs=MAX_EPOCHS,
                n_angles=N_ANGLES, selector="median")
            for inst in insts]


def _run_batched(insts, compact=True):
    return engine.run_instances(insts, n_angles=N_ANGLES,
                                max_epochs=MAX_EPOCHS, compact=compact)


def main(tiny: bool = False, devices: int = 8) -> List[str]:
    insts = build_instances(n_per_node=50, seeds=(0,)) if tiny \
        else build_instances()
    B = len(insts)

    # warm up every engine program shape (hot + cold padded, full B and B=1)
    # so the steady-state sweep cost is measured, then time everything on
    # the shared interleaved harness (see benchmarks/_timing.py for the
    # min-of-repeats / median-of-round-ratios rationale).
    _run_batched(insts)
    _run_batched(insts, compact=False)
    _run_engine_b1(insts[:1])

    repeats = 1 if tiny else 7
    series = {
        "seq": lambda: _run_hostloop(insts),
        "b1": lambda: _run_engine_b1(insts),
        "bat": lambda: _run_batched(insts),                # hot (default)
        "cold": lambda: _run_batched(insts, compact=False),
    }
    out, times = timing.interleaved(series, repeats)
    seq, t_seq = out["seq"], timing.tmin(times, "seq")
    b1, t_b1 = out["b1"], timing.tmin(times, "b1")
    bat, t_bat = out["bat"], timing.tmin(times, "bat")
    cold, t_cold = out["cold"], timing.tmin(times, "cold")

    def ratio(num, den):
        return timing.ratio(times, num, den)

    mismatches = []          # engine batched vs engine B=1 — must be exact
    legacy_disagree = []     # float64 host loop — differential oracle
    hot_cold_bad = []        # hot vs cold padded — must be bit-exact
    per_instance = []
    for i, (inst, rs, r1, rb, rc) in enumerate(zip(insts, seq, b1, bat,
                                                   cold)):
        X = np.concatenate([s[0] for s in inst.shards])
        y = np.concatenate([s[1] for s in inst.shards])
        err = float(np.mean(rb.classifier.predict(X) != y))
        ok = (r1.converged == rb.converged and r1.comm == rb.comm
              and r1.rounds == rb.rounds)
        if not ok:
            mismatches.append(i)
        if not (rs.converged == rb.converged
                and rs.comm["points"] == rb.comm["points"]):
            legacy_disagree.append(i)
        if not (rc.converged == rb.converged and rc.comm == rb.comm
                and rc.rounds == rb.rounds
                and np.array_equal(rc.classifier.w, rb.classifier.w)
                and rc.classifier.b == rb.classifier.b):
            hot_cold_bad.append(i)
        per_instance.append({
            "eps": inst.eps,
            "converged": bool(rb.converged),
            "rounds": rb.rounds,
            "points": rb.comm["points"],
            "global_err": err,
            "err_within_eps": bool(err <= inst.eps),
            "parity_b1": ok,
        })

    # ---- sharded series: mesh dispatch vs single-device hot path --------
    n_dev = max(1, min(devices, len(jax.devices())))
    mesh = make_data_mesh(n_dev)
    sh_insts = build_sharded_instances(SHARDED_B_TINY if tiny else SHARDED_B)
    B_sh = len(sh_insts)

    def _run_hot_wide():
        return engine.run_instances(sh_insts, n_angles=SHARDED_N_ANGLES,
                                    max_epochs=SHARDED_MAX_EPOCHS)

    def _run_sharded():
        return engine.run_instances(sh_insts, n_angles=SHARDED_N_ANGLES,
                                    max_epochs=SHARDED_MAX_EPOCHS, mesh=mesh)

    _run_hot_wide()          # warm both program sets (the wide grid walks
    _run_sharded()           # ~dozens of width buckets — compile once here)
    out_sh, times_sh = timing.interleaved(
        {"hot_wide": _run_hot_wide, "sharded": _run_sharded},
        1 if tiny else 3)
    hot_wide, shd = out_sh["hot_wide"], out_sh["sharded"]
    t_hot_wide = timing.tmin(times_sh, "hot_wide")
    t_shd = timing.tmin(times_sh, "sharded")
    sharded_bad = []         # sharded vs hot — must be bit-exact
    for i, (a, b) in enumerate(zip(shd, hot_wide)):
        if not (a.converged == b.converged and a.comm == b.comm
                and a.rounds == b.rounds
                and np.array_equal(a.classifier.w, b.classifier.w)
                and a.classifier.b == b.classifier.b):
            sharded_bad.append(i)
    speedup_sharded = timing.ratio(times_sh, "hot_wide", "sharded")

    speedup = ratio("seq", "bat")
    speedup_hot_cold = ratio("cold", "bat")
    report = {
        "notes": (
            "sequential_s = the pre-engine per-instance execution model "
            "(host-side Python round loop, device round-trip per round; "
            "benchmarks/legacy_median.py).  batched_s = one repro.engine "
            "sweep on the hot path (fill-capped transcript reads + batch "
            "compaction on the shared engine.hotloop — the default).  "
            "hot_vs_cold replays the cold padded while_loop model "
            "(run_instances(compact=False), the pre-hot-path engine) "
            "against it on the same grid — speedup_hot_vs_cold is the hot "
            "path's acceptance number, and hot_cold_mismatch_indices (bar: "
            "empty) lists instances whose comm/rounds/convergence or exact "
            "final separator differ (the MEDIAN compactions must be "
            "bit-exact, not merely decision-exact).  engine_b1_loop_s = "
            "the public per-instance API (engine at B=1) in a Python loop "
            "— itself compiled end-to-end, so on a CPU-only host it "
            "already captures most of the engine win; the batch axis pays "
            "off where per-dispatch overhead dominates (accelerators, many "
            "small instances).  sharded = the same hot loop with the B "
            "axis split over a ('data',) mesh (donated buffers + "
            "double-buffered dispatch, the mesh defaults) vs the unchanged "
            "single-device hot path, on a wide grid whose every "
            "24th instance carries label noise and a sub-resolution eps so "
            "the sweep runs a long compacted tail; sharded_mismatch_indices "
            "(bar: empty) holds the same bit-exactness standard.  On a "
            "single-core host the sharded win is donation (no full-state "
            "copy per tail turn) + per-shard locality, not parallelism.  "
            "Timings are minima of interleaved repeats on a warm cache."),
        "instances": B,
        "tiny": tiny,
        "n_angles": N_ANGLES,
        "max_epochs": MAX_EPOCHS,
        "sequential_s": round(t_seq, 4),       # legacy host round loop
        "batched_s": round(t_bat, 4),          # one hot engine sweep
        "speedup": round(speedup, 2),
        "engine_b1_loop_s": round(t_b1, 4),    # per-instance engine loop
        "speedup_vs_engine_b1": round(ratio("b1", "bat"), 2),
        "hot_vs_cold": {
            "hot_s": round(t_bat, 4),
            "cold_s": round(t_cold, 4),        # padded while_loop model
            "speedup": round(speedup_hot_cold, 2),
        },
        "speedup_hot_vs_cold": round(speedup_hot_cold, 2),
        "hot_cold_mismatch_indices": hot_cold_bad,
        "sharded": {
            "instances": B_sh,
            "n_devices": n_dev,
            "n_angles": SHARDED_N_ANGLES,
            "max_epochs": SHARDED_MAX_EPOCHS,
            "hot_s": round(t_hot_wide, 4),     # single-device hot path
            "sharded_s": round(t_shd, 4),      # mesh dispatch
            "speedup": round(speedup_sharded, 2),
        },
        "speedup_sharded_vs_hot": round(speedup_sharded, 2),
        "sharded_mismatch_indices": sharded_bad,
        "parity_b1_ok": not mismatches,
        "parity_b1_mismatch_indices": mismatches,
        "legacy_oracle_disagreements": legacy_disagree,
        "all_converged": all(p["converged"] for p in per_instance),
        "all_err_within_eps": all(p["err_within_eps"] for p in per_instance),
        "per_instance": per_instance,
    }
    # --tiny must never clobber the committed full-size acceptance record
    out = OUT.replace(".json", ".tiny.json") if tiny else OUT
    with open(out, "w") as f:
        json.dump(report, f, indent=1)

    print(f"engine sweep: {B} instances  sequential(host loop) {t_seq:.2f}s  "
          f"batched(hot) {t_bat:.3f}s  cold-padded {t_cold:.3f}s  "
          f"hot-vs-cold {speedup_hot_cold:.2f}x  "
          f"B=1-parity={'OK' if not mismatches else mismatches}")
    print(f"(engine B=1 loop {t_b1:.2f}s; legacy-oracle disagreements: "
          f"{legacy_disagree or 'none'}; hot-cold mismatches: "
          f"{hot_cold_bad or 'none'})")
    print(f"sharded sweep: {B_sh} instances on {n_dev} device(s)  "
          f"hot {t_hot_wide:.2f}s  sharded {t_shd:.2f}s  "
          f"{speedup_sharded:.2f}x  mismatches: {sharded_bad or 'none'}")
    print(f"wrote {out}")
    return [f"engine_sweep/batched,{t_bat * 1e6 / B:.0f},"
            f"speedup={speedup:.2f};instances={B};"
            f"hot_vs_cold={speedup_hot_cold:.2f}",
            f"engine_sweep/sequential,{t_seq * 1e6 / B:.0f},"
            f"parity_b1={'ok' if not mismatches else 'FAIL'}",
            f"engine_sweep/sharded,{t_shd * 1e6 / B_sh:.0f},"
            f"speedup_vs_hot={speedup_sharded:.2f};devices={n_dev};"
            f"instances={B_sh}"]


if __name__ == "__main__":
    compile_cache.enable()
    main(tiny=_ARGS.tiny, devices=_ARGS.devices)
